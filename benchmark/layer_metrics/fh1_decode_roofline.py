"""kernels, the model whose every layer keeps pages and a state: the bytes
one decode step must move (harness/ops_bytes_falcon_h1.decode_step_bytes:
every layer's attention, mixer and MLP weights, the K and V slots inside the
bounds, the LIVE rows' state read and written once, the head and f32 logits)
over the HBM bandwidth of peaks.json, divided by the step's time
(`fh1_decode_step_ms`), in %. The counts are the window's own, a step: slots
read in a layer (`serving/global_slots_read`, counted on the host, over
`serving/decode_steps`); live rows are the mean of the window's
snapshots. The step's time holds its share of the beat's prefill piece, so
the share reads low under long prompts, and the program passes over the
state of EVERY resident row where the floor counts the live ones: a
step-level share of the memory roofline, not a kernel's."""

from harness import ops_bytes_falcon_h1 as ob
from layer_metrics import fh1_decode_step_ms
from layer_metrics.queue_wait_ms import ratio


def floor_ms(run):
    snaps = run.get("snapshots") or []
    rows = sum(s["active"] for s in snaps) / len(snaps) if snaps else None
    slots = ratio(run, "serving/global_slots_read", "serving/decode_steps", 1.0)
    if not rows or slots is None:
        return None
    cfg = run["config"]
    b = ob.decode_step_bytes(cfg, rows=rows, slots=slots)
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    step_ms = fh1_decode_step_ms.read(run)
    if not step_ms:
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
