"""kernels, MiniCPM-SALA's whole decode step: the bytes one step must move
(harness/ops_bytes_sala.decode_step_bytes: every layer's weights, a sparse
layer's CHOSEN slots of K and V and the selecting rows' compressed keys, the
LIVE rows' lightning state read and written once, the head and f32 logits)
over the HBM bandwidth of peaks.json, divided by the step's time
(`sala_decode_step_ms`), in %. The counts are the window's own, a step:
`serving/sparse_slots_read`, `serving/sparse_slots_held` and
`serving/global_slots_read` (counted on the host) over
`serving/decode_steps`; live rows are the mean of the window's snapshots.
The step's time holds its share of the beat's prefill piece, so the share
reads low under long prompts: a step-level share of the memory roofline,
not a kernel's."""

from harness import ops_bytes_sala as ob
from layer_metrics import sala_decode_step_ms
from layer_metrics.queue_wait_ms import ratio


def floor_ms(run):
    snaps = run.get("snapshots") or []
    rows = sum(s["active"] for s in snaps) / len(snaps) if snaps else None
    per = lambda key: ratio(run, key, "serving/decode_steps", 1.0)   # noqa: E731
    read, held, every = (per("serving/sparse_slots_read"),
                         per("serving/sparse_slots_held"),
                         per("serving/global_slots_read"))
    if not rows or None in (read, held, every):
        return None
    b = ob.decode_step_bytes(run["config"], rows=rows, slots_read=read,
                             slots_held=held, dense_slots=every - held)
    return 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])


def read(run):
    if "mixer_types" not in (run.get("config") or {}):
        return None
    step_ms = sala_decode_step_ms.read(run)
    if not step_ms:
        return None
    floor = floor_ms(run)
    return None if floor is None else 100.0 * floor / step_ms
