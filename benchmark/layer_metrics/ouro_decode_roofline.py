"""kernels, a looped model's decode step against the HBM bandwidth: the bytes
one step must move (harness/ops_bytes_ouro.decode_step_bytes: the stack's
weights once A PASS, the head once, the K and V of the live rows' slots over
every cache layer, the writes) over the bandwidth of peaks.json, divided by
the DEVICE's seconds a step (`decode_device_step_ms`), in %. The counts are
the program's own inside the traced seconds (`traced_counters`), a step: live
rows (`serving/live_row_steps`) and slots held (`serving/global_slots_read`),
each over `serving/decode_steps`. Nothing where the run has no scope table,
the program no such counters, or the configuration no loop."""

from harness import ops_bytes_ouro as ob
from layer_metrics import decode_device_step_ms
from layer_metrics.ouro_decode_step_ms import looped


def per_step(run, key, which="traced_counters"):
    """`key`'s gain a decode step between two readings of the counters."""
    counters = run.get(which)
    if which == "counters" and counters:
        counters = [counters.get("start"), counters.get("end")]
    if not counters or len(counters) != 2 or None in counters:
        return None
    before, after = counters
    try:
        steps = after["serving/decode_steps"] - before["serving/decode_steps"]
        return (after[key] - before[key]) / steps if steps > 0 else None
    except KeyError:
        return None


def step_bytes(run, which="traced_counters"):
    rows = per_step(run, "serving/live_row_steps", which)
    slots = per_step(run, "serving/global_slots_read", which)
    if rows is None or slots is None:
        return None
    return ob.decode_step_bytes(run["config"], rows=rows, slots=slots)


def read(run):
    if not looped(run):
        return None
    step_ms = decode_device_step_ms.read(run)
    b = step_bytes(run)
    if not step_ms or b is None:
        return None
    floor_ms = 1e3 * b["total"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_ms / step_ms
