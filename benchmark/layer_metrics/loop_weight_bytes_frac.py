"""model: the weights' share of the bytes a looped model's decode step must
move (harness/ops_bytes_ouro.decode_step_bytes: the stack read once a pass
against the head, the live rows' K and V over every cache layer and the
writes), from the window's own counts of live rows and slots a step, in %:
which of the two reads sets the step. Nothing where the configuration has no
loop or the program no such counters."""

from layer_metrics.ouro_decode_roofline import step_bytes
from layer_metrics.ouro_decode_step_ms import looped


def read(run):
    if not looped(run):
        return None
    b = step_bytes(run, "counters")
    return None if b is None else 100.0 * b["weights"] / b["total"]
