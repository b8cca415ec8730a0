"""kernels: a looped model's admission forward against the bf16 peak: the
operations of the tokens the admissions dispatched inside the traced seconds
(`serving/prefill_token_dispatch`, a bucket's pad slots too: the device ran
them; over `serving/admitted` forwards, an admission being one forward where
nothing is chunked: harness/ops_bytes_ouro.
admission_flops, every kernel a token a PASS, causal attention, the head
once a forward), at the peak of peaks.json or at the bandwidth the weights'
one read a pass needs, whichever is longer, over the device's self seconds
under `prefill` in the trace, in %. Nothing where the trace holds no
admission or the program no such counters."""

from harness import ops_bytes_ouro as ob
from harness import scope_trace
from layer_metrics.fh1_ssm_update_roofline import traced
from layer_metrics.ouro_decode_step_ms import looped


def read(run):
    if not looped(run):
        return None
    gains = traced(run, "serving/prefill_token_dispatch", "serving/admitted")
    t = scope_trace.table(run)
    if not gains or not t or gains[0] <= 0 or gains[1] <= 0:
        return None
    spent = scope_trace.seconds_under(t, "prefill")
    if not spent:
        return None
    least = ob.admission_floor_s(run["config"], run["peaks"],
                                 tokens=gains[0], forwards=gains[1])
    return 100.0 * least / (run["chips"] * spent)
