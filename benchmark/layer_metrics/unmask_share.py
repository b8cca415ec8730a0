"""model, generation by blocks: of the device's self seconds under `decode`
(a block forward), those of the scope `sample.unmask`: the choice of the
positions to unmask from the confidences, the block's and the row's state
and counters, in %. The scope sits inside `sample`, whose family the
harness's own reduction does not keep: the cell's driver reduces the trace
with it kept (drivers/serve_block_ref.run). Nothing where the table has no
such scope."""

from harness import scope_trace


def read(run):
    t = scope_trace.table(run)
    if not t:
        return None
    decode = scope_trace.seconds_under(t, "decode")
    unmask = sum(sec for scope, sec in t["by_scope"].items()
                 if scope_trace.under(scope, "decode")
                 and "sample.unmask" in scope.split("/"))
    return 100.0 * unmask / decode if decode and unmask else None
