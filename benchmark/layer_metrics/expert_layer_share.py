"""model, expert layer: self time of the device ops of the sparse-expert MLP
(the grouped matmuls, by their kernel's name, the ops that feed them and the
ops that read them: harness/moe_trace.py) over the device's busy time in the
traced update, in %. Nothing where the trace has no grouped matmul."""


def read(run):
    tr, moe = run.get("trace"), run.get("moe_trace")
    if not tr or not tr["busy_s"] or not moe or not moe["moe_s"]:
        return None
    return 100.0 * moe["moe_s"] / tr["busy_s"]
