"""Device time of the sparse-expert layer, from the profiler's trace.

`nanorlhf_tpu/ops/moe.py` runs its parts under `jax.named_scope("moe.*")`,
but a TPU trace read through `jax.profiler.ProfileData` does not carry the
scope: an event of the op line is named by its HLO line WITHOUT the metadata
(`%gmm.24 = bf16[512,1024]{...} custom-call(..., bf16[512,2048]{...}
%fusion.587, bf16[256,2048,1024]{...} %bitcast.561), ...`) and its stats are
offsets and durations only (read on a real trace, PERF.md PR 27). So the
layer's ops are named by what the HLO line does say:

- `moe.experts`: the grouped matmuls, by the kernel's name: `%gmm*` (the
  megablox Pallas kernel) or `%ragged-dot-none*` (XLA's own), and any op
  that both feeds one and reads one (the SwiGLU between them);
- `moe.dispatch`: the other ops whose result a grouped matmul takes as an
  operand (the gather of the sorted rows, the group metadata);
- `moe.combine`: the other ops that take a grouped matmul's result as an
  operand (the gather back to token order and the weighted sum).

One hop of dataflow, inside one module (an op belongs to the event of the
`XLA Modules` line that contains it; names repeat between modules). The
router's matmul, softmax, top-k and sort are two hops away and are not
counted. Self times as harness/xplane.py has them, means over the device
planes. A trace without a grouped matmul (the parent of PR 27, a dense model)
gives empty tables, and the readers then return nothing.
"""

from __future__ import annotations

import bisect
import re

from harness import xplane

GROUPED_MATMUL = re.compile(r"^%(gmm|ragged-dot-none)[\w.\-]* = ")
KERNEL = re.compile(r"^%gmm[\w.\-]* = ")          # the Pallas kernel alone
NAME = re.compile(r"%[\w.\-]+")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")
MODULE_LINE = "XLA Modules"


def _labels(lines_by_name: dict) -> dict:
    """{op name: scope} for one module's distinct HLO lines."""
    operands = {n: set(NAME.findall(ln.split("(", 1)[1] if "(" in ln else ""))
                for n, ln in lines_by_name.items()}
    experts = {n for n, ln in lines_by_name.items() if GROUPED_MATMUL.match(ln)}
    feeds = {o for e in experts for o in operands[e] if o in lines_by_name}
    reads = {n for n, ops in operands.items() if ops & experts}
    labels = {n: "moe.experts" for n in experts | (feeds & reads)}
    labels.update({n: "moe.dispatch" for n in feeds - reads - experts})
    labels.update({n: "moe.combine" for n in reads - feeds - experts})
    return labels


def matmul_shape(line: str):
    """(M, K, N) of a grouped matmul's HLO line: result [M, N], rows [M, K]."""
    result = [int(x) for x in SHAPE.search(line).group(1).split(",")]
    m, n = result
    for dims in SHAPE.findall(line.split("(", 1)[1]):
        d = [int(x) for x in dims.split(",") if x]
        if len(d) == 2 and d[0] == m:
            return m, d[1], n
    return None


def scope_seconds_of(data) -> dict:
    """{"by_scope": {scope: self seconds}, "ops": the ten largest ops with
    their scope, "moe_s": the sum, "kernel": [{m, k, n, events, seconds}] of
    the Pallas grouped matmul by shape}; seconds are means over the planes."""
    by_scope, by_op, kernel, n_planes = {}, {}, {}, 0
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns)
                         for ln in lines if ln.name == MODULE_LINE
                         for e in ln.events)
        starts = [m[0] for m in modules]
        chosen = [ln for ln in lines if ln.name == xplane.OP_LINE] or [
            ln for ln in lines if ln.name not in xplane.SKIP_LINES]
        seen = False
        for ln in chosen:
            events = list(ln.events)
            seen = seen or bool(events)
            selfs = xplane.self_times(
                [(i, e.start_ns, e.duration_ns) for i, e in enumerate(events)])
            per_module: dict = {}
            for i, e in enumerate(events):
                at = bisect.bisect_right(starts, e.start_ns) - 1
                inside = at >= 0 and e.start_ns < modules[at][1]
                head = NAME.match(e.name)
                if head:
                    per_module.setdefault(at if inside else -1, {}).setdefault(
                        head.group(0), []).append(i)
            for names in per_module.values():
                labels = _labels({n: events[ids[0]].name for n, ids in names.items()})
                for n, scope in labels.items():
                    line = events[names[n][0]].name
                    sec = sum(selfs.get(i, 0.0) for i in names[n])
                    by_scope[scope] = by_scope.get(scope, 0.0) + sec
                    key = scope + " " + xplane.short_name(line)
                    by_op[key] = by_op.get(key, 0.0) + sec
                    if KERNEL.match(line) and matmul_shape(line):
                        k = kernel.setdefault(matmul_shape(line), [0, 0.0])
                        k[0] += len(names[n])
                        k[1] += sec
        n_planes += seen
    n_planes = max(n_planes, 1)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:xplane.TOP]
    return {"by_scope": {k: v / n_planes for k, v in by_scope.items()},
            "ops": [[k, v / n_planes] for k, v in ops],
            "moe_s": sum(by_scope.values()) / n_planes,
            "kernel": [{"m": m, "k": k, "n": n, "events": c / n_planes,
                        "seconds": s / n_planes}
                       for (m, k, n), (c, s) in sorted(kernel.items())]}


def scope_seconds(path: str) -> dict:
    from jax.profiler import ProfileData

    return scope_seconds_of(ProfileData.from_file(path))
