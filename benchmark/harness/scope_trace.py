"""Device time by program scope, from the profiler's trace alone.

The program names its parts with `jax.named_scope`
(`nanorlhf_tpu/utils/profiling.py::DEVICE_SCOPES`: `prefill`, `decode`,
`attn.read`, `mlp`, `head`, ...). The scope is in no event that
`jax.profiler.ProfileData` returns: an event of the op line is named by its
HLO line without the metadata (harness/moe_trace.py). But the `.xplane.pb`
carries a plane `/host:metadata` whose `event_metadata` holds, for each
program that ran, a stat `Hlo Proto`: the OPTIMISED module, serialized, in
which every instruction has `metadata.op_name`, the scope path
(`jit(f)/decode/while/body/mlp/dot_general`). `ProfileData` shows that plane
with no line, so it is decoded here by hand: varints and length-delimited
fields only, every other plane skipped by its length
(tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto).

- `op_scopes(path)`: `{program: {"op_name": {instruction: op_name},
  "fused": {fusion: [(opcode, op_name) of its fused computation]},
  "body": {instruction: the computation it stands in}}}`. A program is named
  as the `XLA Modules` line names its events: `jit_f(5)`.
- `scope_seconds(path)`: the self time (`xplane.self_times`, mean over the
  device planes) of every event of the op line, joined to its instruction
  inside the module the event falls in, keyed by the scope path cut to the
  vocabulary: `jit(..)`, `while`, `body`, `cond`, `checkpoint`,
  `closed_call`, every other transform and the primitive's name go;
  `jvp(x)` and `transpose(jvp(x))` are `x`, and a path with a `transpose`
  ends in ` bwd`. A fusion takes the scope of the dot or convolution inside
  its fused computation where it has one (its time is that matmul's), else
  its own, which is its root's.
- decode steps are COUNTED FROM THE TRACE: in each computation (a loop's
  body) the events of the costliest instruction under `decode` .. `head`
  (it runs once a step), summed over the computations.
- `table(run)`: the readers' way in. They get only `run`: the trace is the
  newest one under `<benchmark>/out/<run["cell"]>/trace`, reduced once and
  kept in `run["scope_trace"]`.

The vocabulary is the program's, copied: this file also runs over a parent
commit that has no `DEVICE_SCOPES` (tests/test_device_scopes.py holds the
two equal).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import time

from harness import xplane

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
MODULE_LINE = "XLA Modules"
TOP = 20
SCOPES = frozenset((
    "prefill", "decode", "verify", "install", "score", "update", "sync",
    "embed", "norm", "attn", "mlp", "head", "sample", "logprob", "loss",
    "optim"))
SCOPE_FAMILIES = ("attn.", "moe.", "mla.")      # attn.read, moe.experts, ...
BACKWARD = " bwd"
MATMULS = ("dot", "convolution")
TRANSFORM = re.compile(r"^(jvp|transpose|vmap)\((.*)\)$")
INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


# ---------------------------------------------------------------- wire format

def _varint(buf, i: int) -> tuple:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf, start: int, end: int):
    """`(number, value)` of one message's fields: an int for a varint or a
    fixed field, `(start, end)` for a length-delimited one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _first(buf, span, number: int):
    return next((v for n, v in _fields(buf, *span) if n == number), None)


def _ints(buf, value) -> list:
    """A repeated int64 field's values: packed, or one at a time."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _map_values(buf, plane, number: int):
    """The values of a `map<int64, Message>` field of a plane."""
    for n, entry in _fields(buf, *plane):
        if n == number:
            value = _first(buf, entry, 2)
            if value is not None:
                yield value


# ------------------------------------------------------------- the op table

def _program(buf, hlo_proto) -> dict:
    """HloProto.hlo_module (1) -> computations (3) -> instructions (2):
    name 1, opcode 2, metadata 7 (op_name 2), called_computation_ids 38."""
    module = _first(buf, hlo_proto, 1)
    op_name, body, calls, opcode, by_id = {}, {}, {}, {}, {}
    for n, comp in _fields(buf, *module) if module else ():
        if n != 3:
            continue
        comp_name, comp_id, names = "", None, []
        for m, v in _fields(buf, *comp):
            if m == 1:
                comp_name = _text(buf, v)
            elif m == 5:
                comp_id = v
            elif m == 2:
                name = scope = code = ""
                called = []
                for k, w in _fields(buf, *v):
                    if k == 1:
                        name = _text(buf, w)
                    elif k == 2:
                        code = _text(buf, w)
                    elif k == 7:
                        at = _first(buf, w, 2)
                        scope = _text(buf, at) if at else ""
                    elif k == 38:
                        called += _ints(buf, w)
                names.append(name)
                op_name[name], opcode[name] = scope, code
                if code == "fusion" and called:
                    calls[name] = called[0]
        by_id[comp_id] = names
        body.update(dict.fromkeys(names, comp_name))
    fused = {f: [(opcode[i], op_name[i]) for i in by_id.get(c, ())]
             for f, c in calls.items()}
    return {"op_name": op_name, "fused": fused, "body": body}


def op_scopes(path: str) -> dict:
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for n, plane in _fields(buf, 0, len(buf)):
        if n != 1:          # XSpace.planes
            continue
        name = _first(buf, plane, 2)
        if name is None or _text(buf, name) != METADATA_PLANE:
            continue
        stat_names = {_first(buf, m, 1): _text(buf, _first(buf, m, 2))
                      for m in _map_values(buf, plane, 5)}
        for meta in _map_values(buf, plane, 4):
            program, proto = None, None
            for k, v in _fields(buf, *meta):
                if k == 2:
                    program = _text(buf, v)
                elif k == 5 and stat_names.get(_first(buf, v, 1)) == HLO_STAT:
                    proto = _first(buf, v, 6)       # XStat.bytes_value
            if program and proto:
                out[program] = _program(buf, proto)
    return out


# -------------------------------------------------------------- the scopes

def scope_of(op_name: str) -> str:
    """`jit(f)/decode/while/body/closed_call/mlp/dot_general` -> `decode/mlp`;
    "" where the path holds no scope of the vocabulary."""
    kept, backward = [], False
    for part in op_name.split("/"):
        m = TRANSFORM.match(part)
        while m:
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
            m = TRANSFORM.match(part)
        # (jax also puts a function's name after a nested jit's: a scope
        # met twice running is one, `prefill/jit(cumsum)/prefill/..`)
        if (part in SCOPES or part.startswith(SCOPE_FAMILIES)) \
                and kept[-1:] != [part]:
            kept.append(part)
    return "/".join(kept) + (BACKWARD if backward and kept else "")


def instruction_scope(program: dict, name: str) -> str | None:
    """The scope an instruction's time goes to; None: not in the table."""
    own = program["op_name"].get(name)
    if own is None:
        return None
    inside = [s for code, s in program["fused"].get(name, ())
              if code in MATMULS and scope_of(s)]
    return scope_of(inside[0]) if inside else scope_of(own)


def under(scope: str, first: str) -> bool:
    return scope == first or scope.startswith(first + "/") or \
        scope.startswith(first + BACKWARD)


def has(scope: str, part: str) -> bool:
    """`part` or one of its family (`attn` takes `attn.read`, `attn.global`)
    is a step of the path."""
    return any(p == part or p.startswith(part + ".")
               for p in scope.removesuffix(BACKWARD).split("/"))


def scope_seconds_of(data, table: dict) -> dict:
    """`data`: a `jax.profiler.ProfileData`; `table`: `op_scopes` of its file."""
    cells: dict = {}      # (program, event name) -> [self seconds, events]
    calls: dict = {}      # program -> events of the module line
    n_planes = 0
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for ln in lines if ln.name == MODULE_LINE
                         for e in ln.events)
        starts = [m[0] for m in modules]
        for _, _, name in modules:
            calls[name] = calls.get(name, 0) + 1
        chosen = [ln for ln in lines if ln.name == xplane.OP_LINE] or [
            ln for ln in lines if ln.name not in xplane.SKIP_LINES]
        seen = False
        for ln in chosen:
            events = [(e.name, e.start_ns, e.duration_ns) for e in ln.events]
            seen = seen or bool(events)
            selfs = xplane.self_times(
                [(i, s, d) for i, (_, s, d) in enumerate(events)])
            for i, (name, start, _) in enumerate(events):
                at = bisect.bisect_right(starts, start) - 1
                inside = at >= 0 and start < modules[at][1]
                cell = cells.setdefault(
                    (modules[at][2] if inside else None, name), [0.0, 0])
                cell[0] += selfs.get(i, 0.0)
                cell[1] += 1
        n_planes += seen
    n_planes = max(n_planes, 1)

    by_scope, by_program, by_op, heads = {}, {}, {}, {}
    unscoped = unjoined = total = 0.0
    for (module, name), (sec, count) in cells.items():
        sec, count = sec / n_planes, count / n_planes
        total += sec
        program = table.get(module)     # both name a program `jit_f(<id>)`
        head = INSTRUCTION.match(name)
        instr = head.group(1) if head else name
        scope = instruction_scope(program, instr) if program else None
        if scope is None:
            unjoined += sec
        if not scope:
            unscoped += sec
        else:
            by_scope[scope] = by_scope.get(scope, 0.0) + sec
            if under(scope, "decode") and has(scope, "head"):
                body = (module, program["body"].get(instr))
                if sec > heads.get(body, (0.0, 0))[0]:
                    heads[body] = (sec, count)
        if module is not None:
            p = by_program.setdefault(
                module, {"seconds": 0.0, "calls": calls[module] / n_planes,
                         "scopes": {}})
            p["seconds"] += sec
            first = scope.removesuffix(BACKWARD).split("/")[0] if scope else ""
            p["scopes"][first] = p["scopes"].get(first, 0.0) + sec
        key = (scope or "", xplane.short_name(name))
        by_op[key] = by_op.get(key, 0.0) + sec
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    return {"by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "by_program": by_program, "busy_s": total, "unscoped_s": unscoped,
            "unjoined_s": unjoined,
            "ops": [[scope, name, sec] for (scope, name), sec in ops[:TOP]],
            "unscoped_ops": [[name, sec] for (scope, name), sec in ops
                             if not scope][:TOP // 2],
            "steps": sum(count for _, count in heads.values()),
            "programs_in_table": len(table)}


def scope_seconds(path: str) -> dict:
    from jax.profiler import ProfileData

    return scope_seconds_of(ProfileData.from_file(path), op_scopes(path))


# ------------------------------------------------------------- the readers

def seconds_under(table: dict, first: str) -> float:
    """Self seconds of the scopes under `first`, forward and backward."""
    return sum(sec for scope, sec in table["by_scope"].items()
               if under(scope, first))


def share_of_decode(run: dict, *parts: str):
    """Self seconds of `decode`'s scopes with one of `parts` (or one of its
    family) on their path over all of `decode`'s, in %; None where the run
    has no such table."""
    t = table(run)
    decode = seconds_under(t, "decode") if t else 0.0
    if not decode:
        return None
    return 100.0 * sum(sec for scope, sec in t["by_scope"].items()
                       if under(scope, "decode")
                       and any(has(scope, p) for p in parts)) / decode


def calls_with(table: dict, first: str) -> float:
    """Calls of the programs that spent time under `first`."""
    return sum(p["calls"] for p in table["by_program"].values()
               if p["scopes"].get(first))


def table(run: dict):
    """The run's scope table, reduced at the first call and kept in
    `run["scope_trace"]` (so `run.json` holds it); None where the run left
    no trace. The trace is the newest under `<benchmark>/out/<cell>/trace`,
    where the drivers write it."""
    if "scope_trace" not in run:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = xplane.newest_xplane(
            os.path.join(here, "out", str(run.get("cell")), "trace"))
        reduced = None
        if run.get("trace") is not None and path is not None:
            t0 = time.time()
            reduced = scope_seconds(path)
            reduced["reduce_s"] = time.time() - t0
            print(json.dumps({"phase": "scopes", **reduced}), flush=True)
        run["scope_trace"] = reduced
    return run["scope_trace"]
