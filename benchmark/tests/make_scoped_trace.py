"""Writes `data/scoped.xplane.pb`: a small trace WITH the plane
`/host:metadata` and KNOWN numbers, for harness/scope_trace.py. Encoded by
hand with make_small_trace.py's encoders (XSpace: tsl/profiler/protobuf/
xplane.proto; the programs: xla/service/hlo.proto).

Three programs, named as the module line names them:

  jit_serving_chunk(11): entry `main.1` holds `while.2` (op_name
    `jit(chunk)/decode/while`), whose body `body.3` runs, a step:
      fusion.10   100 us  decode/../attn/attn.read/dot_general
      fusion.11   150 us  ROOT .../decode/../mlp/add, but its fused
                          computation holds a dot of .../attn/attn.out:
                          the time is that matmul's
      fusion.16   300 us  decode/../mlp/moe.experts/...
      fusion.12   200 us  decode/../head/dot_general   (once a step)
      fusion.13    10 us  decode/../head/mul           (also once a step)
      fusion.14    30 us  decode/../sample/sort
      copy.15      10 us  no op_name
    a call is two steps (1,600 us) inside a `while.2` event of 1,650 us.
  jit_suffix_logits(22), 300 us a call:
      fusion.1      200 us  prefill/../mlp/dot_general
      fusion.2       80 us  prefill/../attn/attn.global/attn.write/scatter
      attn.global.3  20 us  prefill/../attn/attn.global/pallas_call
  jit_update_minibatch(33), 500 us:
      fusion.1    400 us  update/transpose(jvp(mlp))/dot_general: ANOTHER
                          `fusion.1` than the prefill's, and a backward op
      fusion.5    100 us  update/optim/add

Module line, in us: chunk 0..1650, suffix 1700..2000, suffix 2100..2400,
chunk 2500..4150, update 4200..4700; one op, `copy.99`, at 4800..4810 lies in
no module. Both device planes carry the same events, so means are sums.

Known results (us): busy 2 x 1650 + 2 x 300 + 500 + 10 = 4410;
decode/attn/attn.read 400, decode/attn/attn.out 600, decode/mlp/moe.experts
1200, decode/head 840, decode/sample 120, decode 100 (the whiles' own), so
`decode` 3260 over 4 steps = 815 a step; prefill 600 over 2 calls;
`update/mlp bwd` 400, update/optim 100; unscoped 40 + 10 = 50, of which the
10 joined nothing.
"""

import os

from make_small_trace import event, field, line, varint

METADATA_PLANE = "/host:metadata"


def instruction(name: str, opcode: str, op_name: str = "",
                calls: int | None = None) -> bytes:
    out = field(1, name) + field(2, opcode)
    if op_name:
        out += field(7, field(2, op_name))
    if calls is not None:       # called_computation_ids: packed int64
        out += field(38, varint(calls))
    return out


def computation(comp_id: int, name: str, instructions) -> bytes:
    return field(1, name) + b"".join(field(2, i) for i in instructions) + \
        field(5, comp_id)


def hlo_proto(name: str, computations) -> bytes:
    return field(1, field(1, name) + b"".join(
        field(3, c) for c in computations))


CHUNK = "jit(chunk)/decode/while/body/closed_call/"
SUFFIX = "jit(suffix_logits)/prefill/while/body/"
PROGRAMS = {
    "jit_serving_chunk(11)": hlo_proto("jit_serving_chunk", [
        computation(1, "main.1", [
            instruction("p.0", "parameter"),
            instruction("while.2", "while", "jit(chunk)/decode/while")]),
        computation(3, "body.3", [
            instruction("fusion.10", "fusion",
                        CHUNK + "attn/attn.read/dot_general", calls=4),
            instruction("fusion.11", "fusion", CHUNK + "mlp/add", calls=5),
            instruction("fusion.16", "fusion",
                        CHUNK + "mlp/moe.experts/ragged_dot", calls=6),
            instruction("fusion.12", "fusion", CHUNK + "head/dot_general",
                        calls=7),
            instruction("fusion.13", "fusion", CHUNK + "head/mul"),
            instruction("fusion.14", "fusion", CHUNK + "sample/sort"),
            instruction("copy.15", "copy")]),
        computation(4, "fused.10", [
            instruction("dot.40", "dot", CHUNK + "attn/attn.read/dot_general")]),
        computation(5, "fused.11", [
            instruction("p.50", "parameter"),
            instruction("dot.51", "dot", CHUNK + "attn/attn.out/dot_general"),
            instruction("add.52", "add", CHUNK + "mlp/add")]),
        computation(6, "fused.16", [
            instruction("mul.60", "multiply",
                        CHUNK + "mlp/moe.experts/mul")]),
        computation(7, "fused.12", [
            instruction("dot.70", "dot", CHUNK + "head/dot_general")]),
    ]),
    "jit_suffix_logits(22)": hlo_proto("jit_suffix_logits", [
        computation(1, "main.1", [
            instruction("fusion.1", "fusion", SUFFIX + "mlp/dot_general",
                        calls=2),
            instruction("fusion.2", "fusion",
                        SUFFIX + "attn/attn.global/attn.write/scatter"),
            instruction("attn.global.3", "custom-call",
                        SUFFIX + "attn/attn.global/pallas_call")]),
        computation(2, "fused.1", [
            instruction("dot.20", "dot", SUFFIX + "mlp/dot_general")]),
    ]),
    "jit_update_minibatch(33)": hlo_proto("jit_update_minibatch", [
        computation(1, "main.1", [
            instruction(
                "fusion.1", "fusion",
                "jit(update_minibatch)/update/transpose(jvp(mlp))/dot_general"),
            instruction("fusion.5", "fusion",
                        "jit(update_minibatch)/update/optim/add")]),
    ]),
}

STEP = (("fusion.10", 100), ("fusion.11", 150), ("fusion.16", 300),
        ("fusion.12", 200), ("fusion.13", 10), ("fusion.14", 30),
        ("copy.15", 10))
SUFFIX_OPS = (("fusion.1", 200), ("fusion.2", 80), ("attn.global.3", 20))
UPDATE_OPS = (("fusion.1", 400), ("fusion.5", 100))


def hlo_line(name: str) -> str:
    """An op-line event is named by its whole HLO line on a TPU."""
    return (f"%{name} = bf16[64,1536]{{1,0:T(8,128)(2,1)}} fusion("
            f"bf16[64,1536]{{1,0:T(8,128)(2,1)}} %p.0), kind=kOutput")


def metadata_plane() -> bytes:
    programs = b"".join(
        field(4, field(1, i) + field(2, field(1, i) + field(2, name) + field(
            5, field(1, 7) + field(6, proto))))
        for i, (name, proto) in enumerate(PROGRAMS.items(), start=1))
    stats = field(5, field(1, 7) + field(2, field(1, 7) + field(2, "Hlo Proto")))
    return field(1, 9) + field(2, METADATA_PLANE) + programs + stats


def device_plane(plane_id: int, name: str) -> bytes:
    names = [n for n, _ in STEP + SUFFIX_OPS + UPDATE_OPS] + [
        "while.2", "copy.99"]
    ids = {n: i for i, n in enumerate(dict.fromkeys(names), start=1)}
    modules = {"jit_serving_chunk(11)": 101, "jit_suffix_logits(22)": 102,
               "jit_update_minibatch(33)": 103}
    ops, calls = [], []

    def run(program, start, body, wrap=None):
        at = start
        for _ in range(2 if wrap else 1):
            for op, us in body:
                ops.append(event(ids[op], at, at + us))
                at += us
        end = start + wrap if wrap else at
        if wrap:
            ops.append(event(ids["while.2"], start, end))
        calls.append(event(modules[program], start, end))

    run("jit_serving_chunk(11)", 0, STEP, wrap=1650)
    run("jit_suffix_logits(22)", 1700, SUFFIX_OPS)
    run("jit_suffix_logits(22)", 2100, SUFFIX_OPS)
    run("jit_serving_chunk(11)", 2500, STEP, wrap=1650)
    run("jit_update_minibatch(33)", 4200, UPDATE_OPS)
    ops.append(event(ids["copy.99"], 4800, 4810))
    meta = b"".join(
        field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
        for i, n in [(i, hlo_line(n)) for n, i in ids.items()]
        + [(i, n) for n, i in modules.items()])
    return field(1, plane_id) + field(2, name) + field(
        3, line(1, "XLA Ops", ops)) + field(
        3, line(2, "XLA Modules", calls)) + meta


def build() -> bytes:
    planes = (device_plane(1, "/device:TPU:0"), device_plane(2, "/device:TPU:1"),
              metadata_plane())
    return b"".join(field(1, p) for p in planes)


PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scoped.xplane.pb")

if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(build())
    print(PATH, len(build()), "bytes")
