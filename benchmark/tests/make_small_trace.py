"""Writes `data/small.xplane.pb`: a small trace with KNOWN numbers, in the
profiler's own format (XSpace, tsl/profiler/protobuf/xplane.proto), encoded
by hand so the test needs nothing but jax to read it back.

Two device planes and one host plane, times in MICROSECONDS from the trace's start:

  /device:TPU:0, line "XLA Ops":
     fusion.1          0 .. 1000
     while.2        2000 .. 8000     (parent of the next three)
       fusion.3     2000 .. 4000
       all-reduce.4 4000 .. 5000
       fusion.3     6000 .. 8000     (the while itself: 1000 self, 5000-6000)
     all-gather-start.5 9000 .. 9500
  /device:TPU:0, line "XLA Modules": jit_step 0 .. 9500 (must be ignored)
  /device:TPU:1, line "XLA Ops":
     fusion.1          0 .. 2000
     fusion.3       3000 .. 7000
  /host:CPU, line "python":
     bench.trace_window  0 .. 10000
     bench.update        0 .. 10000
     $reward.py:1 grade  1000 .. 2000
     $tiny                8000 .. 8005   (too short to name a gap)

Known results: busy TPU:0 = 1000 + 6000 + 500 = 7500 us, TPU:1 = 6000 us,
mean 6750 us; window 10000 us; collectives (self) TPU:0 = 1500, TPU:1 = 0,
mean 750 us; gaps on TPU:0: 1000-2000 (the host is in `grade`) and 8000-9000
(no host event long enough to name it).
"""

import os


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def event(meta_id: int, start_us: int, end_us: int) -> bytes:
    return field(1, meta_id) + field(2, start_us * 1_000_000) + field(
        3, (end_us - start_us) * 1_000_000)


def line(line_id: int, name: str, events) -> bytes:
    return field(1, line_id) + field(2, name) + field(3, 0) + b"".join(
        field(4, e) for e in events)


def plane(plane_id: int, name: str, lines, names: dict) -> bytes:
    meta = b"".join(field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
                    for i, n in names.items())
    return field(1, plane_id) + field(2, name) + b"".join(
        field(3, ln) for ln in lines) + meta


def build() -> bytes:
    ops = {1: "fusion.1", 2: "while.2", 3: "fusion.3", 4: "all-reduce.4",
           5: "all-gather-start.5", 6: "jit_step"}
    tpu0 = plane(1, "/device:TPU:0", [
        line(1, "XLA Ops", [event(1, 0, 1000), event(2, 2000, 8000),
                            event(3, 2000, 4000), event(4, 4000, 5000),
                            event(3, 6000, 8000), event(5, 9000, 9500)]),
        line(2, "XLA Modules", [event(6, 0, 9500)])], ops)
    tpu1 = plane(2, "/device:TPU:1", [
        line(1, "XLA Ops", [event(1, 0, 2000), event(3, 3000, 7000)])], ops)
    host_names = {1: "bench.trace_window", 2: "bench.update",
                  3: "$reward.py:1 grade", 4: "$tiny"}
    host = plane(3, "/host:CPU", [
        line(1, "python", [event(1, 0, 10000), event(2, 0, 10000),
                           event(3, 1000, 2000), event(4, 8000, 8005)])],
        host_names)
    return b"".join(field(1, p) for p in (tpu0, tpu1, host))


PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")

if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(build())
    print(PATH, len(build()), "bytes")
