"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports. Uses only `jax.profiler.ProfileData`.

- device planes: `/device:TPU:<n>` (one per chip). Of a plane's lines the
  reduction reads the XLA op line (`XLA Ops`); a trace without it falls back
  to every line of the plane except step and module groupings.
- busy: the union of the op intervals on a plane; `busy_s` is its mean over
  the planes. `window_s` is the length of the harness's own annotation
  `bench.trace_window` (written on the host around the traced part), or the
  extent of the device events where that is missing. idle = 1 - busy/window.
- top ops: SELF time by op name (an op's duration minus the ops nested in
  it on the same line, so a `while` does not swallow its body), summed and
  averaged over planes.
- collectives: self time of ops whose name is of a collective class
  (all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute;
  `-start`/`-done` halves included), over busy time.
- idle gaps: the gaps between busy intervals on the first device plane, each
  joined to what the host was in: the innermost `bench.*` annotation open at
  the gap's middle and the host event that covers most of the gap. Summed by
  that name; the ten largest are reported.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Step")
COLLECTIVE = re.compile(
    r"all-?gather|all-?reduce|reduce-?scatter|all-?to-?all|collective-?permute",
    re.IGNORECASE)
WINDOW_ANNOTATION = "bench.trace_window"
ANNOTATION_PREFIX = "bench."
TOP = 10


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def union_seconds(intervals) -> tuple:
    """(total seconds, merged intervals) of [(start_ns, end_ns)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) * 1e-9, merged


def self_times(events) -> dict:
    """{name: self seconds} for (name, start_ns, dur_ns) events of ONE line:
    nested events (start inside an open event) are taken from their parent."""
    out: dict = {}
    stack = []      # [name, end, self_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0) * 1e-9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


HLO_TEXT = re.compile(r"^(%?[\w.\-]+) = (\(?\w+\[[\d,]*\]).*?[})] ([\w\-]+)\(")


def short_name(name: str) -> str:
    """The TPU trace names an op by its whole HLO line; keep `%name`, the
    result's type and shape, and the opcode: `%copy.298 bf16[28,64,2,768,128] copy`."""
    m = HLO_TEXT.match(name)
    return " ".join(m.groups()) if m else name[:120]


def _plane_ops(plane) -> list:
    lines = list(plane.lines)
    chosen = [ln for ln in lines if ln.name == OP_LINE] or [
        ln for ln in lines if ln.name not in SKIP_LINES]
    names: dict = {}

    def short(name):
        if name not in names:
            names[name] = short_name(name)
        return names[name]

    return [[(short(e.name), e.start_ns, e.duration_ns) for e in ln.events]
            for ln in chosen]


def _host_events(planes) -> list:
    out = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.duration_ns > 0:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _host_name(host, start, end) -> str:
    mid = (start + end) / 2
    notes = [h for h in host if h[0].startswith(ANNOTATION_PREFIX)
             and h[0] != WINDOW_ANNOTATION and h[1] <= mid <= h[2]]
    note = min(notes, key=lambda h: h[2] - h[1])[0] if notes else "no annotation"
    best, best_cover = None, 0.0
    for name, s, e in host:
        if name.startswith(ANNOTATION_PREFIX) or e - s > 4 * (end - start):
            continue
        cover = min(e, end) - max(s, start)
        if cover > best_cover:
            best, best_cover = name, cover
    return f"{note} / {best}" if best else note


def reduce_profile(data, max_gaps_named: int = 64) -> dict | None:
    """`data`: a `jax.profiler.ProfileData`. None when the trace has no
    device plane with an operation on it."""
    planes = list(data.planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    per_plane, ops_total, coll_total, merged_first = [], {}, 0.0, None
    lo, hi, n_events = None, None, 0
    for p in sorted(devices, key=lambda p: p.name):
        line_events = _plane_ops(p)
        intervals = [(s, s + d) for ev in line_events for _, s, d in ev if d > 0]
        if not intervals:
            continue
        n_events += len(intervals)
        busy, merged = union_seconds(intervals)
        if merged_first is None:
            merged_first = merged
        lo = merged[0][0] if lo is None else min(lo, merged[0][0])
        hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        selfs: dict = {}
        for ev in line_events:
            for name, sec in self_times(ev).items():
                selfs[name] = selfs.get(name, 0.0) + sec
        coll = sum(sec for name, sec in selfs.items() if COLLECTIVE.search(name))
        for name, sec in selfs.items():
            ops_total[name] = ops_total.get(name, 0.0) + sec
        coll_total += coll
        per_plane.append({"plane": p.name, "busy_s": busy, "collective_s": coll,
                          "events": len(intervals)})
    if not per_plane:
        return None
    n = len(per_plane)
    host = _host_events(planes)
    window = next(((e - s) * 1e-9 for name, s, e in host
                   if name == WINDOW_ANNOTATION), None)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in
                   zip(merged_first, merged_first[1:])), reverse=True)
    gap_by_name: dict = {}
    named = gaps[:max_gaps_named]
    # a host event much shorter than the smallest gap that gets a name cannot
    # cover most of any of them: leave those out of the search
    floor_ns = max(10_000, 0.2 * named[-1][0]) if named else 0
    host = [h for h in host if h[2] - h[1] >= floor_ns
            or h[0].startswith(ANNOTATION_PREFIX)]
    for dur, s, e in named:
        name = _host_name(host, s, e)
        gap_by_name[name] = gap_by_name.get(name, 0.0) + dur * 1e-9
    busy_s = sum(p["busy_s"] for p in per_plane) / n
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "busy_s": busy_s,
        "window_s": window if window else (hi - lo) * 1e-9,
        "window_from": "annotation" if window else "device_extent",
        "device_extent_s": (hi - lo) * 1e-9,
        "collective_s": coll_total / n,
        "device_ops": top({k: v / n for k, v in ops_total.items()}),
        "idle_gaps": top(gap_by_name),
        "gaps_total_s": sum(g[0] for g in gaps) * 1e-9,
        "planes": per_plane, "device_events": n_events,
    }


def reduce_file(path: str) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
