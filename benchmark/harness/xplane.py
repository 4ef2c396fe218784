#!/usr/bin/env python3
"""Reduce the server's profiler trace (`.xplane.pb`) to what the metrics read.

    python benchmark/harness/xplane.py <trace dir or .xplane.pb> <out.json>

The trace is of the window alone (`harness/served.py` starts and stops
the profiler around it).  From the device planes (`/device:TPU:<n>`):

  busy_s    union of the intervals in which an operation ran (the `XLA
            Ops` line), averaged over the device planes that ran anything
  window_s  first to last device operation
  programs  per device program (the `XLA Modules` line, the trailing
            fingerprint dropped): seconds, count
  device_ops  the operations that took most device time, named
              `<program>:<instruction>`, [[name, s], ...]
  idle_gaps   the idle time between operations, summed by the pair of
              programs it lies between, [[name, s], ...]

Reading the file needs `jax.profiler.ProfileData`, so this runs as a
child pinned to CPU after the server has released the chip.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def find_trace(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise SystemExit("no .xplane.pb under %s" % path)
    return found[-1]


def load(path: Path) -> dict:
    """{device plane: {line name: [(name, start_ns, end_ns), ...]}}."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        out[plane.name] = {
            line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
    return out


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def program_name(name: str) -> str:
    """`jit_fold_rows` of `jit_fold_rows(8159546111474316357)`."""
    return _PROGRAM_ID.sub("", name)


def op_name(hlo: str) -> str:
    """`%fusion.18` of `%fusion.18 = s32[4096]{...} fusion(...)`."""
    return hlo.split(" = ", 1)[0].strip()[:48]


def top(table: dict, n: int = 10) -> list:
    return [[name, ns / 1e9] for name, ns in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce_window(planes: dict) -> dict:
    """The summary of the device planes' events."""
    busy_ns = []
    first = last = None
    programs: dict = {}
    ops: dict = {}
    gaps: dict = {}
    for _plane, lines in sorted(planes.items()):
        op_events = lines.get(OPS_LINE, [])
        if not op_events:
            continue
        merged = union([(s, e) for _n, s, e in op_events])
        busy_ns.append(sum(e - s for s, e in merged))
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        # programs do not overlap on one device, so their starts and ends
        # sort alike and a bisection finds the one around a moment
        mods = sorted((s, e, program_name(n))
                      for n, s, e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        ends = [m[1] for m in mods]
        for s, e, n in mods:
            p = programs.setdefault(n, {"seconds": 0.0, "count": 0})
            p["seconds"] += (e - s) / 1e9
            p["count"] += 1
        for n, s, e in op_events:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < ends[i]
            key = "%s:%s" % (mods[i][2] if inside else "?", op_name(n))
            ops[key] = ops.get(key, 0.0) + (e - s)
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            i = bisect.bisect_left(ends, e0)      # the program e0 ends in
            j = bisect.bisect_right(starts, s1) - 1   # ... and s1 starts in
            key = "%s -> %s" % (mods[i][2] if i < len(mods) else "?",
                                mods[j][2] if j >= 0 else "?")
            gaps[key] = gaps.get(key, 0.0) + (s1 - e0)
    if not busy_ns:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "device_ops": [], "idle_gaps": [], "device_planes": 0}
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": (last - first) / 1e9,
            "programs": programs, "device_ops": top(ops),
            "idle_gaps": top(gaps), "device_planes": len(busy_ns)}


def main(argv: list) -> int:
    trace = find_trace(Path(argv[1]))
    summary = reduce_window(load(trace))
    summary.update(trace_file=str(trace), trace_bytes=trace.stat().st_size)
    Path(argv[2]).write_text(json.dumps(summary))
    print(json.dumps({k: v for k, v in summary.items() if k != "programs"}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
