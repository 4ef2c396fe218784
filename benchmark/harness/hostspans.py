#!/usr/bin/env python3
"""Put the device's idle time down to what the host was doing in it.

    python benchmark/harness/hostspans.py <trace dir or .xplane.pb> <out.json>

`harness/xplane.py` names an idle gap by the device programs on either
side of it.  This names it by the program's own spans: the server enters
a `jax.profiler.TraceAnnotation("ipt:<name>", cycle=, n=)` for every
per-dispatch span of its flight recorder (`ingress_plus_tpu/utils/trace.py
flight.span`), so a profiler trace holds them on the host planes, on the
clock of the device planes' `XLA Ops`.  Every device-idle interval (between
the first and the last device operation of the trace) goes to the
innermost `ipt:` span that covers it.  The detect runs on the lane
worker's thread while the dispatch thread waits in `lane_call`, so: the
innermost span open on a lane worker, else the innermost open on the
dispatch thread, else on any other thread (`gc` on the event loop), else
`unannotated`.  Threads are told apart by what they hold: a host line
with `ipt:scan_launch` events is a lane worker's, one with `ipt:cycle`
or `ipt:drain_idle` the dispatch thread's (the profiler names every
Python thread's line `python`).

The output (also printed, without the per-thread detail):

  idle_s       device-idle seconds between the first and last device op
  window_s     first to last device op
  idle_by_span [[span, seconds], ...], `unannotated` among them
  named_share  1 - unannotated / idle_s
  self_s       {span: seconds it was the innermost open span of its own
               thread}, inside the same window
  threads      {lane_worker: n, dispatch: n, other: n} host lines found

A trace without `ipt:` events (a program that enters none) reads all
idle time as `unannotated`.  With several lanes the lane workers' spans
are flattened together, the latest begun on top: exact for one lane, an
approximation beyond.  Wiring this into `run.py`'s `breakdown` is a
benchmark PR's (`run.py` deletes the trace before the readers run); until
then: `BENCH_KEEP_TRACE=1 python3 benchmark/run.py ... --trace 1`, then
this on `benchmark_out/<cell>/seed<n>-trace1/trace`.

Reading the file needs `jax.profiler.ProfileData`, so run it pinned to
CPU (`JAX_PLATFORMS=cpu`) after the server has released the chip.
"""

from __future__ import annotations

import heapq
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from harness import xplane                                    # noqa: E402

SPAN_PREFIX = "ipt:"
UNANNOTATED = "unannotated"
#: what tells a host line's thread (names without the prefix)
LANE_MARKS = {"scan_launch", "scan_dispatch"}
DISPATCH_MARKS = {"cycle", "drain_idle"}


def load_host_spans(path: Path) -> list:
    """[[(name, start_ns, end_ns), ...] per host line that holds `ipt:`
    events], names without the prefix."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans = [(e.name[len(SPAN_PREFIX):], e.start_ns,
                      e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith(SPAN_PREFIX)]
            if spans:
                lines.append(spans)
    return lines


def classify(lines: list) -> dict:
    """{"lane_worker": [spans...], "dispatch": [...], "other": [...]}:
    each class's lines flattened into one list of spans."""
    out = {"lane_worker": [], "dispatch": [], "other": []}
    counts = dict.fromkeys(out, 0)
    for spans in lines:
        names = {n for n, _s, _e in spans}
        kind = ("lane_worker" if names & LANE_MARKS else
                "dispatch" if names & DISPATCH_MARKS else "other")
        out[kind] += spans
        counts[kind] += 1
    return {"spans": out, "threads": counts}


def flatten(spans: list) -> list:
    """Non-overlapping, sorted (start, end, name): at each moment the
    covering span that began last (the innermost, where spans nest)."""
    bounds = sorted({t for _n, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    heap: list = []
    out: list = []
    i = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= t0:
            name, s, e = by_start[i]
            # latest begun on top; of two begun together, the shorter
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)           # ended; the one below shows
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1][1] = t1
        else:
            out.append([t0, t1, name])
    return [tuple(seg) for seg in out]


def clip(segments: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in segments
            if e > lo and s < hi]


def cover(intervals: list, segments: list, table: dict) -> list:
    """Add to `table[name]` the part of `intervals` (sorted, disjoint
    (start, end)) that each of `segments` (sorted, disjoint) covers;
    return the parts no segment covers."""
    left: list = []
    j = 0
    for a, b in intervals:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            if s > at:
                left.append((at, s))
            top = min(e, b)
            if top > max(s, at):
                table[name] = table.get(name, 0.0) + top - max(s, at)
            at = max(at, top)
            k += 1
        if at < b:
            left.append((at, b))
    return left


def attribute(device_planes: dict, lines: list) -> dict:
    """The summary described at the top, from `xplane.load`'s device
    planes and `load_host_spans`'s host lines."""
    got = classify(lines)
    flat = {kind: flatten(spans) for kind, spans in got["spans"].items()}
    table: dict = {}
    idle_ns = unnamed = 0.0
    first = last = None
    for _plane, dev_lines in sorted(device_planes.items()):
        merged = xplane.union(
            [(s, e) for _n, s, e in dev_lines.get(xplane.OPS_LINE, [])])
        if not merged:
            continue
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        # each device's own idle intervals, summed over the devices
        left = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])]
        idle_ns += sum(b - a for a, b in left)
        for kind in ("lane_worker", "dispatch", "other"):
            left = cover(left, flat[kind], table)
        unnamed += sum(b - a for a, b in left)
    if first is None:
        return {"idle_s": 0.0, "window_s": 0.0, "idle_by_span": [],
                "named_share": None, "self_s": {}, "threads": got["threads"]}
    if unnamed:
        table[UNANNOTATED] = unnamed
    self_ns: dict = {}
    for kind in flat:
        for s, e, name in clip(flat[kind], first, last):
            self_ns[name] = self_ns.get(name, 0.0) + e - s
    return {
        "idle_s": idle_ns / 1e9, "window_s": (last - first) / 1e9,
        "idle_by_span": [[n, ns / 1e9] for n, ns in
                         sorted(table.items(), key=lambda kv: -kv[1])],
        "named_share": (1.0 - unnamed / idle_ns) if idle_ns else None,
        "self_s": {n: ns / 1e9 for n, ns in
                   sorted(self_ns.items(), key=lambda kv: -kv[1])},
        "threads": got["threads"]}


def main(argv: list) -> int:
    trace = xplane.find_trace(Path(argv[1]))
    summary = attribute(xplane.load(trace), load_host_spans(trace))
    summary.update(trace_file=str(trace), trace_bytes=trace.stat().st_size)
    Path(argv[2]).write_text(json.dumps(summary))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
