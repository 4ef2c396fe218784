"""Memory side of the scan's roofline: the least time the chip's HBM could
take to move the bytes the traffic of the traced slice *needed* scanned,
over the summed device time of the scan programs in the trace.  Rows are
the slice's own (the counters are scraped when the profiler comes on).

Bytes are counted from shapes and counters (harness/work.py), not from
what an implementation padded or widened to.  The recurrence itself is
32-bit integer VPU work for which no peak is published, so this is a
lower bound of the true roofline share.  Layer: scan implementation."""

import re

from harness import peaks, work

#: device programs that are the scan (names as the trace gives them)
SCAN_PROGRAM = re.compile(r"scan", re.I)


def read(ctx):
    trace = ctx["trace"]
    if not trace or ctx["slice"] is None:
        return None
    programs = {name: p for name, p in trace["programs"].items()
                if SCAN_PROGRAM.search(name)}
    seconds = sum(p["seconds"] for p in programs.values())
    launches = sum(p["count"] for p in programs.values())
    if seconds <= 0:
        return None
    rows = {int(L): n for L, n in
            ctx["slice"].labelled("ipt_bucket_rows_total",
                                  "bucket").items()}
    needed = work.scan_bytes(rows, ctx["config"]["scan_words"], launches)
    least_s = needed / peaks.hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / seconds
