"""From the window's per-request records to the end-to-end metrics.

Nothing is taken from medians of chunks or gaps: the rate is all
non-failed verdicts that arrived inside the window over the whole
window's time, and the latencies are over every request sent in the
window, a failed one counted at the window's worst latency.
"""

from __future__ import annotations

import math
from typing import List, Sequence

#: the end-to-end metrics this module reduces (`setup_s` is the harness's)
END_TO_END = ("verdicts_per_s", "latency_p50_ms", "latency_p99_ms")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def is_failed(rec) -> bool:
    """A request no verdict of the device path answered: never answered,
    answered twice, or answered by a fallback (fail-open flag)."""
    return rec.verdict is None or rec.doubled or rec.verdict["fail_open"]


def latencies_ms(records: List, t_end: float) -> List[float]:
    """Round trips in ms; a failed request counts at the worst latency
    of the window (an unanswered one: at least its wait until `t_end`)."""
    good = [(r.t_recv - r.t_send) * 1e3 for r in records if not is_failed(r)]
    waited = [((r.t_recv if r.t_recv is not None else t_end) - r.t_send) * 1e3
              for r in records if is_failed(r)]
    worst = max(good + waited) if (good or waited) else 0.0
    return good + [worst] * len(waited)


def end_to_end(records: List, t_start: float, t_close: float,
               t_end: float) -> dict:
    """`t_end` = when the drain ended (for requests still unanswered)."""
    lat = latencies_ms(records, t_end)
    in_window = sum(1 for r in records if not is_failed(r)
                    and r.t_recv <= t_close)
    return {
        "verdicts_per_s": in_window / (t_close - t_start),
        "latency_p50_ms": percentile(lat, 50.0),
        "latency_p99_ms": percentile(lat, 99.0),
    }
