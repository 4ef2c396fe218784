"""Percentiles and the rate, on a synthetic window with a stall in it."""

import pytest

from harness import reduce
from harness.loop import Sent


def window(latencies_ms, gap_s=0.001, stall_at=None, stall_s=0.0):
    """Requests sent back to back, each answered after its latency; a
    stall delays every answer from `stall_at` on."""
    records, t = [], 0.0
    for i, ms in enumerate(latencies_ms):
        extra = stall_s if stall_at is not None and i >= stall_at else 0.0
        records.append(Sent(i + 1, i, t, t + ms / 1e3 + extra,
                            {"fail_open": False}))
        t += gap_s
    return records


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert reduce.percentile(values, 50) == 50
    assert reduce.percentile(values, 99) == 99
    assert reduce.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        reduce.percentile([], 50)


def test_a_stall_moves_p99_and_the_rate():
    calm = window([10.0] * 1000)
    stalled = window([10.0] * 1000, stall_at=980, stall_s=0.5)
    a = reduce.end_to_end(calm, 0.0, 1.0, 2.0)
    b = reduce.end_to_end(stalled, 0.0, 1.0, 2.0)
    assert a["latency_p50_ms"] == b["latency_p50_ms"] == pytest.approx(10.0)
    assert a["latency_p99_ms"] == pytest.approx(10.0)
    assert b["latency_p99_ms"] == pytest.approx(510.0)
    # the 20 stalled answers arrive after the close: they are not in the rate
    assert a["verdicts_per_s"] == pytest.approx(990.0)
    assert b["verdicts_per_s"] == pytest.approx(980.0)


def test_a_failed_request_counts_at_the_worst_latency_not_as_absent():
    records = window([10.0] * 99 + [40.0])
    records[0].verdict = {"fail_open": True}      # answered by a fallback
    records[1].verdict, records[1].t_recv = None, None   # never answered
    lat = reduce.latencies_ms(records, t_end=3.0)
    assert len(lat) == 100
    # the unanswered one waited until t_end: 3.0 s - 0.001 s
    assert sorted(lat)[-2:] == pytest.approx([2999.0, 2999.0])
    e2e = reduce.end_to_end(records, 0.0, 1.0, 3.0)
    assert e2e["verdicts_per_s"] == pytest.approx(98.0)
