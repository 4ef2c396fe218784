"""Scrape arithmetic, the count of failed operations, and the comparison."""

import importlib.util
from pathlib import Path

import pytest

from harness import scrape
from harness.loop import Sent

BENCH = Path(__file__).resolve().parent.parent

BEFORE = """
# TYPE ipt_stage_us histogram
ipt_stage_us_bucket{stage="queue",le="1024"} 4
ipt_stage_us_sum{stage="queue"} 1000
ipt_stage_us_count{stage="queue"} 10
ipt_stage_us_sum{stage="batch"} 500
ipt_stage_us_count{stage="batch"} 5
ipt_requests_total 100
ipt_shed_total{reason="deadline"} 1
ipt_shed_total{reason="queue_full"} 0
ipt_breaker_trips_total 0
ipt_breaker_trips_total{device="0"} 0
ipt_bucket_rows_total{bucket="64"} 700
ipt_pad_waste_ratio 0.25
"""
AFTER = """
ipt_stage_us_sum{stage="queue"} 7000
ipt_stage_us_count{stage="queue"} 40
ipt_stage_us_sum{stage="batch"} 900
ipt_stage_us_count{stage="batch"} 15
ipt_requests_total 420
ipt_shed_total{reason="deadline"} 3
ipt_shed_total{reason="queue_full"} 1
ipt_breaker_trips_total 2
ipt_breaker_trips_total{device="0"} 2
ipt_bucket_rows_total{bucket="64"} 1700
ipt_bucket_rows_total{bucket="2048"} 9
ipt_pad_waste_ratio 0.5
garbage line that is not a sample
"""


def window():
    return scrape.Window(scrape.parse_metrics(BEFORE),
                         scrape.parse_metrics(AFTER))


def test_window_differences():
    w = window()
    assert w.stage_mean_ms("queue") == pytest.approx(6000 / 30 / 1e3)
    assert w.stage_mean_ms("confirm") is None
    assert w.stage_count("batch") == 10
    assert w.delta("ipt_shed_total") == 3          # summed over reasons
    assert w.delta("ipt_shed_total", reason="deadline") == 2
    # the unlabelled aggregate leads its device= twins: not counted twice
    assert w.delta_unlabelled("ipt_breaker_trips_total") == 2
    assert w.delta_unlabelled("ipt_requests_total") == 320
    assert w.last("ipt_pad_waste_ratio") == 0.5
    assert w.last("ipt_absent") is None
    # a series that first appears inside the window counts from 0
    assert w.labelled("ipt_bucket_rows_total", "bucket") == {
        "64": 1000.0, "2048": 9.0}


def test_sidecar_status():
    s = scrape.parse_sidecar('{"forwarded": 7, "fail_open_deadline": 2, '
                             '"upstreams": [{"inflight": 1}], "ok": true}')
    assert s == {("sidecar.forwarded", ()): 7.0,
                 ("sidecar.fail_open_deadline", ()): 2.0}


@pytest.fixture(scope="module")
def run_module():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def verdict(attack=False, blocked=False, ids=(), fail_open=False):
    return {"attack": attack, "blocked": blocked, "rule_ids": list(ids),
            "fail_open": fail_open}


def test_fallback_answers_are_failed_and_never_compared(run_module):
    records = [
        Sent(1, 0, 0.0, 0.01, verdict(True, True, [942100])),   # right
        Sent(2, 1, 0.0, 0.01, verdict(fail_open=True)),         # a fallback
        Sent(3, 0, 0.0, 0.01, verdict()),                       # wrong
        Sent(4, 1, 0.0, None, None),                            # never came
    ]
    expected = {0: [True, True, [942100]], 1: [False, False, []]}
    got = run_module.compare(records, expected)
    assert (got["compared"], got["mismatched"]) == (2, 1)
    assert got["reference_attacks"] == 2
    assert got["examples"][0]["pool_index"] == 0
    quiet = dict.fromkeys(
        ("ipt_fail_open_total", "ipt_shed_total", "ipt_degraded_verdicts_total",
         "ipt_cpu_fallback_batches_total", "ipt_breaker_trips_total",
         "sidecar.fail_open_deadline", "sidecar.fail_open_upstream",
         "sidecar.fail_open_overload", "sidecar.late_responses"), 0.0)
    failed, parts = run_module.count_failed(records, quiet)
    assert failed == 2 and parts["flagged_fail_open"] == 1
    assert parts["unanswered"] == 1
    # what only the counters saw: 3 sidecar passes (1 of them the client
    # saw flagged) and 5 verdicts served degraded with no flag (the server
    # counts its 2 sheds as degraded too: 7, of which 2 failed open)
    loud = dict(quiet, **{"sidecar.fail_open_deadline": 3.0,
                          "ipt_fail_open_total": 2.0,
                          "ipt_degraded_verdicts_total": 7.0})
    assert run_module.unflagged_fallbacks(loud) == 5
    failed, parts = run_module.count_failed(records, loud)
    assert parts["seen_only_by_counters"] == (3 + 2 - 1) + 5
    assert failed == len(records)       # an upper estimate, capped


def test_a_degraded_window_is_not_correct(run_module):
    """A degraded or CPU-fallback verdict carries no flag, so it is
    compared like any other; the window it is in is not correct even
    where every verdict agrees."""
    records = [Sent(1, 0, 0.0, 0.01, verdict(True, True, [942100, 920100]))]
    expected = {0: [True, True, [920100, 942100]]}     # ids compare as a set
    quiet = dict.fromkeys(
        ("ipt_degraded_verdicts_total", "ipt_cpu_fallback_batches_total",
         "ipt_fail_open_total"), 0.0)
    correct, checks, _ = run_module.judge(records, quiet, expected, None)
    assert correct and checks["mismatched"]["value"] == 0
    # a shed is counted as degraded and as failed open: it carried the flag
    shed = dict(quiet, ipt_degraded_verdicts_total=3.0, ipt_fail_open_total=3.0)
    assert run_module.judge(records, shed, expected, None)[0]
    for name in ("ipt_degraded_verdicts_total",
                 "ipt_cpu_fallback_batches_total"):
        correct, checks, _ = run_module.judge(
            records, dict(quiet, **{name: 1.0}), expected, None)
        assert not correct
        assert checks["unflagged_fallbacks"] == {"value": 1, "limit": 0}
        assert checks["mismatched"]["value"] == 0
