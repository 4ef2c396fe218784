"""Idle gaps by host span (`harness/hostspans.py`): on synthetic planes,
and on a recorded fixture.

`fixtures/api-small-hostspans-100ms.xplane.pb` is the first 100 ms of a
traced `crs-full.api-small` run on the v5e (my chip run, PR 27), cut by
`tests/trim_hostspans.py`: the device planes' `XLA Ops` / `XLA Modules`
and the host planes' `ipt:` events.  Its expected numbers were reckoned
apart from `harness/hostspans.py`, by a brute-force sweep over every
event edge (for each stretch between two edges: is an op running; if
not, which covering span of the lane worker's began last, else of the
dispatch thread's).
"""

from pathlib import Path

import pytest

from harness import hostspans, xplane

FIXTURE = (Path(__file__).resolve().parent.parent / "fixtures"
           / "api-small-hostspans-100ms.xplane.pb")


def test_flatten_keeps_the_innermost_span():
    spans = [("cycle", 0, 100), ("scan_dispatch", 10, 60),
             ("scan_launch", 10, 30), ("scan_wait", 35, 60),
             ("confirm_walk", 70, 90)]
    assert hostspans.flatten(spans) == [
        (0, 10, "cycle"), (10, 30, "scan_launch"), (30, 35, "scan_dispatch"),
        (35, 60, "scan_wait"), (60, 70, "cycle"), (70, 90, "confirm_walk"),
        (90, 100, "cycle")]
    assert hostspans.flatten([]) == []


def test_flatten_takes_the_latest_begun_where_spans_only_overlap():
    # the mesh loop begins cycle N+1 before it ends cycle N
    assert hostspans.flatten([("a", 0, 50), ("b", 30, 80)]) == [
        (0, 30, "a"), (30, 80, "b")]


def test_cover_splits_intervals_and_returns_what_is_left():
    table = {}
    left = hostspans.cover([(0, 10), (20, 50)],
                           [(5, 25, "x"), (30, 40, "y")], table)
    assert table == {"x": 5 + 5, "y": 10}
    assert left == [(0, 5), (25, 30), (40, 50)]


def test_classify_tells_threads_by_what_they_hold():
    lane = [("scan_launch", 0, 1), ("host_prep", 0, 1)]
    disp = [("cycle", 0, 5), ("lane_call", 1, 4)]
    loop = [("gc", 2, 3)]
    got = hostspans.classify([loop, disp, lane])
    assert got["threads"] == {"lane_worker": 1, "dispatch": 1, "other": 1}
    assert got["spans"]["lane_worker"] == lane


def planes(ops):
    return {"/device:TPU:0": {"XLA Ops": [("%op", s, e) for s, e in ops],
                              "XLA Modules": []}}


def test_idle_goes_to_the_lane_worker_first_then_the_dispatch_thread():
    # device busy 10-20, 40-50, 90-100: idle 20-40 and 50-90
    dev = planes([(10, 20), (40, 50), (90, 100)])
    lane = [("scan_dispatch", 5, 55), ("scan_launch", 5, 35),
            ("scan_wait", 35, 55)]
    disp = [("cycle", 0, 70), ("lane_call", 2, 60), ("drain_idle", 70, 85)]
    loop = [("gc", 84, 88)]
    got = hostspans.attribute(dev, [lane, disp, loop])
    assert got["threads"] == {"lane_worker": 1, "dispatch": 1, "other": 1}
    assert got["idle_s"] == pytest.approx(60e-9)
    assert got["window_s"] == pytest.approx(90e-9)
    by = dict(got["idle_by_span"])
    # 20-35 launch, 35-40 and 50-55 wait (lane worker); 55-60 lane_call,
    # 60-70 cycle, 70-85 drain_idle (dispatch thread; the gc span on
    # the loop's thread shows only where no other thread has a span:
    # 85-88); 88-90 nobody
    assert by == {"scan_launch": pytest.approx(15e-9),
                  "scan_wait": pytest.approx(10e-9),
                  "lane_call": pytest.approx(5e-9),
                  "cycle": pytest.approx(10e-9),
                  "drain_idle": pytest.approx(15e-9),
                  "gc": pytest.approx(3e-9),
                  "unannotated": pytest.approx(2e-9)}
    assert sum(by.values()) == pytest.approx(got["idle_s"])
    assert got["named_share"] == pytest.approx(1 - 2 / 60)
    assert got["idle_by_span"][0][0] in ("scan_launch", "drain_idle")
    # self time: innermost on its own thread, inside the device's window
    assert got["self_s"]["scan_launch"] == pytest.approx(25e-9)   # 10-35
    assert got["self_s"]["lane_call"] == pytest.approx(50e-9)     # 10-60
    assert got["self_s"]["cycle"] == pytest.approx(10e-9)         # 60-70


def test_a_trace_without_spans_is_all_unannotated():
    got = hostspans.attribute(planes([(0, 10), (30, 40)]), [])
    assert got["idle_by_span"] == [["unannotated", pytest.approx(20e-9)]]
    assert got["named_share"] == 0.0


def test_no_device_event_reads_nothing():
    got = hostspans.attribute({}, [[("cycle", 0, 5)]])
    assert got["idle_s"] == 0.0 and got["named_share"] is None


def test_recorded_fixture():
    got = hostspans.attribute(xplane.load(FIXTURE),
                              hostspans.load_host_spans(FIXTURE))
    assert got["threads"] == {"lane_worker": 1, "dispatch": 1, "other": 0}
    assert got["idle_s"] == pytest.approx(98710062e-9, rel=1e-9)
    assert got["window_s"] == pytest.approx(99855783e-9, rel=1e-9)
    want_ns = {"confirm_walk": 45172248, "scan_launch": 24149453,
               "unannotated": 10653322, "scan_pack": 7901270,
               "scan_wait": 3162190, "cycle": 3028630,
               "lane_call": 1563599, "host_prep": 1331350,
               "confirm_fold": 1036260, "gc": 362030,
               "drain_idle": 166100, "scan_dispatch": 139690,
               "finalize_join": 43920}
    by = dict(got["idle_by_span"])
    assert set(by) == set(want_ns)
    for name, ns in want_ns.items():
        assert by[name] == pytest.approx(ns * 1e-9, rel=1e-9), name
    assert [n for n, _s in got["idle_by_span"]][:3] == [
        "confirm_walk", "scan_launch", "unannotated"]
    # the cut drops the spans that were open at either end of the
    # stretch, so more is unannotated here than in a whole slice
    assert got["named_share"] == pytest.approx(1 - 10653322 / 98710062)
    # it agrees with the device-plane reduction on the same file
    dev = xplane.reduce_window(xplane.load(FIXTURE))
    assert got["window_s"] == pytest.approx(dev["window_s"])
    assert got["idle_s"] == pytest.approx(dev["window_s"] - dev["busy_s"])


def test_the_fixture_can_be_cut_again(tmp_path):
    """`trim_hostspans.py` on its own output: a shorter stretch of the
    same planes, still readable."""
    import subprocess
    import sys

    out = tmp_path / "cut.xplane.pb"
    tool = Path(__file__).resolve().parent / "trim_hostspans.py"
    subprocess.run([sys.executable, str(tool), str(FIXTURE), str(out),
                    "0.06"], check=True, capture_output=True)
    assert 0 < out.stat().st_size < FIXTURE.stat().st_size
    got = hostspans.attribute(xplane.load(out),
                              hostspans.load_host_spans(out))
    assert 0 < got["window_s"] <= 0.06
    assert got["threads"]["lane_worker"] == 1
    assert {"scan_launch", "confirm_walk"} <= {
        n for n, _s in got["idle_by_span"]}
