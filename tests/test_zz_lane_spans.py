"""What lanes add to the tracing, and that lanes change no verdict.

The four-lane node is a benchmark configuration (`crs-full-4lane`): its
dispatch loop (`Batcher._run`) is the one every lane count runs, so (a) four lanes, one lane and the benchmark's
plain reference must agree on every verdict of the bundled pack; (b) the
per-lane series (`ipt_lane_stage_us`, `ipt_lane_cycle_us`) must add up
to the cycle's sub-stages while one lane's `/metrics` stays as it was;
(c) the four `lanes.*` readers of `benchmark/layer_metrics/` must read
what they say from a scrape pair.  On the 8-device CPU conftest.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.utils.trace import (
    ACCUMULATED,
    EV_HANDOFF,
    EV_LANE_SCAN,
    EV_SCAN_PACK,
    FlightRecorder,
    flight,
)

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"


@pytest.fixture(scope="module")
def bench_path():
    """`benchmark/` importable (harness, reference), as its own tests
    have it; taken off the path again afterwards."""
    sys.path.insert(0, str(BENCH))
    yield BENCH
    sys.path.remove(str(BENCH))


@pytest.fixture(scope="module")
def bundled():
    """The pack `crs-full` and `crs-full-4lane` serve: the bundled
    SecLang tree plus the signature packs."""
    from ingress_plus_tpu.compiler import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import (
        RULES_DIR,
        load_bundled_rules,
    )

    return compile_ruleset(load_bundled_rules(), base_path=RULES_DIR / "crs")


def _batcher(cr, n_lanes, confirm_workers=1):
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    return Batcher(DetectionPipeline(cr, mode="block", fail_open=False,
                                     confirm_workers=confirm_workers),
                   n_lanes=n_lanes, max_batch=16, max_delay_s=0.001)


def _serve(batcher, requests):
    futs = [batcher.submit(r) for r in requests]
    return [f.result(timeout=300) for f in futs]


# ------------------------------------------------ (a) the same verdicts

def test_four_lanes_one_lane_and_the_plain_reference_agree(
        bench_path, bundled, monkeypatch):
    monkeypatch.setenv("IPT_NO_NATIVE_CONFIRM", "1")
    from harness.wire import encode_request
    from reference import plainwaf
    from reference.walk import load_generator

    from ingress_plus_tpu.serve.protocol import decode_request

    traffic = json.loads((BENCH / "traffic" / "api-small-x4.json").read_text())
    params = dict(traffic["params"], attack_fraction=0.25)
    reqs = load_generator(traffic["generator"]).generate(
        2**31 + 29, 64, params)
    frames = [encode_request(r, req_id=i) for i, r in enumerate(reqs)]
    dep = plainwaf.Deployment(BENCH / "rules" / "crs",
                              sigpack=BENCH / "rules" / "sigpack.json")
    want = []
    for frame in frames:
        _idx, request = plainwaf.decode_frame(frame)
        a, b, ids = plainwaf.verdict(dep, request)
        want.append((a, b, sorted(ids)))

    def decoded():
        out = []
        for i, frame in enumerate(frames):
            _id, mode, req = decode_request(frame[8:])
            req.mode = mode
            req.request_id = "r%d" % i
            out.append(req)
        return out

    served = {}
    for n_lanes in (1, 4):
        b = _batcher(bundled, n_lanes)
        try:
            served[n_lanes] = [
                (bool(v.attack), bool(v.blocked),
                 sorted(int(r) for r in v.rule_ids), v.fail_open, v.degraded)
                for v in _serve(b, decoded())]
            if n_lanes == 4:
                by_lane = [ln.stats.requests for ln in b.lanes.lanes]
                assert sum(by_lane) == len(frames) and min(by_lane) > 0
        finally:
            b.close()
    assert served[4] == served[1]
    assert [v[:3] for v in served[4]] == want
    assert not any(v[3] or v[4] for v in served[4])
    assert sum(w[0] for w in want) >= 8


# ------------------------------------- (b) the per-lane series add up

@pytest.fixture(scope="module")
def small():
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang

    return compile_ruleset(parse_seclang(
        'SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" '
        '"id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,'
        'severity:CRITICAL,tag:\'attack-sqli\'"\n'))


def _requests(n):
    from ingress_plus_tpu.serve.normalize import Request

    return [Request(uri="/p?q=%d%%27%%20UNION%%20SELECT%%20x" % i
                    if i % 3 == 0 else "/index.html?page=%d" % i,
                    headers={}, body=b"", request_id="q%d" % i)
            for i in range(n)]


def _metrics(batcher) -> str:
    from ingress_plus_tpu.serve.server import ServeLoop

    return ServeLoop(batcher, "/tmp/unused.sock")._metrics_text()


def test_lane_series_add_up_to_the_cycles_sub_stages(small):
    flight.configure(enabled=True)
    b = _batcher(small, 4)
    try:
        for _ in range(6):
            assert len(_serve(b, _requests(16))) == 16
        by_lane, cycles = b.lane_stage_us.copy(), b.lane_cycle_us.copy()
        # each sub-stage: the lanes' sums are the cycle's sum
        for name in ACCUMULATED.values():
            lanes_us = sum(us for (_ln, st), (us, _n) in by_lane.items()
                           if st == name)
            assert lanes_us == b.subhist[name].sum_us, name
        stages = {st for _ln, st in by_lane}
        assert {"scan_pack", "scan_launch", "scan_wait", "lane_handoff",
                "confirm_walk", "lane_scan"} <= stages
        assert {ln for ln, _st in by_lane} == {0, 1, 2, 3}
        # a share's interval holds its launch and its wait, and the
        # hand-over to the worker
        for lane in range(4):
            inner = sum(by_lane[(lane, st)][0]
                        for st in ("scan_launch", "scan_wait"))
            assert by_lane[(lane, "lane_scan")][0] >= inner
        # the wall span: no longer than all the lanes' one after
        # another, no shorter than the longest lane's mean share
        wall_us, n_cycles = cycles["scan_wall"]
        lane_scan = [by_lane[(ln, "lane_scan")] for ln in range(4)]
        assert n_cycles == cycles["dispatch_own"][1] >= 6
        assert wall_us <= sum(us for us, _n in lane_scan)
        assert wall_us >= max(us for us, _n in lane_scan)
        text = _metrics(b)
        assert ('ipt_lane_stage_us_sum{device="3",stage="lane_scan"} %d'
                % by_lane[(3, "lane_scan")][0]) in text
        assert ('ipt_lane_cycle_us_count{span="scan_wall"} %d'
                % n_cycles) in text
        assert 'ipt_lane_cycle_us_sum{span="dispatch_own"}' in text
        # the existing family keeps its labels: no lane on it
        assert 'ipt_stage_us_sum{stage="scan_launch"}' in text
        assert "ipt_stage_us_sum{device=" not in text
    finally:
        b.close()


def test_the_wall_span_is_at_most_the_longest_share_plus_the_launches(small):
    """Cycle by cycle, from the ring: first hand-over → last result is
    bounded by the longest share's interval plus the dispatch thread's
    launches of the shares (the hand-overs are that far apart)."""
    from ingress_plus_tpu.utils.trace import EV_LAUNCH, EV_SCAN_WALL, match_spans

    flight.configure(enabled=True)
    b = _batcher(small, 4)
    try:
        for _ in range(4):
            _serve(b, _requests(16))
        snap = flight.snapshot()
    finally:
        b.close()
    by_cycle: dict = {}
    for _tid, code, cycle, tag, _arg, t0, t1 in match_spans(snap["events"]):
        if code in (EV_LANE_SCAN, EV_SCAN_WALL, EV_LAUNCH):
            by_cycle.setdefault(cycle, {}).setdefault(code, []).append(
                (t1 - t0, tag))
    walls = 0
    for spans in by_cycle.values():
        if EV_SCAN_WALL not in spans:
            continue
        walls += 1
        wall = spans[EV_SCAN_WALL][0][0]
        longest = max(d for d, _t in spans[EV_LANE_SCAN])
        launches = sum(d for d, _t in spans[EV_LAUNCH])
        assert longest <= wall <= longest + launches
        assert sorted(t for _d, t in spans[EV_LANE_SCAN]) == \
            sorted(t for _d, t in spans[EV_LAUNCH])
    assert walls >= 4


@pytest.mark.parametrize("confirm_workers", [1, 2])
@pytest.mark.parametrize("n_lanes", [1, 2])
def test_mesh_service_time_samples_do_not_count_a_stretch_twice(
        small, n_lanes, confirm_workers):
    """The admission queue math divides by the loop's time per cycle
    (one lane runs the same loop as several).
    With confirm workers the loop holds a cycle's confirm open across
    the next cycle's launch and scan, so a cycle's launch → resolve
    also holds its neighbours' work and those spans overlap (their sum
    passes the wall time); the samples fed to the estimator must not."""
    import threading
    import time

    flight.configure(enabled=True)
    b = _batcher(small, n_lanes, confirm_workers)
    samples = []

    class Recording(type(b._service)):
        __slots__ = ()

        def update(self, x):
            samples.append(x)
            return super().update(x)

    b._service = Recording()
    stop = threading.Event()

    def feeder():
        while not stop.is_set():
            _serve(b, _requests(8))

    threads = [threading.Thread(target=feeder) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        # cycles that compile are no samples: wait those out
        deadline = time.monotonic() + 120
        while len(samples) < 5 and time.monotonic() < deadline:
            time.sleep(0.05)
        n0, batch_us0 = len(samples), b.hist["batch"].sum_us
        t0 = time.perf_counter()
        time.sleep(1.5)
        wall = time.perf_counter() - t0
        taken = samples[n0 + 1:]     # the first may have begun before t0
        spans = (b.hist["batch"].sum_us - batch_us0) / 1e6
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
        b.close()
    assert not any(t.is_alive() for t in threads)
    samples = taken
    assert len(samples) >= 10
    assert sum(samples) <= wall
    # the precondition, where there are walkers to hold a confirm open
    # on: two cycles were open at once, so their own spans overlapped
    # (an inline confirm is over when its collection returns, and one
    # cycle follows the other)
    if confirm_workers > 1:
        assert spans > 1.2 * sum(samples)


def test_one_lane_keeps_every_series_as_it_was(small):
    flight.configure(enabled=True)
    b = _batcher(small, 1)
    try:
        for _ in range(3):
            _serve(b, _requests(16))
        assert b.lane_stage_us == {} and b.lane_cycle_us == {}
        text = _metrics(b)
    finally:
        b.close()
    assert "ipt_lane_stage_us" not in text
    assert "ipt_lane_cycle_us" not in text
    assert "lane_scan" not in text and "scan_wall" not in text
    # the families a one-lane server had, still there
    for series in ('ipt_stage_us_count{stage="scan_launch"}',
                   'ipt_stage_us_count{stage="lane_handoff"}',
                   'ipt_lane_requests_total{device="0"}',
                   "ipt_lane_count 1"):
        assert series in text


def test_the_recorder_keeps_lane_sums_beside_the_cycles():
    rec = FlightRecorder(enabled=True)
    rec.set_cycle(5)
    with rec.span(EV_SCAN_PACK):
        pass                                    # no ambient lane
    rec.set_lane(2)
    with rec.span(EV_SCAN_PACK) as sp:
        pass
    rec.span_at(EV_HANDOFF, 1_000, 4_000)
    rec.span_at(EV_LANE_SCAN, 0, 9_000)         # the ring's alone
    rec.set_lane(-1)
    sub, lanes = rec.take_with_lanes(5)
    assert lanes == {(2, "scan_pack"): sp.us, (2, "lane_handoff"): 3}
    assert sub["lane_handoff"] == 3 and sub["scan_pack"] >= sp.us
    assert "lane_scan" not in sub
    assert rec.take_with_lanes(5) == ({}, {}) and rec.take(5) == {}
    # a re-armed ring keeps its thread's lane
    rec.set_lane(1)
    rec.configure()
    rec.set_cycle(6)
    with rec.span(EV_SCAN_PACK):
        pass
    assert list(rec.take_with_lanes(6)[1]) == [(1, "scan_pack")]


# --------------------------------------- (c) the readers, on scrape pairs

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metrics." + name.replace(".", "_"),
        BENCH / "layer_metrics" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _scrape(requests, lane_scan_us, wall_us, own_us, cycles=100):
    """A `/metrics` text as a server of `len(requests)` lanes renders
    the series the readers use."""
    lines = ["ipt_lane_count %d" % len(requests)]
    for lane, n in enumerate(requests):
        lines.append('ipt_lane_requests_total{device="%d"} %d' % (lane, n))
    if len(requests) > 1:
        for lane, us in enumerate(lane_scan_us):
            labels = 'device="%d",stage="lane_scan"' % lane
            lines += ["ipt_lane_stage_us_sum{%s} %d" % (labels, us),
                      "ipt_lane_stage_us_count{%s} %d" % (labels, cycles)]
        for span, us in (("scan_wall", wall_us), ("dispatch_own", own_us)):
            lines += ['ipt_lane_cycle_us_sum{span="%s"} %d' % (span, us),
                      'ipt_lane_cycle_us_count{span="%s"} %d'
                      % (span, cycles)]
    return "\n".join(lines)


ZERO4 = _scrape([0] * 4, [0] * 4, 0, 0, cycles=0)

#: (scrape after, scrape before, {metric: value})
SCRAPE_PAIRS = {
    "even split, all four in flight together": (
        _scrape([400] * 4, [500_000] * 4, 500_000, 800_000), ZERO4,
        {"lanes.request_share_min": 100.0, "lanes.scan_overlap_share": 100.0,
         "lanes.serial_host_share": 40.0, "lanes.share_scan_ms": 5.0}),
    "one lane served nothing": (
        _scrape([600, 500, 500, 0], [600_000, 500_000, 500_000, 0],
                800_000, 1_000_000), ZERO4,
        {"lanes.request_share_min": 0.0,
         "lanes.scan_overlap_share": 100.0 * (2.0 - 1.0) / 3.0,
         "lanes.serial_host_share": 50.0,
         "lanes.share_scan_ms": 1_600_000 / 400 / 1e3}),
    "the lanes one after another": (
        _scrape([400] * 4, [250_000] * 4, 1_000_000, 2_000_000), ZERO4,
        {"lanes.request_share_min": 100.0, "lanes.scan_overlap_share": 0.0,
         "lanes.serial_host_share": 100.0, "lanes.share_scan_ms": 2.5}),
    "one lane": (
        _scrape([1600], [], 0, 0), _scrape([0], [], 0, 0),
        {"lanes.request_share_min": None, "lanes.scan_overlap_share": None,
         "lanes.serial_host_share": None, "lanes.share_scan_ms": None}),
    "four lanes of a program without the series (the parent)": (
        "ipt_lane_count 4\n" + "\n".join(
            'ipt_lane_requests_total{device="%d"} 400' % i for i in range(4)),
        "ipt_lane_count 4",
        {"lanes.request_share_min": 100.0, "lanes.scan_overlap_share": None,
         "lanes.serial_host_share": None, "lanes.share_scan_ms": None}),
}


@pytest.mark.parametrize("metric", ["lanes.request_share_min",
                                    "lanes.scan_overlap_share",
                                    "lanes.serial_host_share",
                                    "lanes.share_scan_ms"])
@pytest.mark.parametrize("case", sorted(SCRAPE_PAIRS))
def test_lane_readers_on_recorded_scrape_pairs(bench_path, metric, case):
    from harness import scrape

    after, before, want = SCRAPE_PAIRS[case]
    ctx = {"window": scrape.Window(scrape.parse_metrics(before),
                                   scrape.parse_metrics(after)),
           "seconds": 2.0}
    got = _reader(metric)(ctx)
    if want[metric] is None:
        assert got is None
    else:
        assert got == pytest.approx(want[metric])
