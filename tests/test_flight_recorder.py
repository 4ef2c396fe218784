"""Cycle flight recorder (ISSUE 12, docs/OBSERVABILITY.md "Cycle
flight recorder"): per-thread ring bound/evict/drop accounting,
cross-thread flow stitching for a request spanning a lane worker AND a
confirm worker, Perfetto/Chrome-trace schema round trip, overlap-report
math on a synthetic event stream with a KNOWN overlap fraction, the
``--no-flight-recorder`` escape hatch zeroing the surface, the
clean-path A/B overhead bound, the slow-ring worker=/tenant=/
generation= satellite, and the promlint / bench-trend satellite
checkers."""

import asyncio
import json
import time

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils import trace as trace_mod
from ingress_plus_tpu.utils.overlap import (
    brief,
    check_claims,
    overlap_report,
    spans_from_events,
)
from ingress_plus_tpu.utils.trace import (
    EV_CONFIRM,
    EV_CYCLE,
    EV_DEVICE,
    EV_DRAIN,
    EV_SUBMIT,
    EV_VERDICT,
    PH_B,
    PH_E,
    PH_I,
    FlightRecorder,
    flight,
    request_tag,
)

RULES = """
SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" "id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_URI|ARGS "@rx /etc/(?:passwd|shadow)" "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
"""


@pytest.fixture(scope="module")
def cr():
    return compile_ruleset(parse_seclang(RULES))


@pytest.fixture(autouse=True)
def fresh_flight():
    """Isolate the process-global recorder per test (rings re-arm
    lazily on the next event; enabled state restored to the default)."""
    flight.configure(ring_kb=256, enabled=True)
    yield
    flight.configure(ring_kb=256, enabled=True)


def _reqs(n, attack_every=2):
    out = []
    for i in range(n):
        if i % attack_every == 0:
            r = Request(uri="/p?q=1%27%20UNION%20SELECT%20x",
                        headers={}, body=b"", request_id="atk-%d" % i)
        else:
            r = Request(uri="/ok?page=%d" % i, headers={}, body=b"",
                        request_id="ben-%d" % i)
        out.append(r)
    return out


def _serve(batcher, reqs, timeout=60):
    futs = [batcher.submit(r) for r in reqs]
    return [f.result(timeout=timeout) for f in futs]


# ------------------------------------------------- ring accounting

def test_ring_bound_evict_drop_accounting():
    rec = FlightRecorder(ring_kb=1)   # floor: 64 slots
    cap = rec._cap()
    assert cap == 64
    n = 200
    for i in range(n):
        rec.instant(EV_SUBMIT, cycle=1, tag=i)
    snap = rec.snapshot()
    assert len(snap["events"]) == cap          # bounded, oldest evicted
    assert snap["dropped"] == n - cap          # every eviction counted
    # chronological, newest retained: tags are the LAST cap values
    tags = [e[5] for e in snap["events"]]
    assert tags == list(range(n - cap, n))
    # timestamps monotonic within the ring
    ts = [e[1] for e in snap["events"]]
    assert ts == sorted(ts)


def test_ring_cap_scales_with_kb():
    rec = FlightRecorder(ring_kb=256)
    assert rec._cap() == (256 * 1024) // trace_mod.EVENT_BYTES


# ------------------------------------- cross-thread flow stitching

def test_cross_thread_flow_lane_plus_confirm_worker(cr):
    """A request's path is followable across admission → lane worker →
    confirm worker → verdict: the submit/verdict flow tags match, and
    the cycle id stitches device spans (lane worker threads) to confirm
    spans (confirm worker threads)."""
    pipe = DetectionPipeline(cr, mode="block", confirm_workers=2)
    b = Batcher(pipe, max_batch=8, n_lanes=2)
    try:
        reqs = _reqs(32)
        vs = _serve(b, reqs)
        assert sum(v.attack for v in vs) == 16
        snap = flight.snapshot()
    finally:
        b.close()
    roots = {t["root"] for t in snap["threads"]}
    assert {"dispatch", "lane_worker", "confirm_worker",
            "watchdog", "oversized"} <= roots
    by_code = {}
    for e in snap["events"]:
        by_code.setdefault(e[2], []).append(e)
    # flow endpoints: every request's submit tag has a matching verdict
    sub_tags = {e[5] for e in by_code.get(EV_SUBMIT, ())}
    ver_tags = {e[5] for e in by_code.get(EV_VERDICT, ())}
    want = {request_tag(r.request_id) for r in reqs}
    assert want <= sub_tags
    assert want <= ver_tags
    # cycle stitching: device spans (lane workers) and confirm spans
    # (confirm workers) share cycle ids with the dispatch thread's
    # cycle envelopes — and run on DIFFERENT threads
    tid_root = {t["tid"]: t["root"] for t in snap["threads"]}
    dev_cycles = {e[4] for e in by_code.get(EV_DEVICE, ())
                  if tid_root[e[0]] == "lane_worker" and e[4] > 0}
    conf_cycles = {e[4] for e in by_code.get(EV_CONFIRM, ())
                   if tid_root[e[0]] == "confirm_worker" and e[4] > 0}
    cyc_cycles = {e[4] for e in by_code.get(EV_CYCLE, ())
                  if tid_root[e[0]] == "dispatch" and e[4] > 0}
    assert dev_cycles and conf_cycles
    assert dev_cycles <= cyc_cycles
    assert conf_cycles <= cyc_cycles
    assert dev_cycles & conf_cycles   # same cycle crossed both planes
    # both lanes and both confirm workers actually recorded
    assert {e[5] for e in by_code.get(EV_DEVICE, ())} >= {0, 1}
    assert {e[5] for e in by_code.get(EV_CONFIRM, ())} >= {0, 1}


# --------------------------------------------- Perfetto round trip

def test_chrome_trace_schema_round_trip(cr):
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        _serve(b, _reqs(24))
        ct = flight.chrome_trace(cycles=16)
    finally:
        b.close()
    # JSON round trip: the exact bytes /debug/trace serves load back
    loaded = json.loads(json.dumps(ct))
    events = loaded["traceEvents"]
    assert isinstance(events, list) and events
    phases = {e["ph"] for e in events}
    # matched begin/end: the exporter folds B/E into complete X slices
    # — no unmatched B or E phase ever reaches the output
    assert "B" not in phases and "E" not in phases
    assert "X" in phases and "M" in phases
    tids_meta = {e["tid"] for e in events if e["ph"] == "M"}
    per_thread_ts = {}
    for e in events:
        if e["ph"] == "M":
            assert e["name"] == "thread_name"
            assert e["args"]["name"]
            continue
        assert e["tid"] in tids_meta      # every event's thread named
        assert e["ts"] >= 0
        per_thread_ts.setdefault((e["tid"], e["ph"]), []).append(e["ts"])
        if e["ph"] == "X":
            assert e["dur"] > 0
    # monotonic timestamps: the global event list is time-sorted
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    # request flows: every finish has a start with the same id
    starts = {e["id"] for e in events if e["ph"] == "s"}
    finishes = {e["id"] for e in events if e["ph"] == "f"}
    assert finishes and finishes <= starts


# ------------------------------------------------ overlap-report math

def _ms(x):
    return int(x * 1e6)   # ms → ns


def _synthetic_snapshot():
    """Known structure: cycle [0,100]ms on dispatch, device busy
    [0,50]ms on a lane worker, confirm [30,90]ms on a confirm worker,
    drain [90,100]ms on dispatch.  Overlap = [30,50] = 20ms of the
    60ms confirm → fraction 1/3."""
    threads = [
        {"tid": 0, "root": "dispatch", "thread": "ipt-batcher",
         "dropped": 0},
        {"tid": 1, "root": "lane_worker", "thread": "ipt-device-0",
         "dropped": 0},
        {"tid": 2, "root": "confirm_worker", "thread": "ipt-confirm-1",
         "dropped": 0},
    ]
    events = [
        (0, _ms(0), EV_CYCLE, PH_B, 1, 0, 4),
        (1, _ms(0), EV_DEVICE, PH_B, 1, 0, 4),
        (2, _ms(30), EV_CONFIRM, PH_B, 1, 0, 4),
        (1, _ms(50), EV_DEVICE, PH_E, 1, 0, 0),
        (2, _ms(90), EV_CONFIRM, PH_E, 1, 0, 0),
        (0, _ms(90), EV_DRAIN, PH_B, 0, 0, 0),
        (0, _ms(100), EV_DRAIN, PH_E, 0, 0, 0),
        (0, _ms(100), EV_CYCLE, PH_E, 1, 0, 0),
    ]
    events.sort(key=lambda e: e[1])
    return {"enabled": True, "ring_kb": 256, "threads": threads,
            "events": events, "dropped": 0}


def test_overlap_backfills_silent_lanes():
    """A lane that recorded NO device span (wedged/starved) must show
    idle 1.0, not vanish from the report."""
    rep = overlap_report(_synthetic_snapshot(), confirm_workers=2,
                         n_lanes=3)
    assert rep["lane_idle_share"]["1"] == 1.0
    assert rep["lane_idle_share"]["2"] == 1.0
    assert rep["lane_idle_share"]["0"] == pytest.approx(0.5, abs=1e-4)


def test_overlap_report_known_fraction():
    rep = overlap_report(_synthetic_snapshot(), confirm_workers=2,
                         n_lanes=1)
    assert rep is not None
    assert rep["cycles"] == 1
    assert rep["window_ms"] == 100.0
    assert rep["scan_confirm_overlap"] == pytest.approx(20 / 60,
                                                        abs=1e-4)
    assert rep["lane_idle_share"]["0"] == pytest.approx(0.5, abs=1e-4)
    assert rep["drain_occupancy"] == pytest.approx(0.1, abs=1e-4)
    # confirm (60ms) out-lasts device (50ms): the cycle's critical path
    assert next(iter(rep["critical_path"])) == "confirm_walk"
    # serialized residue: confirm worker holds the largest exclusive
    # share (40ms of the 90ms any-busy union)
    top = rep["serialized_residue"][0]
    assert top["thread"].startswith("confirm_worker")
    assert top["exclusive_share"] == pytest.approx(40 / 90, abs=1e-3)
    b = brief(rep)
    assert b["scan_confirm_overlap"] == rep["scan_confirm_overlap"]
    assert b["bounding_thread"]["thread"].startswith("confirm_worker")


def test_overlap_spans_and_empty_window():
    spans = spans_from_events(_synthetic_snapshot())
    assert len(spans) == 4
    assert overlap_report({"threads": [], "events": [],
                           "dropped": 0}) is None
    # missing report is itself a LOUD claim-check finding
    assert check_claims(None)


def test_check_claims_flags_serialized_thread():
    snap = _synthetic_snapshot()
    # remove the confirm span → device alone, 100% exclusive
    snap["events"] = [e for e in snap["events"] if e[2] != EV_CONFIRM]
    rep = overlap_report(snap, confirm_workers=4, n_lanes=2)
    warns = check_claims(rep)
    assert any("critical path" in w for w in warns)


# ------------------------------------------------- escape hatch

def test_no_flight_recorder_zeroes_surface(cr):
    flight.configure(enabled=False)
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        vs = _serve(b, _reqs(16))
        assert len(vs) == 16              # verdicts unaffected
        snap = flight.snapshot()
        assert snap["events"] == []
        assert snap["threads"] == []
        assert snap["enabled"] is False
        ct = flight.chrome_trace()
        assert ct["traceEvents"] == []
        # /debug/trace reports disabled with an empty event list
        from ingress_plus_tpu.serve.server import ServeLoop
        serve = ServeLoop(b, socket_path="/tmp/ipt-flight-test.sock")

        async def _call():
            return await serve._route_http("GET", "/debug/trace", b"")

        status, _ctype, body = asyncio.run(_call())
        assert status.startswith("200")
        out = json.loads(body)
        assert out == {"enabled": False, "traceEvents": []}
        # /healthz pipeline_overlap goes null
        assert serve._pipeline_overlap_brief() is None
    finally:
        b.close()


def test_debug_trace_endpoint_perfetto_loadable(cr):
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        _serve(b, _reqs(12))
        from ingress_plus_tpu.serve.server import ServeLoop
        serve = ServeLoop(b, socket_path="/tmp/ipt-flight-test2.sock")

        async def _call():
            return await serve._route_http(
                "GET", "/debug/trace?cycles=8", b"")

        status, ctype, body = asyncio.run(_call())
        assert status.startswith("200")
        out = json.loads(body)
        assert out["traceEvents"]
        assert {e["ph"] for e in out["traceEvents"]} <= \
            {"M", "X", "i", "s", "f"}
        # the healthz brief carries the compact block
        ov = serve._pipeline_overlap_brief()
        assert ov is not None and ov["cycles"] >= 1
    finally:
        b.close()


# ----------------------------------------------- clean-path overhead

def test_clean_path_ab_overhead(cr):
    """Recorder-on vs recorder-off A/B on the library detect path.
    The pinned <3% budget is enforced on the bench's same-host A/B
    (CHANGES.md carries the measured number); this in-suite assertion
    uses a noise-tolerant bound so a loaded CI host cannot flake it,
    while still catching an accidentally-hot record path (a 2x
    regression fails loudly).  The two sides are timed call by call,
    in turn, and compared at their lower quartile: a block of calls
    per side read 0.67-1.36 on an unchanged tree under the suite's
    own load (xdist), the quartile of interleaved calls 1.02-1.08."""
    pipe = DetectionPipeline(cr, mode="block")
    reqs = _reqs(16)
    pipe.detect(reqs)                      # compile outside the clock

    times = {False: [], True: []}
    for _ in range(150):
        for enabled in (False, True):
            flight.configure(enabled=enabled)
            t0 = time.perf_counter()
            pipe.detect(reqs)
            times[enabled].append(time.perf_counter() - t0)
    t_off, t_on = (sorted(times[e])[len(times[e]) // 4]
                   for e in (False, True))
    ratio = t_on / t_off
    assert ratio < 1.30, (
        "flight recorder clean-path overhead ratio %.3f (on=%.4fs "
        "off=%.4fs) — the record() path got hot" % (ratio, t_on, t_off))


# ------------------------------------------- slow-ring satellite dims

def test_slow_ring_carries_worker_tenant_generation(cr):
    pipe = DetectionPipeline(cr, mode="block", confirm_workers=2)
    b = Batcher(pipe, max_batch=8, n_lanes=2)
    try:
        reqs = _reqs(24)
        for i, r in enumerate(reqs):
            r.tenant = i % 3
        vs = _serve(b, reqs)
        assert {v.confirm_worker for v in vs if not v.fail_open} \
            == {0, 1}
        exemplars = b.slow.snapshot()
        assert exemplars
        for e in exemplars:
            assert "worker" in e and "tenant" in e and "generation" in e
            assert e["tenant"] in (0, 1, 2)
            assert e["generation"] == pipe.generation_tag
            assert e["worker"] in (-1, 0, 1)
        assert {e["worker"] for e in exemplars} & {0, 1}
    finally:
        b.close()


def test_dbg_latency_renders_new_dims(cr):
    from ingress_plus_tpu.control.dbg import render_latency
    slow = {"slowest": [{"request_id": "r1", "e2e_us": 1200,
                         "queue_us": 10, "batch": {"prep_us": 1},
                         "lane": 0, "worker": 1, "tenant": 7,
                         "generation": "crs-4.3.0+g1",
                         "rule_ids": [942100]}]}
    out = render_latency("", slow)
    assert "wrk" in out and "ten" in out and "gen" in out
    assert "crs-4.3.0+g" in out and " 7 " in out


def test_dbg_timeline_render(cr):
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        _serve(b, _reqs(12))
        ct = flight.chrome_trace(cycles=6)
    finally:
        b.close()
    from ingress_plus_tpu.control.dbg import render_timeline
    out = render_timeline(ct)
    assert "cycle " in out
    assert "scan_dispatch" in out and "host_prep" in out
    assert "|" in out and "#" in out
    # disabled surface renders the explanation, not a stack trace
    assert "disabled" in render_timeline(
        {"enabled": False, "traceEvents": []})


# ------------------------------------------------ promlint satellite

def test_promlint_checker_units():
    from ingress_plus_tpu.analysis.promlint import check_exposition
    good = "\n".join([
        "# HELP ipt_good_total good things",
        "# TYPE ipt_good_total counter",
        "ipt_good_total 3",
        "# HELP ipt_h histogram of things",
        "# TYPE ipt_h histogram",
        'ipt_h_bucket{le="1"} 1',
        'ipt_h_bucket{le="+Inf"} 2',
        "ipt_h_sum 2",
        "ipt_h_count 2",
    ])
    assert check_exposition(good) == []
    assert any("namespace prefix" in f for f in check_exposition(
        "# HELP foo_total x\n# TYPE foo_total counter\nfoo_total 1"))
    assert any("_total" in f for f in check_exposition(
        "# HELP ipt_bad x\n# TYPE ipt_bad counter\nipt_bad 1"))
    assert any("TYPE without # HELP" in f for f in check_exposition(
        "# TYPE ipt_x_total counter\nipt_x_total 1"))
    assert any("no # TYPE" in f for f in check_exposition(
        "ipt_untyped_total 1"))
    assert any("+Inf" in f for f in check_exposition(
        "# HELP ipt_h x\n# TYPE ipt_h histogram\n"
        'ipt_h_bucket{le="1"} 1'))
    assert any("non-monotonic" in f for f in check_exposition(
        "# HELP ipt_h x\n# TYPE ipt_h histogram\n"
        'ipt_h_bucket{le="1"} 5\nipt_h_bucket{le="+Inf"} 2'))
    # unbounded per-rule series: the satellite's reason to exist
    unbounded = ["# HELP ipt_rule_total x", "# TYPE ipt_rule_total counter"]
    unbounded += ['ipt_rule_total{rule="%d"} 1' % i for i in range(50)]
    assert any("unbounded" in f
               for f in check_exposition("\n".join(unbounded)))


def test_promlint_live_exposition_clean(cr):
    """The REAL exposition passes its own lint after multi-tenant
    traffic (the in-process twin of the CI gate, on the small pack)."""
    from ingress_plus_tpu.analysis.promlint import check_exposition
    from ingress_plus_tpu.serve.server import ServeLoop
    pipe = DetectionPipeline(cr, mode="monitoring")
    b = Batcher(pipe, max_batch=16)
    try:
        reqs = _reqs(64)
        for i, r in enumerate(reqs):
            r.tenant = i % 48     # past the 30-series fold budget
        _serve(b, reqs)
        serve = ServeLoop(b, socket_path="/tmp/ipt-promlint-test.sock")
        text = serve._metrics_text()
    finally:
        b.close()
    assert check_exposition(text) == []
    # the tenant fold actually engaged (48 tenants > the 30 budget)
    assert 'tenant="other"' in text
    # HELP precedes TYPE for the headline metrics
    assert "# HELP ipt_requests_total" in text


# ---------------------------------------------- bench-trend satellite

def test_bench_trend_gate(tmp_path):
    from tools.bench_trend import REGRESSION_GATE, load_artifacts, trend

    def art(tag, value, error=None):
        parsed = {"value": value, "platform": "cpu"}
        if error:
            parsed["error"] = error
        (tmp_path / ("BENCH_%s.json" % tag)).write_text(json.dumps(
            {"parsed": parsed}))

    # no artifacts → SKIP (a fresh tree never fails CI)
    assert trend(load_artifacts(str(tmp_path)))["status"] == "SKIP"
    art("r01", 1000.0)
    assert trend(load_artifacts(str(tmp_path)))["status"] == "SKIP"
    # healthy growth → OK
    art("r02", 1500.0)
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "OK" and rep["latest"] == "r02"
    # >10% regression vs the previous snapshot → FAIL
    art("r03", 1500.0 * (1 - REGRESSION_GATE) - 1)
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "FAIL"
    assert "regressed" in rep["detail"]
    # recovery → OK again, with the best-ever note not gating
    art("r04", 1490.0)
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "OK"
    # a regression measured on a DEGRADED host (the artifact's own
    # error tag) warns but does not hard-fail CI on infrastructure
    art("r05", 500.0, error="tpu-unavailable: backend init hung")
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "OK"
    assert any("degraded-host" in w for w in rep["warnings"])


# ------------------------------- stage shares (ISSUE 13 host_prep rank)

def test_overlap_stage_shares_known_values():
    """device busy [0,50], confirm [30,90] → stage-busy union 90ms:
    device busy 50/90 with [0,30] exclusive, confirm [50,90]
    exclusive; no host_prep span recorded → 0 shares."""
    rep = overlap_report(_synthetic_snapshot(), confirm_workers=2,
                         n_lanes=1)
    ss = rep["stage_shares"]
    assert ss["device_scan"]["busy_share"] == pytest.approx(50 / 90,
                                                            abs=1e-3)
    assert ss["device_scan"]["exclusive_share"] == pytest.approx(
        30 / 90, abs=1e-3)
    assert ss["confirm"]["exclusive_share"] == pytest.approx(40 / 90,
                                                             abs=1e-3)
    assert ss["host_prep"]["busy_share"] == 0.0
    # healthy structure: host prep does NOT rank above the device
    assert not any("host_prep" in w for w in check_claims(rep))


def test_check_claims_flags_host_prep_above_device():
    """A timeline where host prep out-ranks the device lanes in
    exclusive busy must produce the ISSUE 13 claim-check warning — the
    condition the raw-byte device path exists to remove."""
    from ingress_plus_tpu.utils.trace import EV_PREP

    threads = [
        {"tid": 0, "root": "dispatch", "thread": "ipt-batcher",
         "dropped": 0},
        {"tid": 1, "root": "lane_worker", "thread": "ipt-device-0",
         "dropped": 0},
    ]
    events = [
        (0, _ms(0), EV_CYCLE, PH_B, 1, 0, 4),
        (0, _ms(0), EV_PREP, PH_B, 1, 0, 4),
        (0, _ms(60), EV_PREP, PH_E, 1, 0, 0),
        (1, _ms(60), EV_DEVICE, PH_B, 1, 0, 4),
        (1, _ms(70), EV_DEVICE, PH_E, 1, 0, 0),
        (0, _ms(100), EV_CYCLE, PH_E, 1, 0, 0),
    ]
    snap = {"enabled": True, "ring_kb": 256, "threads": threads,
            "events": sorted(events, key=lambda e: e[1]), "dropped": 0}
    rep = overlap_report(snap, confirm_workers=1, n_lanes=1)
    ss = rep["stage_shares"]
    assert ss["host_prep"]["exclusive_share"] > \
        ss["device_scan"]["exclusive_share"]
    warns = check_claims(rep)
    assert any("host_prep ranks ABOVE" in w for w in warns)


# ------------------------- bench-trend backend guard (ISSUE 13 sat.)

def test_bench_trend_refuses_cross_backend(tmp_path):
    """A CPU→TPU flip (or the reverse fallback) must never read as a
    10x win or a regression: the gate refuses the comparison, and the
    best-ever note only compares same-backend points."""
    from tools.bench_trend import load_artifacts, trend

    def art(tag, value, platform):
        (tmp_path / ("BENCH_%s.json" % tag)).write_text(json.dumps(
            {"parsed": {"value": value, "platform": platform}}))

    art("r01", 1000.0, "cpu")
    art("r02", 8000.0, "tpu")      # flip up: NOT a 8x win
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "SKIP"
    assert any("not comparable" in w for w in rep["warnings"])
    art("r03", 900.0, "cpu")       # flip back down: NOT a regression
    assert trend(load_artifacts(str(tmp_path)))["status"] == "SKIP"
    art("r04", 950.0, "cpu")       # same backend again: gating resumes
    rep = trend(load_artifacts(str(tmp_path)))
    assert rep["status"] == "OK"
    # the tpu point is not this trajectory's best-ever
    assert not any("r02" in w for w in rep.get("warnings", []))
    art("r05", 100.0, "cpu")       # same-backend regression still gates
    assert trend(load_artifacts(str(tmp_path)))["status"] == "FAIL"


# ------------------------------------- the span primitive (ISSUE 27)

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what the
    primitive hands the profiler and when it enters and leaves."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, self.kwargs))


@pytest.fixture
def fake_annotation(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(trace_mod, "_annotation_cls", _FakeAnnotation)
    return _FakeAnnotation.log


def test_span_writes_ring_accumulator_and_annotation_from_one_call(
        fake_annotation):
    rec = FlightRecorder()
    rec.set_cycle(7)
    with rec.span(trace_mod.EV_SCAN_PACK, tag=2, arg=3) as sp:
        time.sleep(0.002)
    # (a) the ring's begin/end pair, under the thread's ambient cycle id
    events = rec.snapshot()["events"]
    assert [(e[2], e[3], e[4], e[5]) for e in events] == [
        (trace_mod.EV_SCAN_PACK, PH_B, 7, 2),
        (trace_mod.EV_SCAN_PACK, PH_E, 7, 2)]
    assert events[0][6] == 3                      # arg rides the begin
    ring_us = (events[1][1] - events[0][1]) // 1000
    # (b) the cycle's accumulator holds the same elapsed time, once
    assert sp.us == ring_us >= 2000
    assert rec.take(7) == {"scan_pack": sp.us}
    assert rec.take(7) == {}
    # (c) one profiler annotation, entered and left, named and tagged
    assert fake_annotation == [
        ("enter", "ipt:scan_pack", {"cycle": 7, "n": 3}),
        ("exit", "ipt:scan_pack", {"cycle": 7, "n": 3})]


def test_spans_nest_under_the_cycle_id_across_threads(fake_annotation):
    """A span opened on a worker that the cycle id travelled to
    (flight.scoped) accumulates under that cycle, beside the spans of
    the thread that owns the cycle; begin/end is the same primitive."""
    import threading

    rec = FlightRecorder()
    rec.set_cycle(11)
    env = rec.span(trace_mod.EV_CYCLE, arg=4).begin()

    def work():
        with rec.span(trace_mod.EV_SCAN_LAUNCH, arg=2):
            with rec.span(trace_mod.EV_SCAN_WAIT):
                pass

    t = threading.Thread(target=lambda: rec.scoped(11, work))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    with rec.span(trace_mod.EV_CONFIRM_FOLD):
        pass
    rec.span_at(trace_mod.EV_HANDOFF, 1_000, 6_000, tag=1)
    env.end()
    acc = rec.take(11)
    assert set(acc) == {"scan_launch", "scan_wait", "confirm_fold",
                        "lane_handoff"}
    assert acc["lane_handoff"] == 5               # (6000 - 1000) ns
    assert acc["scan_launch"] >= acc["scan_wait"]   # nested inside it
    # the envelope itself is a span (ring + annotation), not a sub-stage
    spans = trace_mod.match_spans(rec.snapshot()["events"])
    assert {s[1] for s in spans} == {
        trace_mod.EV_CYCLE, trace_mod.EV_SCAN_LAUNCH,
        trace_mod.EV_SCAN_WAIT, trace_mod.EV_CONFIRM_FOLD,
        trace_mod.EV_HANDOFF}
    assert {s[2] for s in spans} == {11}
    # a back-dated span is ring-only: nothing for it on the profiler
    names = [n for kind, n, _kw in fake_annotation if kind == "enter"]
    assert sorted(names) == ["ipt:confirm_fold", "ipt:cycle",
                             "ipt:scan_launch", "ipt:scan_wait"]


def test_span_is_a_noop_without_the_flight_recorder(fake_annotation):
    rec = FlightRecorder(enabled=False)
    sp = rec.span(trace_mod.EV_SCAN_PACK, arg=3)
    assert sp is trace_mod._NO_SPAN       # no object built per call
    with sp as inner:
        pass
    assert inner.us == 0 and sp.begin() is sp and sp.end() is None
    rec.span_at(trace_mod.EV_HANDOFF, 0, 5_000)
    assert rec.take(0) == {} and rec.snapshot()["events"] == []
    assert fake_annotation == []


def test_accumulators_nobody_takes_stay_bounded(fake_annotation):
    rec = FlightRecorder()
    for cid in range(1, 200):
        rec.span_at(trace_mod.EV_SCAN_PACK, 0, 1_000, cycle=cid)
    assert len(rec._acc) <= trace_mod._MAX_OPEN_CYCLES
    assert rec.take(199) == {"scan_pack": 1} and rec.take(1) == {}
    rec.reset()
    assert rec._acc == {}


def test_batch_trace_carries_sub_spans_inside_their_stages(cr):
    """Every dispatch's sub-spans lie inside the stage they open, and
    the sub-stage histograms count dispatches (drain_idle too: the
    loop's wait for each dispatch's work, empty drains included)."""
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        _serve(b, _reqs(24))
        traces = b.traces.snapshot()
        n_batches = b.hist["batch"].total
    finally:
        b.close()
    assert traces and n_batches == len(traces)
    for t in traces:
        sub = t["sub_us"]
        assert set(sub) == set(trace_mod.PER_DISPATCH), sub
        assert (sub["scan_pack"] + sub["scan_launch"] + sub["scan_wait"]
                <= t["engine_us"])
        assert sub["confirm_walk"] + sub["confirm_fold"] <= t["confirm_us"]
        assert sub["lane_handoff"] <= t["batch_us"]
    for name in trace_mod.PER_DISPATCH:
        assert b.subhist[name].total == n_batches, name
    assert set(b.subhist) == set(trace_mod.SUBSTAGES)
    assert set(trace_mod.SUBSTAGES) == set(trace_mod.PER_DISPATCH) | {
        "reply"}
    # the exemplars' stage dict names the parts too
    stages = b.slow.snapshot()[0]["batch"]
    assert {"scan_launch_us", "confirm_fold_us", "gc_us"} <= set(stages)


def test_sub_stage_series_are_absent_without_the_recorder(cr):
    flight.configure(enabled=False)
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        vs = _serve(b, _reqs(8))
        assert all(v.resolved_ns == 0 for v in vs)
        assert all(h.total == 0 for h in b.subhist.values())
        assert b.traces.snapshot()[0]["sub_us"] == {}
        from ingress_plus_tpu.serve.server import ServeLoop
        text = ServeLoop(b, socket_path="/tmp/ipt-flight-test3.sock"
                         )._metrics_text()
        assert 'stage="scan"' in text and 'stage="scan_pack"' not in text
        assert "ipt_device_launches_total" in text
    finally:
        b.close()


def test_lane_handoff_is_the_callers_wait_less_the_closures_run():
    from ingress_plus_tpu.serve.lanes import Lane

    lane = Lane(0)
    try:
        flight.set_cycle(5)
        t0 = time.monotonic_ns()
        assert lane.call(lambda: time.sleep(0.02) or 41, 10.0) == 41
        wall_us = (time.monotonic_ns() - t0) // 1000
    finally:
        lane.close()
    handoff = flight.take(5)["lane_handoff"]
    assert 0 <= handoff <= wall_us - 20_000
    snap = flight.snapshot()
    root = {t["tid"]: t["root"] for t in snap["threads"]}
    spans = [s for s in trace_mod.match_spans(snap["events"])
             if s[1] == trace_mod.EV_HANDOFF]
    # to the worker (tag 0, on its ring), and back (tag 1, the caller's)
    assert [(s[3], root[s[0]] == "lane_worker") for s in spans] == [
        (0, True), (1, False)]
    assert sum((s[6] - s[5]) // 1000 for s in spans) == pytest.approx(
        handoff, abs=2)
    # lane_call covers the whole wait on the caller's thread
    (call,) = [s for s in trace_mod.match_spans(snap["events"])
               if s[1] == trace_mod.EV_LANE_CALL]
    assert (call[6] - call[5]) // 1000 >= 20_000 + handoff - 2


def test_confirm_pool_workers_stamp_no_lane_handoff(cr):
    pipe = DetectionPipeline(cr, mode="block", confirm_workers=2)
    try:
        flight.set_cycle(9)
        pipe.detect(_reqs(8))
    finally:
        pipe.confirm_pool.close()
    assert "lane_handoff" not in flight.take(9)


def test_gc_watch_counts_pauses_and_records_spans():
    import gc

    rec = FlightRecorder()
    rec.register_thread("test")            # the hook never registers one
    watch = trace_mod.GcWatch(rec)
    watch.install()
    watch.install()                        # idempotent
    try:
        before = list(watch.collections_by_gen)
        gc.collect()
        gc.collect(0)
    finally:
        watch.uninstall()
    assert gc.callbacks.count(watch._on_gc) == 0
    assert watch.collections_by_gen[2] == before[2] + 1
    assert watch.collections_by_gen[0] >= before[0] + 1
    assert watch.pause_us() == sum(watch.pause_us_by_gen) > 0
    spans = [s for s in trace_mod.match_spans(rec.snapshot()["events"])
             if s[1] == trace_mod.EV_GC]
    assert {s[4] for s in spans} >= {0, 2}         # arg = generation
    gc.collect()                           # uninstalled: counts stay
    assert watch.collections_by_gen[2] == before[2] + 1


def test_empty_drains_count_for_the_dispatch_that_ends_them(cr):
    """An idle server's 50 ms drain time-outs are nobody's until a
    dispatch follows: a scrape after the traffic has stopped reads no
    more idle time than one before it stopped."""
    pipe = DetectionPipeline(cr, mode="block")
    b = Batcher(pipe, max_batch=8)
    try:
        _serve(b, _reqs(4))
        time.sleep(0.3)                    # ~6 empty drains
        assert b.subhist["drain_idle"].total == b.hist["batch"].total
        idle_before = b.subhist["drain_idle"].sum_us
        _serve(b, _reqs(4))
        waited = b.subhist["drain_idle"].sum_us - idle_before
        last = b.traces.snapshot()[-1]["sub_us"]["drain_idle"]
    finally:
        b.close()
    assert waited >= 250_000               # the idle stretch, counted once
    assert last <= waited


def test_gc_hook_takes_no_lock_and_registers_nothing():
    """A collection starts wherever its thread allocates — also inside
    the recorder's own locked sections (thread registration allocates
    a ring under the lock).  The hook must not need that lock: a
    thread with no ring yet only counts; it is not registered from
    inside a collection."""
    import gc
    import threading

    rec = FlightRecorder()
    watch = trace_mod.GcWatch(rec)
    watch.install()
    done = []

    def worker():
        # no ring yet on this thread, and the registration lock is
        # held, as it is while _register builds the ring
        with rec._lock:
            gc.collect()
        done.append(len(rec.snapshot()["threads"]))

    t = threading.Thread(target=worker)
    try:
        before = watch.collections_by_gen[2]
        t.start()
        t.join(timeout=20)
        assert not t.is_alive(), "the GC hook waited for the lock"
    finally:
        watch.uninstall()
    assert watch.collections_by_gen[2] == before + 1
    assert done == [0]                     # counted, nothing registered
