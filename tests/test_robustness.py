"""Fail-safe serve plane (docs/ROBUSTNESS.md): bounded admission +
deadline shedding, the brownout degradation ladder, the dispatch
watchdog + circuit breaker + CPU fallback, the deterministic
fault-injection harness, exporter backoff/spool bounding, and the
websocket sticky-fail-open path.

The invariant under test everywhere: every admitted request resolves to
exactly one verdict, and no fault becomes an unhandled exception or a
block.
"""

import asyncio
import json
import socket
import threading
import time
import urllib.request
from concurrent.futures import Future

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import (
    DetectionPipeline,
    LoadController,
)
from ingress_plus_tpu.serve.batcher import Batcher, CircuitBreaker
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.faults import (
    ATTACK_URI,
    FaultError,
    FaultPlan,
    run_fault_matrix,
)

RULES = """
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_URI|ARGS "@rx (?i)<script" \
    "id:941100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-xss'"
"""


@pytest.fixture(scope="module")
def cr():
    return compile_ruleset(parse_seclang(RULES))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """Every test starts and ends without an active fault plan."""
    faults.clear()
    yield
    faults.clear()


def _mk_batcher(cr, **kw):
    kw.setdefault("max_batch", 16)
    kw.setdefault("max_delay_s", 0.001)
    b = Batcher(DetectionPipeline(cr, mode="block"), **kw)
    # pre-compile the serve shapes so hang budgets in tests never race
    # a first-dispatch XLA compile
    warm = [Request(uri="/w%d" % i, request_id="w%d" % i)
            for i in range(kw["max_batch"])]
    for size in (1, 4, kw["max_batch"]):
        b.pipeline.detect(warm[:size])
    return b


# ------------------------------------------------------------ FaultPlan

def test_faultplan_parse_schedule_and_determinism():
    plan = FaultPlan.from_spec(
        "dispatch_raise:after=2,times=2;slow_confirm:delay_s=0.5")
    # after=2: arrivals 0,1 skip; 2,3 fire; times=2: 4+ exhausted
    fires = [plan.fire("dispatch_raise") is not None for _ in range(6)]
    assert fires == [False, False, True, True, False, False]
    assert plan.fire("export_5xx") is None      # site not in the plan
    r = plan.rules["slow_confirm"]
    assert r.delay_s == 0.5 and r.times is None and r.after == 0
    # probabilistic plans replay identically under the same seed
    a = FaultPlan.from_spec("export_5xx:prob=0.5", seed=7)
    b = FaultPlan.from_spec("export_5xx:prob=0.5", seed=7)
    seq_a = [a.fire("export_5xx") is not None for _ in range(32)]
    seq_b = [b.fire("export_5xx") is not None for _ in range(32)]
    assert seq_a == seq_b and True in seq_a and False in seq_a
    snap = plan.snapshot()
    assert {r["site"] for r in snap["rules"]} == {"dispatch_raise",
                                                  "slow_confirm"}


def test_faultplan_rejects_bad_specs():
    with pytest.raises(ValueError):
        FaultPlan.from_spec("not_a_site:times=1")
    with pytest.raises(ValueError):
        FaultPlan.from_spec("dispatch_hang:bogus_arg=1")
    with pytest.raises(ValueError):
        FaultPlan.from_spec("")


def test_faultplan_env_install():
    env = {"IPT_FAULTS": "swap_fail:times=1", "IPT_FAULTS_SEED": "3"}
    plan = faults.install_from_env(env)
    assert plan is not None and faults.active() is plan
    assert plan.seed == 3
    with pytest.raises(FaultError):
        faults.raise_if("swap_fail")
    assert not faults.fire("swap_fail")       # times=1 exhausted
    faults.clear()
    assert faults.install_from_env({}) is None


# ------------------------------------------------------- LoadController

def test_load_controller_hysteresis():
    lc = LoadController(up_us=(100.0, 200.0), down_factor=0.5,
                        dwell_s=2.0, alpha=1.0,   # alpha=1: no smoothing
                        up_confirm_s=0.5)
    t = 1000.0
    assert lc.observe(50, now=t) == 0
    # a single over-threshold spike does NOT step (confirm window)...
    assert lc.observe(150, now=t + 0.01) == 0
    # ...a recovered signal resets the window...
    assert lc.observe(50, now=t + 0.2) == 0
    assert lc.observe(150, now=t + 0.3) == 0
    assert lc.observe(150, now=t + 0.7) == 0   # window restarted at 0.3
    # ...sustained pressure steps up, one rung per served window
    assert lc.observe(150, now=t + 0.9) == 1
    assert lc.observe(250, now=t + 1.0) == 1
    assert lc.observe(250, now=t + 1.5) == 2
    # signal drops below down threshold, but dwell not served: hold
    assert lc.observe(10, now=t + 2.0) == 2
    # dwell served: step down ONE rung per observation
    assert lc.observe(10, now=t + 4.0) == 1
    assert lc.observe(10, now=t + 5.0) == 1   # dwell restarts per change
    assert lc.observe(10, now=t + 7.0) == 0
    assert lc.steps_up == 2 and lc.steps_down == 2
    # a borderline signal (between down and up thresholds) never flaps
    lc2 = LoadController(up_us=(100.0, 200.0), down_factor=0.5,
                         dwell_s=0.0, alpha=1.0, up_confirm_s=0.0)
    lc2.observe(150, now=t)
    assert lc2.level == 1
    for i in range(10):
        lc2.observe(80, now=t + i)   # above 0.5*100, below 200
    assert lc2.level == 1
    # single-spike clamp: observations cap at obs_cap, so one huge
    # outlier (post-compile backlog) cannot catapult the signal
    lc3 = LoadController(up_us=(100.0, 200.0), alpha=0.2)
    lc3.observe(10_000_000, now=t)
    assert lc3.ewma.get() <= lc3.obs_cap_us
    lc3.observe(10_000_000, now=t + 0.1)
    assert lc3.ewma.get() <= lc3.obs_cap_us


def test_load_controller_deadline_derivation():
    lc = LoadController()
    lc.configure_deadline(0.25)
    assert lc.up_us == (62_500.0, 150_000.0)
    assert lc.snapshot()["mode"] == "full"


# ------------------------------------------------------- CircuitBreaker

def test_circuit_breaker_transitions():
    brk = CircuitBreaker(failure_threshold=2, cooldown_s=0.15)
    assert brk.route() == "device"
    brk.record_failure()
    assert brk.state == "closed"            # below threshold
    brk.record_failure()
    assert brk.state == "open" and brk.trips == 1
    assert brk.route() == "fallback"        # cooldown not served
    time.sleep(0.2)
    assert brk.route() == "canary"          # half-open probe
    brk.record_failure()                    # canary failed: re-open
    assert brk.state == "open" and brk.trips == 2
    assert brk.route() == "fallback"
    time.sleep(0.2)
    assert brk.route() == "canary"
    brk.record_success()                    # canary ok: closed
    assert brk.state == "closed" and brk.closes == 1
    # a hang trips immediately, no threshold
    brk.trip("hang")
    assert brk.state == "open" and brk.last_trip_reason == "hang"
    snap = brk.snapshot()
    assert snap["trips"] == 3 and snap["state"] == "open"


# ------------------------------------------------- bounded admission

def test_bounded_admission_sheds_fail_open(cr):
    """Queue cap reached → requests shed fail-open AT enqueue, every
    future still resolves (never strands, never blocks)."""
    b = _mk_batcher(cr, queue_cap=8, hard_deadline_s=0.5)
    faults.install(FaultPlan.from_spec(
        "slow_confirm:times=50,delay_s=0.05"))
    try:
        futs = [b.submit(Request(uri="/x?i=%d" % i, request_id=str(i)))
                for i in range(200)]
        vs = [f.result(timeout=60) for f in futs]
        assert len(vs) == 200
        assert not any(v.blocked for v in vs)
        shed = dict(b.pipeline.stats.shed)
        assert shed.get("queue_full", 0) + shed.get("deadline", 0) > 0
        n_shed = sum(shed.values())
        assert sum(1 for v in vs if v.fail_open) >= n_shed
    finally:
        b.close()


def test_deadline_shed_by_queue_math(cr):
    """Queue math predicts a deadline miss → shed at enqueue without
    touching the queue (reason="deadline")."""
    b = _mk_batcher(cr, queue_cap=1024, hard_deadline_s=0.25)
    # freeze the dispatch thread out of the picture: queued work stays
    # queued, the estimator is set by hand
    b._stop.set()
    b._thread.join(timeout=5)
    for _ in range(8):          # past the cold-estimator sample floor
        b._service.update(1.0)  # "one second per cycle" service rate
    f1 = b.submit(Request(uri="/a", request_id="a"))   # depth 0: admitted
    f2 = b.submit(Request(uri="/b", request_id="b"))   # est 2s > 0.25: shed
    assert not f1.done()
    assert f2.done() and f2.result().fail_open
    assert b.pipeline.stats.shed.get("deadline") == 1
    b.close()
    # close() drained the admitted request fail-open (shutdown contract)
    assert f1.done() and f1.result().fail_open


def test_brownout_floor_sheds_at_admission(cr):
    b = _mk_batcher(cr)
    try:
        b.pipeline.load_controller.level = 2
        f = b.submit(Request(uri="/x", request_id="x"))
        v = f.result(timeout=5)
        assert v.fail_open and v.degraded and not v.blocked
        assert b.pipeline.stats.shed.get("brownout") == 1
        assert b.pipeline.stats.degraded == 1
    finally:
        b.pipeline.load_controller.level = 0
        b.close()


# ------------------------------------------------- degradation ladder

def test_brownout_prefilter_only_verdicts(cr):
    """Ladder rung 1: verdicts come from the sound prefilter alone —
    attacks still FLAG (candidates are a superset of confirmed hits)
    but never BLOCK, and carry degraded=True."""
    p = DetectionPipeline(cr, mode="block")
    atk = Request(uri=ATTACK_URI, request_id="a")
    ben = Request(uri="/benign?x=1", request_id="b")
    full = p.detect([atk, ben])
    assert full[0].attack and full[0].blocked and not full[0].degraded
    assert not full[1].attack

    p.load_controller.level = 1
    deg = p.detect([atk, ben])
    assert deg[0].degraded and deg[0].attack and not deg[0].blocked
    assert 942100 in deg[0].rule_ids and deg[0].score >= full[0].score
    assert deg[1].degraded and not deg[1].blocked
    assert p.stats.degraded == 2

    p.load_controller.level = 2
    fo = p.detect([atk])
    assert fo[0].fail_open and fo[0].degraded and not fo[0].attack


def test_cpu_fallback_verdict_parity(cr):
    """detect_cpu_only (breaker-open fallback) must agree with the full
    device path on every verdict field that matters."""
    p = DetectionPipeline(cr, mode="block")
    reqs = [Request(uri=ATTACK_URI, request_id="a"),
            Request(uri="/q?a=<script>alert(1)</script>", request_id="x"),
            Request(uri="/benign", request_id="b")]
    dev = p.detect(reqs)
    cand_before = int(p.rule_stats.candidates.sum())
    cpu = p.detect_cpu_only(reqs)
    for d, c in zip(dev, cpu):
        assert (d.attack, d.blocked, sorted(d.rule_ids), d.score) == \
            (c.attack, c.blocked, sorted(c.rule_ids), c.score), d.request_id
        assert not c.fail_open
    # the fallback's synthetic all-ones candidate matrix must NOT book
    # as per-rule prefilter statistics (/rules/health would be swamped)
    assert int(p.rule_stats.candidates.sum()) == cand_before


# --------------------------------------------------- fault matrix

@pytest.mark.parametrize("scenario", [
    "overload_burst", "dispatch_hang", "dispatch_raise",
    "recompile_storm", "swap_fail", "export_5xx", "slow_confirm",
    "rollout_promote_fail", "rollout_shadow_diverge", "lkg_corrupt",
    "lane_dispatch_hang", "lane_dispatch_raise", "confirm_worker_hang",
    "tenant_flood", "tenant_flood_during_canary"])
def test_fault_matrix_scenario(scenario):
    rep = run_fault_matrix(only=[scenario])
    res = rep["scenarios"][scenario]
    assert res["ok"], res["violations"]


def test_stream_cycle_hang_bounded_by_lane(cr):
    """A device wedge first hitting STREAM work is bounded by the same
    lane hang budget as batch dispatch (not the monitor's much larger
    grace): finishes resolve fail-open and the breaker trips."""
    b = _mk_batcher(cr, hang_budget_s=0.2, breaker_cooldown_s=0.3)
    faults.install(FaultPlan.from_spec("dispatch_hang:times=1,delay_s=1.0"))
    try:
        h = b.begin_stream(Request(uri="/s", request_id="s1"))
        b.feed_chunk(h, b"hello stream")
        f = b.finish_stream(h)
        v = f.result(timeout=3.0)
        assert v.fail_open and not v.blocked
        assert b.stats.hangs >= 1
        assert b.breaker.trips >= 1
    finally:
        b.close()


# --------------------------------------------------- watchdog monitor

def test_watchdog_releases_wedged_dispatch_thread(cr):
    """Last-resort backstop: the dispatch thread itself wedges (not the
    device lane) — the monitor releases the cycle's futures fail-open
    and drains newly queued work until the dispatcher moves again."""
    b = _mk_batcher(cr, hang_budget_s=0.1, hard_deadline_s=0.1)
    assert b._watch_grace < 1.5
    orig = b._stream_step_guarded
    release = threading.Event()

    def wedged(begins, chunks, finishes, route, lane=None):
        # runs ON the dispatch thread (unlike _stream_step, which now
        # rides the watchdogged lane) — this wedges the dispatcher
        release.wait(timeout=4.0)
        return orig(begins, chunks, finishes, route, lane=lane)

    b._stream_step_guarded = wedged
    try:
        f1 = b.submit(Request(uri="/x", request_id="x"))
        v1 = f1.result(timeout=3.0)   # released by the monitor, not dispatch
        assert v1.fail_open
        assert b.stats.watchdog_released >= 1
        assert b.breaker.state == "open"
        # work queued while the dispatcher is still stuck drains too
        f2 = b.submit(Request(uri="/y", request_id="y"))
        assert f2.result(timeout=3.0).fail_open
    finally:
        release.set()
        b._stream_step_guarded = orig
        b.close()


# ---------------------------------------------- close() queue drain

def test_close_drains_main_queue_fail_open(cr):
    """Satellite: a request queued at shutdown must not strand its
    connection handler — close() resolves it fail-open the way the
    oversized side lane always did."""
    b = _mk_batcher(cr)
    b._stop.set()
    b._thread.join(timeout=5)
    futs = [b.submit(Request(uri="/q%d" % i, request_id=str(i)))
            for i in range(5)]
    assert not any(f.done() for f in futs)
    b.close()
    for f in futs:
        v = f.result(timeout=1)
        assert v.fail_open and not v.blocked
    assert b.pipeline.stats.shed.get("shutdown") == 5


# ------------------------------------------------- exporter backoff

def test_exporter_backoff_and_spool_bound(tmp_path):
    from ingress_plus_tpu.post.export import Exporter
    from ingress_plus_tpu.post.queue import HitQueue

    exp = Exporter(HitQueue(), spool_dir=str(tmp_path / "spool"),
                   interval_s=1.0, backoff_max_s=8.0, jitter_seed=1,
                   max_spool_bytes=400)
    # healthy: base interval
    assert exp.next_wait_s() == 1.0
    # failures: exponential growth with jitter, hard ceiling
    prev = 1.0
    for n in (1, 2, 3, 10):
        exp.consecutive_failures = n
        w = exp.next_wait_s()
        assert w <= 8.0
        base = min(1.0 * 2 ** (n - 1), 8.0)
        assert w >= min(base, 8.0) - 1e-9
        if base < 8.0:
            assert w > prev
        prev = w
    exp.consecutive_failures = 0
    assert exp.next_wait_s() == 1.0

    # spool bound: oldest files drop to fit the cap, counted
    spool = tmp_path / "spool"
    old = spool / "attacks.111.jsonl"
    old.write_text("x" * 300)
    t = time.time()
    import os
    os.utime(old, (t - 100, t - 100))
    newer = spool / "attacks.222.jsonl"
    newer.write_text("y" * 300)
    rec = {"class": "sqli", "count": 1}
    assert exp._enforce_spool_bound(len(json.dumps(rec)) + 1,
                                    spool / "attacks.333.jsonl")
    assert not old.exists()          # oldest dropped first
    assert newer.exists()
    assert exp.spool_dropped_files == 1
    assert exp.spool_dropped_bytes == 300
    # a batch that can never fit is skipped and counted, never written
    ok = exp._enforce_spool_bound(10_000, spool / "attacks.333.jsonl")
    assert not ok
    exp.close()


# -------------------------------------- serve plane HTTP endpoints

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture()
def serve_loop(cr, tmp_path):
    from ingress_plus_tpu.serve.server import ServeLoop

    b = _mk_batcher(cr)
    port = _free_port()
    sock = str(tmp_path / "ipt.sock")
    loop = asyncio.new_event_loop()
    serve = ServeLoop(b, sock, http_port=port)

    def runner():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(serve.start())
        loop.run_forever()

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % port, timeout=2)
            break
        except OSError:
            time.sleep(0.05)
    yield serve, b, port, sock
    for s in serve._servers:
        loop.call_soon_threadsafe(s.close)
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)
    b.close()


def _get(port, path):
    r = urllib.request.urlopen("http://127.0.0.1:%d%s" % (port, path),
                               timeout=10)
    return r.status, r.read().decode()


def test_readyz_faults_and_metrics_endpoints(serve_loop):
    serve, b, port, _sock = serve_loop
    # liveness carries the robustness block and stays 200
    code, body = _get(port, "/healthz")
    health = json.loads(body)
    assert code == 200
    rb = health["robustness"]
    assert rb["breaker"]["state"] == "closed"
    assert rb["ladder"]["mode"] == "full"
    # silent-thread-death repair (ISSUE 11): the uncaught-exception
    # counter block is always present (a dict, usually empty)
    assert isinstance(rb["thread_uncaught"], dict)
    # ready while healthy
    code, body = _get(port, "/readyz")
    assert code == 200 and json.loads(body)["ready"]

    # breaker open → unready (503) while /healthz stays 200
    b.breaker.trip("test")
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(port, "/readyz")
    assert ei.value.code == 503
    payload = json.loads(ei.value.read())
    assert "breaker_open" in payload["reasons"]
    assert _get(port, "/healthz")[0] == 200
    # cooldown elapsed (probe_due): readiness returns even with NO
    # traffic — the canary that closes the breaker needs the pod back
    # in rotation (an unready breaker would deadlock forever)
    b.breaker._opened_at -= b.breaker.cooldown_s + 1
    assert b.breaker.snapshot()["probe_due"]
    assert _get(port, "/readyz")[0] == 200
    b.breaker.record_success()
    b.breaker.state = "closed"

    # ladder above full → unready
    b.pipeline.load_controller.level = 1
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(port, "/readyz")
    assert ei.value.code == 503
    assert "degraded_prefilter_only" in json.loads(ei.value.read())["reasons"]
    b.pipeline.load_controller.level = 0

    # /faults: install over HTTP, observe counters, clear
    req = urllib.request.Request(
        "http://127.0.0.1:%d/faults" % port,
        data=json.dumps({"spec": "slow_confirm:times=1,delay_s=0.01",
                         "seed": 5}).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    out = json.loads(urllib.request.urlopen(req, timeout=10).read())
    assert out["active"] and out["plan"]["seed"] == 5
    assert faults.active() is not None
    code, body = _get(port, "/faults")
    assert json.loads(body)["active"]
    req = urllib.request.Request(
        "http://127.0.0.1:%d/faults" % port, data=b"{}",
        method="POST", headers={"Content-Type": "application/json"})
    assert not json.loads(
        urllib.request.urlopen(req, timeout=10).read())["active"]
    assert faults.active() is None

    # bad spec → 400, plan untouched
    req = urllib.request.Request(
        "http://127.0.0.1:%d/faults" % port,
        data=json.dumps({"spec": "nope:times=1"}).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 400

    # the fail-safe metrics are scrapeable
    _code, metrics = _get(port, "/metrics")
    for name in ("ipt_queue_depth", "ipt_degraded_mode",
                 "ipt_breaker_state", "ipt_breaker_trips_total",
                 "ipt_watchdog_hangs_total",
                 "ipt_cpu_fallback_batches_total",
                 "ipt_degraded_verdicts_total",
                 "ipt_thread_uncaught_total"):
        assert name in metrics, name
    # shed series appears once something was shed
    b.pipeline.stats.count_shed("queue_full")
    _code, metrics = _get(port, "/metrics")
    assert 'ipt_shed_total{reason="queue_full"}' in metrics


def test_ws_sticky_fail_open_server_path(serve_loop):
    """Satellite: serve/server.py's websocket reply path sets
    ``sticky_fail_open`` when a message's verdict future raises — every
    later frame of that stream must answer fail-open on the wire."""
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_ws)
    from tests.test_websocket import ws_frame

    serve, b, _port, sock = serve_loop
    orig_finish = b.finish_stream
    injected = []

    def failing_finish(handle):
        # first message: its verdict future raises (the client-vanished
        # /cancelled-future shape) — afterwards restore the real path
        b.finish_stream = orig_finish
        b.abort_stream(handle)
        fut = Future()
        fut.set_exception(RuntimeError("injected verdict failure"))
        injected.append(handle)
        return fut

    b.finish_stream = failing_finish
    try:
        s = socket.socket(socket.AF_UNIX)
        s.settimeout(30)
        s.connect(sock)
        frames = [
            encode_ws(1, 900, ws_frame(b"hello message one")),
            encode_ws(2, 900, ws_frame(b"hello message two")),
        ]
        for f in frames:
            s.sendall(f)
        reader, got = FrameReader(RESP_MAGIC), {}
        while set(got) != {1, 2}:
            for payload in reader.feed(s.recv(1 << 16)):
                r = decode_response(payload)
                got[r["req_id"]] = r
        s.close()
        assert injected, "failing finish_stream was never exercised"
        # the frame whose message future raised answers fail-open...
        assert got[1]["fail_open"] and not got[1]["blocked"]
        # ...and the STICKY flag survives onto later, healthy frames
        assert got[2]["fail_open"] and not got[2]["blocked"]
    finally:
        b.finish_stream = orig_finish


# --------------------------------------------------------- dbg views

def test_dbg_breaker_and_faults_renderers():
    from ingress_plus_tpu.control.dbg import render_breaker, render_faults

    health = {"robustness": {
        "breaker": {"state": "open", "trips": 2, "closes": 1, "probes": 3,
                    "last_trip_reason": "hang", "consecutive_failures": 0,
                    "failure_threshold": 3, "cooldown_s": 5.0},
        "ladder": {"level": 1, "mode": "prefilter_only",
                   "queue_delay_ewma_us": 81000.0, "steps_up": 1,
                   "steps_down": 0},
        "queue_depth": 12, "queue_cap": 8192,
        "shed": {"deadline": 4, "queue_full": 9},
        "degraded_verdicts": 33, "hangs": 1,
        "cpu_fallback_batches": 7, "watchdog_released": 0,
    }}
    out = render_breaker(health)
    assert "breaker: open" in out and "trips=2" in out
    assert "prefilter_only" in out
    assert "deadline=4" in out and "queue_full=9" in out
    assert "no robustness block" in render_breaker({})

    plan = FaultPlan.from_spec("dispatch_hang:times=1,delay_s=2")
    plan.fire("dispatch_hang")
    out = render_faults({"active": True, "plan": plan.snapshot()})
    assert "dispatch_hang" in out and "seed=0" in out
    assert render_faults({"active": False}) == "no fault plan active"


def test_verdict_degraded_flag_survives_postanalytics(cr):
    """Degraded verdicts flow into the post channel without blowing up
    (duck-typed Hit path) and are visible as attack flags, not blocks."""
    from ingress_plus_tpu.post.channel import PostChannel

    p = DetectionPipeline(cr, mode="block")
    p.load_controller.level = 1
    ch = PostChannel(brute=False)
    v = p.detect([Request(uri=ATTACK_URI, request_id="d1")])[0]
    ch.record(Request(uri=ATTACK_URI, request_id="d1"), v)
    st = ch.status()
    assert st["requests"] == 1 and st["attacks"] == 1
    assert st["blocked"] == 0
