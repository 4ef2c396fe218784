"""Mesh-scale data-parallel serving (serve/lanes.py + the batcher's
double-buffered lane loop, docs/MESH_SERVING.md).

Covers the ISSUE 7 acceptance criteria on the virtual 8-device CPU
mesh (conftest): N-lane dispatch of a shuffled corpus is byte-identical
to the single-lane path (oversized side-lane and stream sticky-verdict
requests included, streams pinned to ONE lane), a fault targeted at one
lane degrades capacity only, steady-state serving never recompiles,
per-device observability surfaces in /metrics and /healthz, hot-swap
replays every lane's warm shapes, and the PR 5 guarded rollout stays
generation-correct across lanes.
"""

import asyncio
import json
import random
import threading
import time

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.serve.lanes import CircuitBreaker, Lane, LanePool
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.faults import FaultPlan

RULES = """
SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" "id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,severity:CRITICAL,tag:'attack-sqli'"
SecRule ARGS|REQUEST_BODY "@rx (?i)<script[^>]*>" "id:941100,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|ARGS "@rx /etc/(?:passwd|shadow)" "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
SecRule ARGS "@pm sleep( benchmark( xp_cmdshell" "id:942150,phase:2,block,severity:ERROR,tag:'attack-sqli'"
"""


@pytest.fixture(scope="module")
def cr():
    return compile_ruleset(parse_seclang(RULES))


def _corpus(n=48, seed=7):
    """Mixed benign/attack requests with bodies, unique ids."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        kind = i % 4
        if kind == 0:
            r = Request(uri="/p?q=1%27%20UNION%20SELECT%20x%20FROM%20t",
                        headers={}, body=b"", request_id="atk-sqli-%d" % i)
        elif kind == 1:
            r = Request(uri="/login", headers={"content-type":
                                               "application/x-www-form-urlencoded"},
                        body=b"user=a&pass=" + bytes(
                            rng.randrange(97, 123) for _ in
                            range(rng.randrange(4, 200))),
                        request_id="benign-post-%d" % i)
        elif kind == 2:
            r = Request(uri="/p?f=../../etc/passwd", headers={},
                        body=b"", request_id="atk-lfi-%d" % i)
        else:
            r = Request(uri="/index.html?page=%d" % i, headers={},
                        body=b"", request_id="benign-get-%d" % i)
        out.append(r)
    return out


def _vt(v):
    return (v.attack, v.blocked, tuple(v.rule_ids), v.score,
            tuple(v.classes), v.fail_open, v.degraded)


def _serve_all(batcher, requests, timeout=60):
    futs = [batcher.submit(r) for r in requests]
    return {r.request_id: f.result(timeout=timeout)
            for r, f in zip(requests, futs)}


def _mk(cr, n_lanes, **kw):
    kw.setdefault("max_batch", 16)
    kw.setdefault("max_delay_s", 0.001)
    p = DetectionPipeline(cr, mode="block")
    return Batcher(p, n_lanes=n_lanes, **kw)


# ------------------------------------------------------------- units

def test_lane_pool_needs_a_distinct_device_per_lane():
    """More than one lane with no distinct device each is a start-up
    error — never N lanes quietly piled on the first chip."""
    import jax

    devs = jax.devices()
    with pytest.raises(ValueError, match="distinct devices"):
        LanePool(n_lanes=3)                        # no devices at all
    with pytest.raises(ValueError, match="distinct devices"):
        LanePool(n_lanes=3, devices=devs[:2])      # lanes outnumber chips
    with pytest.raises(ValueError, match="distinct devices"):
        LanePool(n_lanes=2, devices=[devs[0], devs[0]])
    LanePool(n_lanes=1).close()                    # default device: fine
    pool = LanePool(n_lanes=3, devices=devs)
    assert [ln.device for ln in pool.lanes] == list(devs[:3])
    pool.close()


def test_lane_pool_split_balances_by_weight_and_caps_canary():
    import jax

    pool = LanePool(n_lanes=3, devices=jax.devices())
    targets = [(pool.lane(0), "device"), (pool.lane(1), "device"),
               (pool.lane(2), "canary")]
    items = list(range(30))
    shares = LanePool.split(items, targets, weight=lambda i: 1)
    # canary lane capped at 4; the rest balances over the device lanes
    assert len(shares[2]) <= 4
    assert abs(len(shares[0]) - len(shares[1])) <= 1
    assert sorted(sum(shares, [])) == items   # exactly-once partition
    # byte weighting: one huge item must not be joined by everything else
    shares = LanePool.split([1000, 1, 1, 1, 1, 1], targets[:2],
                            weight=lambda w: w)
    big = 0 if 1000 in shares[0] else 1
    assert len(shares[1 - big]) == 5
    pool.close()


def test_fault_plan_lane_targeting():
    plan = FaultPlan.from_spec("dispatch_raise:lane=1,times=2")
    try:
        faults.set_current_lane(0)
        assert plan.fire("dispatch_raise") is None     # wrong lane
        faults.set_current_lane(1)
        assert plan.fire("dispatch_raise") is not None
        assert plan.fire("dispatch_raise") is not None
        assert plan.fire("dispatch_raise") is None     # times exhausted
        snap = plan.snapshot()
        assert snap["rules"][0]["lane"] == 1
        assert snap["rules"][0]["fired"] == 2
    finally:
        faults.set_current_lane(None)


def test_breaker_reexported_from_batcher():
    # PR 4 consumers import CircuitBreaker from the batcher module
    from ingress_plus_tpu.serve import batcher as batcher_mod

    assert batcher_mod.CircuitBreaker is CircuitBreaker
    b = _mk(compile_ruleset(parse_seclang(RULES)), n_lanes=1)
    try:
        assert b.breaker is b.lanes.primary.breaker
        assert b.device_available()
    finally:
        b.close()


# ----------------------------------------------------------- parity

def test_nlane_verdict_parity_with_single_lane(cr):
    """The ISSUE 7 property: an N-lane dispatch of a shuffled corpus
    produces byte-identical verdicts to the single-lane path —
    including an oversized request that rides the side lane."""
    reqs = _corpus(48)
    # oversized: attack buried past the 16KB batch tier, auto-rerouted
    # through the stream-engine side lane in both modes
    big = (b"x=" + b"A" * (Batcher.OVERSIZE_THRESHOLD + 512)
           + b"&q=1 union select passwords")
    reqs.append(Request(uri="/upload", headers={}, body=big,
                        request_id="atk-oversized"))

    b1 = _mk(cr, n_lanes=1)
    try:
        want = {rid: _vt(v) for rid, v in _serve_all(b1, reqs).items()}
        assert b1.stats.oversized_rerouted == 1
    finally:
        b1.close()
    assert want["atk-oversized"][0]        # the buried attack was seen
    assert any(w[0] for w in want.values())
    assert not all(w[0] for w in want.values())

    shuffled = list(reqs)
    random.Random(3).shuffle(shuffled)
    b4 = _mk(cr, n_lanes=4)
    try:
        got = {rid: _vt(v) for rid, v in
               _serve_all(b4, shuffled).items()}
        assert b4.stats.oversized_rerouted == 1
        # the work genuinely sharded: more than one lane served rows
        served = [ln for ln in b4.lanes.lanes if ln.stats.requests]
        assert len(served) > 1
    finally:
        b4.close()
    assert got == want


def test_stream_sticky_verdict_pinned_to_one_lane(cr):
    """Streaming bodies produce the same sticky verdict on a mesh pool,
    and ALL stream scan work rides exactly one lane (chunk-carried scan
    state must never interleave across devices)."""
    def run_stream(b):
        h = b.begin_stream(Request(uri="/post", headers={},
                                   request_id="stream-1"))
        b.feed_chunk(h, b"q=1 uni")
        time.sleep(0.05)              # force a chunk-boundary cycle
        b.feed_chunk(h, b"on select 2")
        return b.finish_stream(h).result(timeout=30)

    b1 = _mk(cr, n_lanes=1)
    try:
        want = _vt(run_stream(b1))
    finally:
        b1.close()
    b3 = _mk(cr, n_lanes=3)
    try:
        got = _vt(run_stream(b3))
        lanes_used = [ln.index for ln in b3.lanes.lanes
                      if ln.stats.stream_cycles]
        assert lanes_used == [0], lanes_used   # pinned to first serving
    finally:
        b3.close()
    assert got == want
    assert want[0]                    # the split attack was detected


# ------------------------------------------------- compiles / warmup

def test_steady_state_serving_never_recompiles(cr):
    """ISSUE 7 satellite: serve-time recompile count stays 0 — after
    the first pass of a traffic mix (and warm_lanes' tier pass), the
    same mix replays with ZERO fresh executables on any lane."""
    b = _mk(cr, n_lanes=4)
    try:
        b.warm_lanes(max_batch=16)
        assert b.pipeline.stats.engine_compiles == 0   # reset by warm
        reqs = _corpus(32, seed=11)
        _serve_all(b, reqs)                  # first pass may compile
        b.reset_latency_observations()
        for burst in (reqs[:16], reqs[16:20], reqs[20:21], reqs):
            _serve_all(b, list(burst))
        assert b.pipeline.stats.engine_compiles == 0, \
            "steady-state mesh serving paid a serve-time XLA compile"
    finally:
        b.close()


def test_hot_swap_replays_lane_shapes(cr):
    """The batcher hot-swap pre-compiles every LANE's device-bound
    executables for the new pack (seen_lane_shapes replay) — post-swap
    traffic of the same mix pays zero serve-time compiles and verdicts
    keep flowing from the new generation."""
    b = _mk(cr, n_lanes=3)
    try:
        reqs = _corpus(24, seed=5)
        _serve_all(b, reqs)
        lane_shapes = set(b.pipeline.seen_lane_shapes)
        assert lane_shapes, "mesh serving recorded no lane shapes"
        cr2 = compile_ruleset(parse_seclang(RULES))
        b.swap_ruleset(cr2)
        assert set(b.pipeline.seen_lane_shapes) >= lane_shapes
        b.pipeline.stats.reset_efficiency()
        got = _serve_all(b, reqs)
        assert b.pipeline.stats.engine_compiles == 0, \
            "post-swap mesh traffic recompiled (lane replay missed)"
        assert any(v.attack for v in got.values())
        assert all(v.generation == cr2.version
                   for v in got.values() if v.generation)
    finally:
        b.close()


# ------------------------------------------------------ lane faults

def test_single_lane_fault_degrades_capacity_only(cr):
    """dispatch_raise pinned to lane 1: its share fails open, ITS
    breaker opens, siblings serve on, no global fallback, and the lane
    recovers through its own half-open canary."""
    b = _mk(cr, n_lanes=3, breaker_failures=1, breaker_cooldown_s=0.3)
    try:
        warm = _corpus(24, seed=9)
        _serve_all(b, warm)                    # compile all lane shapes
        faults.install(FaultPlan.from_spec("dispatch_raise:lane=1,times=1"))
        got = _serve_all(b, _corpus(24, seed=10))
        assert len(got) == 24                  # exactly one verdict each
        assert any(v.attack and not v.fail_open for v in got.values())
        assert b.lanes.lane(1).breaker.trips == 1
        assert b.lanes.lane(0).breaker.trips == 0
        assert b.lanes.lane(2).breaker.trips == 0
        assert b.stats.cpu_fallback_batches == 0
        # recovery: the exhausted fault lets the half-open canary close
        deadline = time.monotonic() + 15
        while b.lanes.lane(1).breaker.state != CircuitBreaker.CLOSED \
                and time.monotonic() < deadline:
            _serve_all(b, _corpus(8, seed=12))
            time.sleep(0.05)
        assert b.lanes.lane(1).breaker.state == CircuitBreaker.CLOSED
    finally:
        faults.clear()
        b.close()


def test_all_lanes_down_serves_cpu_fallback(cr):
    """Only when EVERY lane is open does the global CPU confirm-only
    fallback engage — and it still produces real verdicts."""
    b = _mk(cr, n_lanes=2, breaker_failures=1, breaker_cooldown_s=30.0)
    try:
        _serve_all(b, _corpus(16, seed=13))
        for ln in b.lanes.lanes:
            ln.breaker.trip("test")
        got = _serve_all(b, _corpus(16, seed=14))
        assert len(got) == 16
        assert b.stats.cpu_fallback_batches >= 1
        assert any(v.attack and not v.fail_open for v in got.values())
    finally:
        b.close()


# -------------------------------------------------- observability

def test_metrics_healthz_and_dbg_lane_views(cr):
    from ingress_plus_tpu.control.dbg import render_breaker
    from ingress_plus_tpu.serve.server import ServeLoop

    b = _mk(cr, n_lanes=3)
    try:
        _serve_all(b, _corpus(24, seed=15))
        serve = ServeLoop(b, "/tmp/unused-mesh-lanes.sock")
        text = serve._metrics_text()
        assert "ipt_lane_count 3" in text
        for i in range(3):
            assert 'ipt_breaker_state{device="%d"}' % i in text
            assert 'ipt_dispatch_fill{device="%d"}' % i in text
            assert 'ipt_watchdog_hangs_total{device="%d"}' % i in text
            assert 'ipt_lane_rows_total{device="%d"}' % i in text
        status, _ctype, body = asyncio.run(
            serve._route_http("GET", "/healthz", b""))
        assert status.startswith("200")
        health = json.loads(body)
        lanes = health["robustness"]["lanes"]
        assert [ln["lane"] for ln in lanes] == [0, 1, 2]
        assert all(ln["breaker"]["state"] == "closed" for ln in lanes)
        # per-lane rows in /debug/slow exemplars: every retained
        # exemplar names the device that served it
        status, _ctype, body = asyncio.run(
            serve._route_http("GET", "/debug/slow", b""))
        slow = json.loads(body)["slowest"]
        assert slow and all("lane" in e for e in slow)
        out = render_breaker(health)
        assert "lanes:" in out and "TFRT_CPU" in out
    finally:
        b.close()


def test_readyz_mesh_stays_ready_with_one_dead_lane(cr):
    from ingress_plus_tpu.serve.server import ServeLoop

    b = _mk(cr, n_lanes=2, breaker_cooldown_s=60.0)
    try:
        serve = ServeLoop(b, "/tmp/unused-mesh-ready.sock")
        b.lanes.lane(1).breaker.trip("test")
        status, _ctype, body = asyncio.run(
            serve._route_http("GET", "/readyz", b""))
        assert status.startswith("200"), body   # one chip != unready
        assert json.loads(body)["ready"]
        b.lanes.lane(0).breaker.trip("test")
        status, _ctype, body = asyncio.run(
            serve._route_http("GET", "/readyz", b""))
        assert status.startswith("503")
        assert "breaker_open" in json.loads(body)["reasons"]
    finally:
        b.close()


def test_build_default_batcher_lane_serving(tmp_path):
    """The serve entrypoint wires --lanes through: warmed lane pool,
    rollout controller attached, and the --mesh/--lanes combination is
    rejected loudly (they parallelize the same chips differently)."""
    from ingress_plus_tpu.serve.server import build_default_batcher

    (tmp_path / "tiny.conf").write_text(RULES)
    b = build_default_batcher(rules_dir=str(tmp_path), max_batch=8,
                              warmup=True, scan_impl="pair", n_lanes=2)
    try:
        assert b.lanes.n == 2
        assert b.rollout is not None
        assert b.pipeline.stats.engine_compiles == 0   # warm + reset
        got = _serve_all(b, _corpus(8, seed=21))
        assert len(got) == 8
        assert any(v.attack for v in got.values())
    finally:
        b.close()
    with pytest.raises(ValueError):
        build_default_batcher(rules_dir=str(tmp_path), warmup=False,
                              scan_impl="pair", n_lanes=2,
                              mesh_spec="2x4")


# ------------------------------------------------- rollout on lanes

def test_staged_rollout_generation_correct_across_lanes():
    """PR 5 contract on the mesh: a staged rollout driven through a
    3-lane batcher reaches LIVE, every scanned verdict names exactly
    one of the two known generations, and the drift freeze still
    captures the incumbent."""
    from ingress_plus_tpu.control.rollout import (
        _DRILL_CANDIDATE,
        _DRILL_INCUMBENT,
        LIVE,
        REJECTED,
        ROLLED_BACK,
        RolloutConfig,
        RolloutController,
    )
    from ingress_plus_tpu.utils.faults import _collect, _requests

    inc = compile_ruleset(parse_seclang(_DRILL_INCUMBENT))
    cand = compile_ruleset(parse_seclang(_DRILL_CANDIDATE))
    b = _mk(inc, n_lanes=3)
    cfg = RolloutConfig(steps=(0.25, 1.0), step_min_requests=8,
                        shadow_min_requests=4, shadow_sample=1.0,
                        corpus_n=32, diff_min_compared=4)
    ro = RolloutController(b, cfg)
    b.rollout = ro
    try:
        _collect([b.submit(r) for r in _requests(16, tag="warm")], 60)
        ro.admit(ruleset=cand)
        verdicts = []
        deadline = time.monotonic() + 60
        wave = 0
        while ro.state not in (LIVE, REJECTED, ROLLED_BACK) \
                and time.monotonic() < deadline:
            futs = [b.submit(r) for r in
                    _requests(24, attack_every=4, tag="m%d" % wave)]
            vs, viol = _collect(futs, timeout_s=30)
            assert not viol, viol
            verdicts += vs
            wave += 1
        assert ro.state == LIVE, (ro.state, ro.rollback_reason)
        assert b.pipeline.ruleset.version == cand.version
        gens = {v.generation for v in verdicts if v.generation}
        assert gens <= {inc.version, cand.version}, gens
        assert any(v.generation == cand.version for v in verdicts)
        assert b.pipeline.frozen_rule_stats is not None
        assert b.pipeline.frozen_rule_stats.version == inc.version
    finally:
        b.close()
