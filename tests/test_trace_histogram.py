"""Latency-attribution layer units (ISSUE 1): Histogram bucket math,
Prometheus rendering, the text→percentile round trip bench.py relies on,
the slow-exemplar ring, and the bench scrape path WITHOUT a server."""

import math

from ingress_plus_tpu.utils.trace import (
    DEFAULT_BUCKETS_US,
    STAGES,
    BatchTrace,
    Histogram,
    SlowRing,
    TraceRing,
    stage_breakdown_from_metrics,
)


# -------------------------------------------------------------- Histogram

def test_bucket_assignment_log2_edges():
    h = Histogram()
    # exact bucket math on the log2 edges: observe(b) lands in the
    # bucket whose upper bound is b (le semantics), observe(b+1) in the
    # next one
    h.observe(1)
    h.observe(2)
    h.observe(3)
    h.observe(4)
    counts, total, sum_us = h.snapshot()
    assert total == 4 and sum_us == 10
    assert counts[0] == 1          # le=1
    assert counts[1] == 1          # le=2
    assert counts[2] == 2          # 3 and 4 both land in le=4
    # overflow: beyond the last bound goes to +Inf
    h.observe(DEFAULT_BUCKETS_US[-1] + 1)
    assert h.snapshot()[0][-1] == 1


def test_percentiles_interpolated_and_bounded():
    h = Histogram()
    for _ in range(100):
        h.observe(100)             # all in the (64, 128] bucket
    p50 = h.percentile(0.5)
    assert 64 <= p50 <= 128
    assert h.percentile(0.99) <= 128
    # empty histogram: 0, never NaN
    assert Histogram().percentile(0.5) == 0.0
    assert not math.isnan(p50)


def test_prometheus_rendering_cumulative_and_labeled():
    h = Histogram(bounds=(1, 10, 100))
    for v in (1, 5, 50, 500):
        h.observe(v)
    lines = h.prometheus("ipt_stage_us", {"stage": "scan"})
    assert 'ipt_stage_us_bucket{stage="scan",le="1"} 1' in lines
    assert 'ipt_stage_us_bucket{stage="scan",le="10"} 2' in lines
    assert 'ipt_stage_us_bucket{stage="scan",le="100"} 3' in lines
    assert 'ipt_stage_us_bucket{stage="scan",le="+Inf"} 4' in lines
    assert 'ipt_stage_us_sum{stage="scan"} 556' in lines
    assert 'ipt_stage_us_count{stage="scan"} 4' in lines
    # unlabeled series render without braces on _sum/_count
    plain = Histogram(bounds=(1,)).prometheus("ipt_batch_size")
    assert "ipt_batch_size_sum 0" in plain


def test_text_roundtrip_matches_live_percentiles():
    """The parser must recover the same percentiles the live Histogram
    reports — this is the bench stage_breakdown contract."""
    hists = {s: Histogram() for s in STAGES}
    for i in range(200):
        for s in STAGES:
            hists[s].observe((i % 37 + 1) * 10)
    lines = ["# TYPE ipt_stage_us histogram"]
    for s, h in hists.items():
        lines += h.prometheus("ipt_stage_us", {"stage": s})
    sb = stage_breakdown_from_metrics("\n".join(lines))
    assert sb is not None and set(sb) == set(STAGES)
    for s in STAGES:
        assert sb[s]["count"] == 200
        # parser rounds to 0.1µs; live percentile is exact
        assert abs(sb[s]["p50_us"] - hists[s].percentile(0.5)) < 0.06
        assert abs(sb[s]["p99_us"] - hists[s].percentile(0.99)) < 0.06


def test_malformed_metrics_is_none_not_garbage():
    assert stage_breakdown_from_metrics("") is None
    assert stage_breakdown_from_metrics("ipt_requests_total 5\n") is None
    # non-monotonic cumulative counts = malformed histogram
    bad = ('ipt_stage_us_bucket{stage="queue",le="1"} 5\n'
           'ipt_stage_us_bucket{stage="queue",le="2"} 3\n')
    assert stage_breakdown_from_metrics(bad) is None
    # unparsable le
    bad2 = 'ipt_stage_us_bucket{stage="queue",le="wat"} 5\n'
    assert stage_breakdown_from_metrics(bad2) is None
    # truncated text where only the +Inf bucket survived: malformed →
    # None, never an IndexError (dbg latency calls this bare)
    bad3 = 'ipt_stage_us_bucket{stage="e2e",le="+Inf"} 5\n'
    assert stage_breakdown_from_metrics(bad3) is None


def test_histogram_reset_drops_warmup_observations():
    h = Histogram()
    for _ in range(10):
        h.observe(1 << 20)     # "warmup compile" observations
    h.reset()
    assert h.snapshot() == ([0] * (len(DEFAULT_BUCKETS_US) + 1), 0, 0)
    h.observe(100)
    assert h.percentile(0.99) <= 128


# --------------------------------------------------------------- SlowRing

def test_slow_ring_retains_k_slowest():
    r = SlowRing(capacity=4)
    assert r.threshold() == -1          # not full: accept everything
    for i in range(100):
        r.offer(i, {"request_id": "r%d" % i})
    snap = r.snapshot()
    assert [e["e2e_us"] for e in snap] == [99, 98, 97, 96]
    assert r.find_request("r99")["e2e_us"] == 99
    assert r.find_request("r0") is None            # displaced
    assert r.snapshot(2) == snap[:2]
    # threshold peek = smallest retained (the offer-skip fast path)
    assert r.threshold() == 96
    r.reset()
    assert r.snapshot() == [] and r.threshold() == -1


# ----------------------------------------------------- BatchTrace / ring

def test_batch_trace_stages_and_request_lookup():
    ring = TraceRing(capacity=4)
    t = BatchTrace(ts=1.0, n_requests=2, n_stream_items=0,
                   queue_delay_us=100, batch_us=1000, engine_us=600,
                   confirm_us=100, prep_us=200,
                   request_ids=["a", "b"])
    ring.record(t)
    st = t.stages()
    assert st["prep_us"] == 200 and st["scan_us"] == 600
    assert st["other_us"] == 100   # 1000 - 200 - 600 - 100
    found = ring.find_request("b")
    assert found is not None and found["stages"] == st
    assert ring.find_request("zz") is None
    # slowest() carries the stage breakdown too
    assert ring.slowest(1)[0]["stages"] == st


# ------------------------------------------- bench scrape path, no server

def test_bench_scrape_path_imports_without_server():
    """ISSUE 1 satellite: the bench stage_breakdown scrape must be
    importable and runnable with NO running server — a stub with
    _metrics_text() stands in for the live ServeLoop."""
    import bench

    class StubServe:
        def __init__(self, text):
            self._text = text

        def _metrics_text(self):
            return self._text

    hists = {s: Histogram() for s in STAGES}
    for i in range(50):
        hists["queue"].observe(10)
        hists["prep"].observe(20)
        hists["scan"].observe(100)
        hists["confirm"].observe(30)
        hists["batch"].observe(160)
        hists["e2e"].observe(170)
    lines = ["# TYPE ipt_stage_us histogram"]
    for s, h in hists.items():
        lines += h.prometheus("ipt_stage_us", {"stage": s})
    sb = bench.scrape_stage_breakdown(StubServe("\n".join(lines)))
    assert sb is not None
    assert set(STAGES) <= set(sb)
    # the decomposition check: stage sum ≈ e2e within the log-bucket
    # slack (every stage here is a point mass, so within 2x)
    chk = sb["sum_check"]
    assert 0.5 < chk["stage_sum_over_e2e_p99_us"] < 2.0
    # malformed/missing histograms → None (the loud-warning contract)
    assert bench.scrape_stage_breakdown(StubServe("nope 1\n")) is None


def test_dbg_render_latency_on_real_shapes():
    """`dbg latency` rendering consumes real endpoint payload shapes
    (metrics text + /debug/slow JSON + sidecar status JSON)."""
    from ingress_plus_tpu.control.dbg import render_latency

    h = Histogram()
    for _ in range(10):
        h.observe(500)
    text = "# TYPE ipt_stage_us histogram\n" + "\n".join(
        h.prometheus("ipt_stage_us", {"stage": "e2e"}))
    slow = {"slowest": [{"request_id": "41", "e2e_us": 900,
                         "queue_us": 100,
                         "batch": {"prep_us": 50, "scan_us": 700,
                                   "confirm_us": 50},
                         "rule_ids": [942100]}]}
    sidecar = {"pending": 0, "late_responses": 0,
               "upstreams": [{"path": "/run/s.sock", "ewma_ms": 1.25,
                              "inflight": 2}]}
    out = render_latency(text, slow, sidecar)
    assert "e2e" in out and "41" in out and "942100" in out
    assert "ewma_ms=1.250" in out
    # missing histograms: explicit, not a crash
    out2 = render_latency("", {"slowest": []})
    assert "MISSING" in out2


# ------------------------------------------------- sub-stages (ISSUE 27)

def _exposition(stage_us: dict, n: int = 50) -> str:
    lines = ["# TYPE ipt_stage_us histogram"]
    for stage, us in stage_us.items():
        h = Histogram()
        for _ in range(n):
            h.observe(us)
        lines += h.prometheus("ipt_stage_us", {"stage": stage})
    return "\n".join(lines)


STAGE_US = {"queue": 10, "prep": 20, "scan": 100, "confirm": 30,
            "batch": 160, "e2e": 170}
SUBSTAGE_US = {"scan_pack": 5, "scan_launch": 60, "scan_wait": 30,
               "confirm_walk": 20, "confirm_fold": 8, "lane_handoff": 4,
               "confirm_ipc": 3,
               "drain_idle": 400, "reply": 90}


def test_substages_render_and_parse_beside_the_stages():
    from ingress_plus_tpu.utils.trace import SUBSTAGES

    assert set(SUBSTAGE_US) == set(SUBSTAGES)
    assert not set(SUBSTAGES) & set(STAGES)
    sb = stage_breakdown_from_metrics(
        _exposition({**STAGE_US, **SUBSTAGE_US}))
    assert set(sb) == set(STAGES) | set(SUBSTAGES)
    for name, us in SUBSTAGE_US.items():
        assert sb[name]["count"] == 50
        assert us / 2 <= sb[name]["p50_us"] <= us * 2, name


def test_substages_stay_out_of_the_stage_sum():
    """The bench's decomposition check adds queue + prep + scan +
    confirm; sub-stages lie inside scan and confirm and must not be
    added a second time."""
    import bench

    class StubServe:
        def __init__(self, text):
            self._text = text

        def _metrics_text(self):
            return self._text

    plain = bench.scrape_stage_breakdown(StubServe(_exposition(STAGE_US)))
    both = bench.scrape_stage_breakdown(
        StubServe(_exposition({**STAGE_US, **SUBSTAGE_US})))
    assert both["sum_check"] == plain["sum_check"]
    assert set(both) == set(plain)


def test_dbg_latency_lists_substages_after_the_stages():
    from ingress_plus_tpu.control.dbg import render_latency
    from ingress_plus_tpu.utils.trace import SUBSTAGES

    out = render_latency(_exposition({**SUBSTAGE_US, **STAGE_US}),
                         {"slowest": []})
    rows = [ln.split()[0] for ln in out.splitlines()[1:]
            if ln.strip() and ln.split()[0] in STAGES + SUBSTAGES]
    assert rows == list(STAGES) + list(SUBSTAGES)


def test_batch_trace_stages_carry_sub_spans_and_gc():
    t = BatchTrace(ts=1.0, n_requests=2, n_stream_items=0,
                   queue_delay_us=100, batch_us=1000, engine_us=600,
                   confirm_us=100, prep_us=200,
                   sub_us={"scan_launch": 400, "scan_wait": 150},
                   gc_us=7)
    st = t.stages()
    assert st["scan_launch_us"] == 400 and st["scan_wait_us"] == 150
    assert st["gc_us"] == 7
    assert st["other_us"] == 100       # sub-spans are not subtracted
