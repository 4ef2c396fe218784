"""Stage-level latency attribution end to end (ISSUE 1): real serve
loop subprocess, real frames over a real socket, then the three
observability surfaces — /metrics histograms, /traces/request?id=, and
/debug/slow — must agree on the same request's stage timings, and the
`dbg latency` CLI must parse the live endpoints."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PORT = 19931

TINY_RULES = """
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
"""


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs")
    rules_dir = tmp / "rules"
    rules_dir.mkdir()
    (rules_dir / "tiny.conf").write_text(TINY_RULES)
    sock = str(tmp / "ipt.sock")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ingress_plus_tpu.serve",
         "--socket", sock, "--http-port", str(PORT),
         "--rules-dir", str(rules_dir), "--platform", "cpu",
         "--max-delay-us", "1000", "--no-warmup",
         "--trace-dir", str(tmp / "trace")],
        cwd=str(REPO), env=env, stderr=subprocess.PIPE, text=True)
    for _ in range(600):
        if Path(sock).exists():
            try:
                s = socket.socket(socket.AF_UNIX)
                s.connect(sock)
                s.close()
                break
            except OSError:
                pass
        if proc.poll() is not None:
            raise RuntimeError("server died: %s" % proc.stderr.read())
        time.sleep(0.1)
    else:
        proc.kill()
        raise RuntimeError("server socket never appeared")
    yield sock
    proc.terminate()
    proc.wait(timeout=10)


def _get(path):
    return urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (PORT, path), timeout=10).read()


def _post(path):
    """(status, decoded JSON body) of an empty POST."""
    import urllib.error

    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (PORT, path), data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _drive(sock_path, reqs):
    """Send requests over the wire; return req_id → decoded verdict."""
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)

    s = socket.socket(socket.AF_UNIX)
    s.connect(sock_path)
    s.settimeout(120)
    for req, rid in reqs:
        s.sendall(encode_request(req, req_id=rid))
    reader, got = FrameReader(RESP_MAGIC), {}
    while len(got) < len(reqs):
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    return got


def test_surfaces_agree_on_stage_timings(server):
    from ingress_plus_tpu.serve.normalize import Request

    reqs = [(Request(uri="/item/%d?q=benign" % i,
                     headers={"Host": "shop.example.com"},
                     request_id=str(4000 + i)), 4000 + i)
            for i in range(6)]
    reqs.append((Request(uri="/q?a=1+union+select+2",
                         request_id="4100"), 4100))
    got = _drive(server, reqs)
    assert got[4100]["attack"]

    # --- /metrics: Prometheus stage histograms with real observations
    # (a cycle's verdicts go out before its histograms are fed: the
    # last cycle's observations may be a moment behind its replies)
    from ingress_plus_tpu.utils.trace import stage_breakdown_from_metrics
    deadline = time.monotonic() + 10
    while True:
        metrics = _get("/metrics").decode()
        sb = stage_breakdown_from_metrics(metrics)
        if (sb and sb["e2e"]["count"] >= len(reqs)) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    for stage in ("queue", "prep", "scan", "confirm", "batch", "e2e"):
        assert 'ipt_stage_us_bucket{stage="%s"' % stage in metrics, stage
    assert "ipt_batch_size_bucket" in metrics
    assert sb is not None
    assert sb["e2e"]["count"] >= len(reqs)
    assert sb["queue"]["count"] >= len(reqs)
    assert sb["e2e"]["p99_us"] > 0

    # --- /traces/request?id=: the wire req_id resolves to its batch
    tr = json.loads(_get("/traces/request?id=4100"))
    assert tr["found"] and tr["batch"] is not None
    assert "4100" in tr["batch"]["request_ids"]
    stages = tr["stages"]
    assert stages["batch_us"] > 0
    # the pipelined loop drains for the next cycle while this one's
    # scan runs on the lane's worker: those drains are taken off the
    # cycle's own clock, and lie in its drain_idle
    assert stages["batch_us"] + stages["drain_idle_us"] >= \
        stages["scan_us"] + stages["confirm_us"]

    # --- /debug/slow: the same request's exemplar, with matching spans
    slow = json.loads(_get("/debug/slow"))["slowest"]
    assert slow, "slow ring empty after traffic"
    ex = {e["request_id"]: e for e in slow}.get("4100")
    assert ex is not None, "attack request not retained in slow ring"
    # the exemplar's batch breakdown IS the batch's trace record — the
    # three surfaces describe the same dispatch cycle
    for k in ("prep_us", "scan_us", "confirm_us", "batch_us"):
        assert ex["batch"][k] == stages[k], (k, ex["batch"], stages)
    assert ex["e2e_us"] >= ex["queue_us"]
    assert ex["e2e_us"] >= stages["scan_us"]
    assert ex["rule_ids"] == [942100]
    assert ex["input"]["uri_len"] == len("/q?a=1+union+select+2")
    # ...and the e2e histogram's +Inf-cumulative covers the exemplar
    assert sb["e2e"]["count"] >= 1

    # unknown id: explicit not-found, never a 500
    missing = json.loads(_get("/traces/request?id=999999"))
    assert not missing["found"]


def test_oversized_body_lands_in_slow_ring(server):
    """The oversized side lane (likeliest slowest requests) must feed
    the e2e histogram and the slow ring too — not vanish from the
    attribution layer."""
    from ingress_plus_tpu.serve.normalize import Request

    body = b"P" * (64 << 10) + b" 1' union select password from users --"
    got = _drive(server, [(Request(method="POST", uri="/upload",
                                   body=body, request_id="4200"), 4200)])
    assert got[4200]["attack"]
    ex = None
    for _ in range(40):     # side lane resolves asynchronously
        slow = json.loads(_get("/debug/slow"))["slowest"]
        ex = {e["request_id"]: e for e in slow}.get("4200")
        if ex is not None:
            break
        time.sleep(0.25)
    assert ex is not None, "oversized request missing from slow ring"
    assert ex.get("oversized") is True
    assert ex["input"]["body_len"] == len(body)
    assert ex["rule_ids"] == [942100]
    # its id resolves via the exemplar, NOT a batch record — the side
    # lane's work must not be attributed to a batch's stage spans
    tr = json.loads(_get("/traces/request?id=4200"))
    assert tr["found"] and tr["batch"] is None
    assert tr["exemplar"]["oversized"] is True


def test_traces_slowest_carries_stage_breakdown(server):
    body = json.loads(_get("/traces?slowest=5"))["traces"]
    assert body
    assert "stages" in body[0] and "prep_us" in body[0]["stages"]


def test_rules_stats_and_health_after_traffic(server):
    """ISSUE 3: the detection-plane telemetry surfaces appear on the
    live server after traffic — /rules/stats carries per-rule
    candidate/confirm accounting, /rules/health the dead/never-hit
    view, /rules/drift answers (no swap yet), and /metrics gains the
    family series + device-efficiency gauges."""
    from ingress_plus_tpu.serve.normalize import Request

    got = _drive(server, [(Request(uri="/q?a=9+union+select+9",
                                   request_id="4300"), 4300)])
    assert got[4300]["attack"]

    stats = json.loads(_get("/rules/stats"))
    assert stats["requests"] >= 1
    rows = {r["rule_id"]: r for r in stats["rules"]}
    assert rows[942100]["candidates"] >= 1
    assert rows[942100]["confirmed"] >= 1
    assert stats["efficiency"]["dispatch_fill"] is not None
    assert stats["device"]["n_rules"] == len(rows)

    health = json.loads(_get("/rules/health"))
    assert health["runtime_dead"] == []        # tiny pack is healthy
    assert health["requests"] >= 1

    drift = json.loads(_get("/rules/drift"))
    assert "note" in drift                     # no hot swap happened

    metrics = _get("/metrics").decode()
    assert 'ipt_rule_family_hits_total{' in metrics
    assert 'family="942"' in metrics
    assert "ipt_pad_waste_ratio" in metrics
    assert "ipt_dispatch_fill" in metrics
    assert "ipt_engine_recompiles_total" in metrics
    # per-generation series carry the version label (satellite)
    assert 'ipt_rules_runtime_dead{version="' in metrics
    assert 'ipt_confirm_errors_total{version="' in metrics


def test_dbg_rules_renders_live_endpoints(server, capsys):
    from ingress_plus_tpu.control import dbg

    rc = dbg.main(["rules", "--server", "127.0.0.1:%d" % PORT])
    assert rc == 0
    out = capsys.readouterr().out
    assert "942100" in out
    assert "runtime-dead rules (0)" in out

    rc = dbg.main(["drift", "--server", "127.0.0.1:%d" % PORT])
    assert rc == 0
    out = capsys.readouterr().out
    assert "no ruleset swap since startup" in out


def test_dbg_latency_parses_live_endpoints(server, capsys):
    """ISSUE 1 satellite: `dbg latency` drives the real endpoints and
    renders a parseable stage table."""
    from ingress_plus_tpu.control import dbg

    rc = dbg.main(["latency", "--server", "127.0.0.1:%d" % PORT])
    assert rc == 0
    out = capsys.readouterr().out
    lines = out.splitlines()
    header = next(l for l in lines if l.startswith("stage"))
    cols = header.split()
    assert cols == ["stage", "count", "p50_us", "p90_us", "p99_us"]
    rows = {}
    for l in lines[lines.index(header) + 1:]:
        if not l.strip():
            break
        parts = l.split()
        rows[parts[0]] = [float(x) for x in parts[1:]]
    for stage in ("queue", "prep", "scan", "confirm", "e2e"):
        assert stage in rows, out
        assert rows[stage][0] > 0          # count
    assert "slowest requests" in out


# ------------------------------- sub-spans, GC, profiler (ISSUE 27)

def _wave(base, n=12):
    from ingress_plus_tpu.serve.normalize import Request

    return [(Request(uri="/q?a=%d+union+select+2" % i if i % 4 == 0
                     else "/item/%d?q=benign" % i,
                     request_id=str(base + i)), base + i)
            for i in range(n)]


def test_sub_stage_series_and_counters_after_traffic(server):
    """Every series this PR adds is on /metrics after traffic, and per
    dispatch the sub-spans lie inside the stage they open."""
    from ingress_plus_tpu.utils.trace import (
        PER_DISPATCH, SUBSTAGES, stage_breakdown_from_metrics)

    got = _drive(server, _wave(5000))
    assert got[5000]["attack"] and not got[5001]["attack"]
    metrics = _get("/metrics").decode()
    sb = stage_breakdown_from_metrics(metrics)
    for stage in SUBSTAGES:
        assert sb[stage]["count"] > 0, stage
    # per-dispatch sub-stages count dispatches; reply counts requests
    for stage in PER_DISPATCH:
        assert sb[stage]["count"] == sb["batch"]["count"], stage
    assert sb["reply"]["count"] >= len(got)
    values = {ln.split()[0]: float(ln.split()[1])
              for ln in metrics.splitlines()
              if ln and not ln.startswith("#")}
    # a dispatch launches one program per occupied L tier and the one
    # expansion: at least 2, at most (tiers the traffic filled) + 1
    tiers = sum(1 for k, v in values.items()
                if k.startswith("ipt_bucket_rows_total{") and v > 0)
    batches = sb["batch"]["count"]
    assert tiers >= 1
    assert (2 * batches <= values["ipt_device_launches_total"]
            <= (tiers + 1) * batches)
    for g in "012":
        assert 'ipt_gc_pause_us_total{generation="%s"}' % g in values
        assert 'ipt_gc_collections_total{generation="%s"}' % g in values
    # traffic allocates: the youngest generation has collected by now
    assert values['ipt_gc_collections_total{generation="0"}'] > 0
    assert values['ipt_gc_pause_us_total{generation="0"}'] > 0
    # CPU reports no device memory: the gauge is left out, not zeroed
    assert "ipt_device_memory_peak_bytes" not in metrics
    assert ("# HELP ipt_lane_busy_us_sum host clock around launch and "
            "wait per lane (us)") in metrics

    traces = json.loads(_get("/traces"))["traces"]
    assert traces
    full = 0
    for t in traces:
        # a cycle that scanned nothing (its one request went to the
        # oversized side lane) has no scan spans: absent reads 0
        sub = {name: t["sub_us"].get(name, 0)
               for name in PER_DISPATCH}
        full += set(t["sub_us"]) == set(PER_DISPATCH)
        assert (sub["scan_pack"] + sub["scan_launch"] + sub["scan_wait"]
                <= t["engine_us"]), t
        if sub["confirm_ipc"] == 0:
            # an inline walk lies inside the stage
            assert (sub["confirm_walk"] + sub["confirm_fold"]
                    <= t["confirm_us"]), t
        else:
            # shares walked in walker processes: confirm_walk adds up
            # the shares' time over the workers and may pass the wall
            # the dispatch thread paid
            assert sub["confirm_fold"] <= t["confirm_us"], t
        assert t["gc_us"] >= 0
    assert full >= 1

    # a slow exemplar names its part: sub-spans and the cycle's GC time
    slow = json.loads(_get("/debug/slow"))["slowest"]
    ex = {e["request_id"]: e for e in slow}["5000"]
    for name in PER_DISPATCH:
        assert name + "_us" in ex["batch"], name
    assert "gc_us" in ex["batch"]


def test_forced_collection_moves_the_gc_counters():
    """In-process (a collection cannot be forced in another process):
    the hook the serve entry point installs counts a forced
    gc.collect() on /metrics, by generation."""
    import gc

    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.serve.server import ServeLoop
    from ingress_plus_tpu.utils.trace import gc_watch

    def counters(serve):
        return {ln.split()[0]: float(ln.split()[1])
                for ln in serve._metrics_text().splitlines()
                if ln.startswith("ipt_gc_")}

    b = Batcher(DetectionPipeline(
        compile_ruleset(parse_seclang(TINY_RULES)), mode="block"),
        max_batch=8)
    gc_watch.install()
    try:
        serve = ServeLoop(b, socket_path="/tmp/ipt-obs-gc.sock")
        before = counters(serve)
        gc.collect()
        after = counters(serve)
    finally:
        gc_watch.uninstall()
        b.close()
    key = 'ipt_gc_collections_total{generation="2"}'
    assert after[key] == before[key] + 1
    assert (after['ipt_gc_pause_us_total{generation="2"}']
            > before['ipt_gc_pause_us_total{generation="2"}'])


def test_debug_profile_writes_a_trace_holding_the_spans(server):
    """POST /debug/profile?seconds= traces the next stretch with the
    Python tracer off, refuses a second session meanwhile, and the
    .xplane.pb holds the program's spans on the profiler's clock."""
    import threading

    first = {}

    def profile():
        first["answer"] = _post("/debug/profile?seconds=1.0")

    t = threading.Thread(target=profile)
    t.start()
    time.sleep(0.3)
    busy_status, busy = _post("/debug/profile?seconds=0.2")
    for k in range(3):
        _drive(server, _wave(6000 + 100 * k))
    t.join(timeout=120)
    assert not t.is_alive()
    assert busy_status == 409 and "running" in busy["error"]
    status, written = first["answer"]
    assert status == 200
    assert written["path"].endswith(".xplane.pb")
    assert written["bytes"] == os.path.getsize(written["path"]) > 0
    assert written["seconds"] >= 1.0 and written["write_s"] >= 0
    assert written["options"]["python_tracer_level"] == 0

    from jax.profiler import ProfileData

    spans = {}
    for plane in ProfileData.from_file(written["path"]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ipt:"):
                    spans.setdefault(e.name, []).append(dict(e.stats))
    assert spans["ipt:scan_launch"], sorted(spans)
    for name in ("ipt:cycle", "ipt:host_prep", "ipt:scan_pack",
                 "ipt:scan_dispatch", "ipt:scan_wait", "ipt:confirm_walk",
                 "ipt:finalize_join", "ipt:confirm_fold", "ipt:lane_collect",
                 "ipt:drain_idle"):
        assert name in spans, (name, sorted(spans))
    # per-request instants stay out of the profiler's trace
    assert not {"ipt:submit", "ipt:verdict", "ipt:reply"} & set(spans)
    # the annotation carries the cycle id the ring stitches by
    assert all(s["cycle"] > 0 for s in spans["ipt:scan_launch"])
    # once it has ended the switch is free again; bad requests say so
    assert _post("/debug/profile?seconds=0.2")[0] == 200
    assert _post("/debug/profile?seconds=0")[0] == 400
    assert _post("/debug/profile?seconds=abc")[0] == 400
    try:
        _get("/debug/profile?seconds=0.2")
        raise AssertionError("GET /debug/profile must be refused")
    except urllib.error.HTTPError as e:
        assert e.code == 405
