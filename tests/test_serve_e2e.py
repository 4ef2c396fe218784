"""End-to-end serve path: UDS server subprocess ⇄ C++ loadgen binary.

The kind-cluster e2e analog (SURVEY.md §4): a real serve loop process, the
real native client, real frames over a real socket — asserting verdict
behavior and liveness endpoints, not internals.  Uses a tiny ruleset so
the CPU-backed scan keeps CI fast.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
LOADGEN = REPO / "native" / "sidecar" / "loadgen"

TINY_RULES = """
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)<script" \
    "id:941100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|ARGS|REQUEST_BODY|REQUEST_HEADERS "@rx /etc/passwd" \
    "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
SecRule RESPONSE_BODY "@rx (?i)you have an error in your sql syntax" \
    "id:951100,phase:4,block,t:lowercase,severity:CRITICAL,tag:'attack-leak'"
"""


@pytest.fixture(scope="module")
def loadgen_bin():
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    subprocess.run(["make", "-s", "-C", str(REPO / "native" / "sidecar")],
                   check=True)
    assert LOADGEN.exists()
    return LOADGEN


@pytest.fixture(scope="module")
def server(tmp_path_factory, loadgen_bin):
    tmp = tmp_path_factory.mktemp("serve")
    rules_dir = tmp / "rules"
    rules_dir.mkdir()
    (rules_dir / "tiny.conf").write_text(TINY_RULES)
    sock = str(tmp / "ipt.sock")
    spool = tmp / "spool"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ingress_plus_tpu.serve",
         "--socket", sock, "--http-port", "19901",
         "--rules-dir", str(rules_dir), "--platform", "cpu",
         # warmup ON (tiny pack, compiles in seconds): with --no-warmup
         # a cold-compile stall mid-loadgen queues requests long enough
         # for the brownout ladder to serve degraded (attack, unblocked)
         # verdicts — the test then flakes on blocked == attacks under
         # full-suite CPU contention
         # hard deadline raised WAY above the production default: the
         # brownout ladder derives its queue-delay thresholds from it,
         # and a full-suite 1-core CI host can stall any subprocess for
         # hundreds of ms (scheduler bursts, cold XLA) — this module
         # asserts exact verdicts (blocked == attacks), not shedding
         # behavior, so the ladder must not be armed at CI sensitivity
         "--hard-deadline-ms", "5000",
         "--max-delay-us", "1000", "--max-batch", "64",
         "--spool-dir", str(spool), "--export-interval-s", "0.5"],
        cwd=str(REPO), env=env,
        stderr=subprocess.PIPE, text=True)
    # wait for the socket: the start is ~100 CPU-seconds of XLA compiles
    # (the warm-up's 42 shapes), 20 s of wall on an idle 8-core host and
    # past 60 s beside the suite's other xdist workers
    for _ in range(1800):
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

    class Srv(str):  # str so existing uses (socket path) keep working
        pass

    srv = Srv(sock)
    srv.spool = spool
    yield srv
    proc.terminate()
    proc.wait(timeout=10)


def _export_corpus(path, n=200, attack_fraction=0.3):
    from ingress_plus_tpu.utils.export_corpus import export

    return export(str(path), n=n, seed=3, attack_fraction=attack_fraction)


def test_loadgen_roundtrip(server, loadgen_bin, tmp_path):
    corpus = tmp_path / "c.bin"
    n = _export_corpus(corpus, n=200)
    out = subprocess.run(
        [str(loadgen_bin), "--socket", server, "--corpus", str(corpus),
         "--connections", "2", "--inflight", "16", "--requests", "400"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    assert result["requests"] == 400
    assert result["fail_open"] == 0
    # the corpus plants sqli/xss/lfi payloads the tiny ruleset must catch
    assert result["attacks"] > 0
    assert result["blocked"] == result["attacks"]  # block mode
    assert result["rps"] > 0


def test_health_and_metrics(server):
    health = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/healthz", timeout=10).read())
    assert health["status"] == "ok"
    metrics = urllib.request.urlopen(
        "http://127.0.0.1:19901/metrics", timeout=10).read().decode()
    assert "ipt_requests_total" in metrics
    assert "ipt_ruleset_info" in metrics


def test_healthz_device_path_names_the_resolved_lowering(server):
    """The server runs its default ``--scan-impl auto``: /healthz
    ``robustness.device_path.scan_impl`` (the key benchmark/run.py
    reads) and /rules/stats ``device.scan_impl`` carry the lowering the
    engine resolved from the pack's tables, never ``auto``."""
    health = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/healthz", timeout=10).read())
    path = health["robustness"]["device_path"]
    assert path["scan_impl"] == "pair"
    assert set(path) == {"scan_impl", "backend", "device_kind",
                         "device_count", "lane_devices"}
    stats = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/rules/stats", timeout=10).read())
    assert stats["device"]["scan_impl"] == "pair"


def test_wallarm_status_and_spool(server):
    """Postanalytics read side: counters endpoint + exporter spool
    (the /wallarm-status† + export-attacks† analogs, SURVEY.md §3.4/§3.5).
    Runs after loadgen so counters are non-zero."""
    st = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/wallarm-status", timeout=10).read())
    assert st["requests"] > 0
    assert st["attacks"] > 0
    assert st["blocked"] == st["attacks"]
    assert "queue" in st and "export" in st
    # exporter flushes every 0.5s; a per-pid attacks.*.jsonl must appear
    spool_file = None
    for _ in range(40):
        files = sorted(server.spool.glob("attacks*.jsonl"))
        if files and files[0].read_text().strip():
            spool_file = files[0]
            break
        time.sleep(0.25)
    assert spool_file is not None, "spool file never appeared"
    recs = [json.loads(l) for l in spool_file.read_text().splitlines()]
    assert sum(r["count"] for r in recs) > 0
    assert all("class" in r and "client" in r for r in recs)


def test_python_client_roundtrip(server):
    """Drive the raw protocol from Python too (sidecar-independent)."""
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)
    from ingress_plus_tpu.serve.normalize import Request

    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.sendall(encode_request(
        Request(uri="/q?a=1+union+select+2"), req_id=7001))
    s.sendall(encode_request(Request(uri="/benign"), req_id=7002))
    reader = FrameReader(RESP_MAGIC)
    got = {}
    s.settimeout(120)
    while len(got) < 2:
        frames = reader.feed(s.recv(65536))
        for f in frames:
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert got[7001]["attack"] and got[7001]["blocked"]
    assert 942100 in got[7001]["rule_ids"]
    assert not got[7002]["attack"]

def test_response_scan_over_wire(server):
    """Response-side analysis (wallarm_parse_response analog): a PTPI
    frame carrying an upstream response with a planted SQL error leak
    must come back flagged; a clean response must not.  Request-side
    rules must NOT fire on response bytes (station-keeping: the planted
    body contains 'union select' too, but 942100 targets request
    streams only)."""
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_response_scan)
    from ingress_plus_tpu.serve.normalize import Response

    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    leaky = Response(
        status=500, headers={"Content-Type": "text/html"},
        body=b"<h1>Oops</h1>You have an error in your SQL syntax near "
             b"'union select' at line 1 ")
    clean = Response(
        status=200, headers={"Content-Type": "application/json"},
        body=b'{"status": "ok", "items": [1, 2, 3]}')
    s.sendall(encode_response_scan(leaky, req_id=8001))
    s.sendall(encode_response_scan(clean, req_id=8002))
    reader = FrameReader(RESP_MAGIC)
    got = {}
    s.settimeout(120)
    while len(got) < 2:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert got[8001]["attack"] and got[8001]["blocked"]
    assert got[8001]["rule_ids"] == [951100]
    assert got[8001]["classes"] == ["leak"]
    assert not got[8002]["attack"]


def test_streaming_body_over_wire(server):
    """Config #5 on the wire: MODE_STREAM request + chunk frames; attack
    spans a chunk boundary; a parallel clean stream passes."""
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        MODE_STREAM, RESP_MAGIC, FrameReader, decode_response,
        encode_chunk, encode_request)

    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.settimeout(120)
    # stream 1: attack split across inline-first-chunk + two chunk frames
    s.sendall(encode_request(Request(uri="/upload", body=b"f=1 uni"),
                             req_id=6001, mode=2 | MODE_STREAM))
    s.sendall(encode_chunk(6001, b"on sele"))
    # stream 2 interleaved: clean
    s.sendall(encode_request(Request(uri="/upload2"),
                             req_id=6002, mode=2 | MODE_STREAM))
    s.sendall(encode_chunk(6002, b"hello "))
    s.sendall(encode_chunk(6001, b"ct pass from users", last=True))
    s.sendall(encode_chunk(6002, b"world", last=True))
    reader, got = FrameReader(RESP_MAGIC), {}
    while len(got) < 2:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert got[6001]["attack"] and got[6001]["blocked"]
    assert 942100 in got[6001]["rule_ids"]
    assert not got[6002]["attack"]


def test_wrapped_bodies_over_wire(server):
    """SURVEY.md §3.3 decode/unpack parity on the wire: a gzipped and a
    base64-wrapped SQLi body must be detected end-to-end; streamed gzip
    chunks too."""
    import base64
    import gzip

    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        MODE_STREAM, RESP_MAGIC, FrameReader, decode_response,
        encode_chunk, encode_request)

    sqli = b"q=1' UNION SELECT password FROM users--"
    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.settimeout(120)
    s.sendall(encode_request(
        Request(method="POST", uri="/api",
                headers={"Content-Encoding": "gzip"},
                body=gzip.compress(sqli)), req_id=8001))
    s.sendall(encode_request(
        Request(method="POST", uri="/api",
                body=base64.b64encode(sqli)), req_id=8002))
    # streamed gzip: the same compressed body split into chunk frames
    comp = gzip.compress(b"x" * 30000 + sqli + b"y" * 30000)
    s.sendall(encode_request(
        Request(method="POST", uri="/up",
                headers={"Content-Encoding": "gzip"}, body=comp[:1000]),
        req_id=8003, mode=2 | MODE_STREAM))
    for i in range(1000, len(comp), 4096):
        s.sendall(encode_chunk(8003, comp[i:i + 4096]))
    s.sendall(encode_chunk(8003, b"", last=True))
    reader, got = FrameReader(RESP_MAGIC), {}
    while len(got) < 3:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    for rid in (8001, 8002, 8003):
        assert got[rid]["attack"] and got[rid]["blocked"], (rid, got[rid])
        assert "sqli" in got[rid]["classes"], (rid, got[rid])


def test_oversized_body_over_wire(server):
    """BASELINE config #5 corner: a 1MB padded-prefix attack sent as ONE
    non-streamed frame must be caught (the serve loop reroutes it through
    the stream engine internally)."""
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)

    body = b"P" * (1 << 20) + b" 1' union select password from users --"
    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.settimeout(120)
    s.sendall(encode_request(
        Request(method="POST", uri="/upload", body=body), req_id=9001))
    reader, got = FrameReader(RESP_MAGIC), {}
    while len(got) < 1:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert got[9001]["attack"] and got[9001]["blocked"]
    assert 942100 in got[9001]["rule_ids"]


def test_configuration_endpoints_and_dbg(server, tmp_path):
    """Dynamic-config plane: tenant push, ruleset hot-swap (sync-node
    analog), inspection — all through the dbg CLI code path."""
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.control import dbg

    conf = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/configuration", timeout=10).read())
    assert conf["rules"] == 4 and conf["tenants"] == 1, conf

    # push a tenant table: tenant 1 = sqli only
    req = urllib.request.Request(
        "http://127.0.0.1:19901/configuration/tenants",
        data=json.dumps({"1": ["attack-sqli"]}).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    assert json.loads(urllib.request.urlopen(req, timeout=10).read()) == \
        {"tenants": 2}

    # tenant 1 must not fire the xss rule, tenant 0 must
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)
    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.sendall(encode_request(
        Request(uri="/q?a=<script>x</script>", tenant=1), req_id=8001))
    s.sendall(encode_request(
        Request(uri="/q?a=<script>x</script>", tenant=0), req_id=8002))
    reader, got = FrameReader(RESP_MAGIC), {}
    s.settimeout(120)
    while len(got) < 2:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert not got[8001]["attack"], "tenant mask failed to exclude xss rule"
    assert got[8002]["attack"]

    # hot-swap to a 1-rule ruleset from a checkpoint artifact
    art = tmp_path / "swap"
    cr = compile_ruleset(parse_seclang(
        'SecRule ARGS "@rx (?i)drop\\s+table" '
        '"id:955000,phase:2,block,severity:CRITICAL,tag:\'attack-sqli\'"'))
    cr.save(art)
    # --force: this asserts the ONE-SHOT swap lane (break-glass).  The
    # default is now the guarded staged rollout (control/rollout.py) —
    # and it would correctly REJECT this pack: a bare "drop table" rule
    # blocks the benign SQL-in-prose fixtures (tests/test_rollout.py
    # covers the staged path end to end).
    rc = dbg.main(["ruleset", "--server", "127.0.0.1:19901",
                   "--swap", str(art), "--force"])
    assert rc == 0
    conf = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/configuration", timeout=10).read())
    assert conf["rules"] == 1 and conf["ruleset"] == cr.version
    # old rules gone, new rule live
    s = socket.socket(socket.AF_UNIX)
    s.connect(server)
    s.sendall(encode_request(
        Request(uri="/q?a=1;drop+table+users"), req_id=9001))
    s.sendall(encode_request(
        Request(uri="/q?a=1+union+select+2"), req_id=9002))
    reader, got = FrameReader(RESP_MAGIC), {}
    s.settimeout(120)
    while len(got) < 2:
        for f in reader.feed(s.recv(65536)):
            r = decode_response(f)
            got[r["req_id"]] = r
    s.close()
    assert got[9001]["attack"] and 955000 in got[9001]["rule_ids"]
    assert not got[9002]["attack"]


def test_acl_hot_swap_over_wire(server):
    """wallarm-acl enforcement e2e (VERDICT r03 item #6): push an ACL via
    the dynamic-config lane, then verify deny / greylist+safe_blocking /
    allow decisions change live verdicts with no restart."""
    from ingress_plus_tpu.models.acl import CLIENT_IP_HEADER
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)

    req = urllib.request.Request(
        "http://127.0.0.1:19901/configuration/acl",
        data=json.dumps({
            "acls": {"edge": {"deny": ["203.0.113.0/24"],
                              "greylist": ["198.51.100.0/24"]}},
            "default": "edge",
        }).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    assert json.loads(urllib.request.urlopen(req, timeout=10).read())[
        "acls"] == ["edge"]

    def verdict(uri, ip, mode=2, rid=8101):
        s = socket.socket(socket.AF_UNIX)
        s.connect(server)
        s.sendall(encode_request(Request(
            uri=uri, headers={"host": "h", CLIENT_IP_HEADER: ip}),
            req_id=rid, mode=mode))
        reader = FrameReader(RESP_MAGIC)
        s.settimeout(120)
        got = None
        while got is None:
            for f in reader.feed(s.recv(65536)):
                got = decode_response(f)
        s.close()
        return got

    # denied source: blocked even on a benign request, class "acl"
    r = verdict("/benign", "203.0.113.50")
    assert r["blocked"] and "acl" in r["classes"], r
    # neutral source, benign: untouched
    r = verdict("/benign", "192.0.2.1", rid=8102)
    assert not r["blocked"], r
    # greylisted source + safe_blocking location mode: attack blocks
    # (the suite's earlier hot-swap test left the 1-rule "drop table"
    # pack live — use its payload)
    r = verdict("/q?a=1;drop+table+users", "198.51.100.9", mode=3, rid=8103)
    assert r["attack"] and r["blocked"], r
    # non-greylisted source + safe_blocking: attack monitored only
    r = verdict("/q?a=1;drop+table+users", "192.0.2.9", mode=3, rid=8104)
    assert r["attack"] and not r["blocked"], r

    # swap to an allowlist: the same attack source is now exempt
    req = urllib.request.Request(
        "http://127.0.0.1:19901/configuration/acl",
        data=json.dumps({"acls": {"edge": {"allow": ["192.0.2.0/24"]}},
                         "default": "edge"}).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=10)
    r = verdict("/q?a=1;drop+table+users", "192.0.2.9", rid=8105)
    assert r["attack"] and not r["blocked"], r

    # the dbg CLI drives the same lane (push + inspect)
    from ingress_plus_tpu.control import dbg
    rc = dbg.main(["acl", "--server", "127.0.0.1:19901", "--set",
                   json.dumps({"acls": {"ops": {"deny": ["203.0.113.0/24"]}},
                               "default": "ops"})])
    assert rc == 0
    conf = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:19901/configuration", timeout=10).read())
    assert conf["acls"] == ["ops"]
    assert dbg.main(["acl", "--server", "127.0.0.1:19901"]) == 0

    # clear ACLs so later tests see the original behavior
    req = urllib.request.Request(
        "http://127.0.0.1:19901/configuration/acl",
        data=json.dumps({"acls": {}}).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=10)
