"""The scanner-flood node: postanalytics and the verdict cache on the
served path, against the benchmark's plain reference.

The benchmark's configuration ``crs-full-postanalytics`` runs the server
with ``--spool-dir`` (every answered request recorded, attacks exported
by the exporter thread) and ``--confirm-cache`` (a verdict cache in each
walker process), under the mix ``attack-repeated`` (generator
``benchmark/generators/scanner.py``: half the pool attack payloads from
four scanner addresses, re-sent as the sweep repeats).  Here, on the CPU
at a small size:

* a served run (``python -m ingress_plus_tpu.serve`` on the CPU, two
  walker processes, the cache on, the spool on) over a seeded scanner
  pool sent four times: every verdict is the one
  ``benchmark/reference/plainwaf.py`` gives, the cache answers lookups,
  every answered request is one postanalytics hit and none is dropped,
  and the exported attacks account for every attack verdict's client
  and classes;
* the four per-layer readers of the cell on recorded scrapes, a parent's
  scrape without the series among them (the metric is left out);
* the generator: the same seed gives the same pool, the attack share is
  exact, the sources are the stated ones, and the reference walks every
  entry without refusing one.
"""

import importlib.util
import json
import os
import socket
import struct
import subprocess
import sys
from collections import defaultdict
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from generators import scanner                                # noqa: E402
from generators.corpus import attack_payloads                 # noqa: E402
from harness import launch                                    # noqa: E402
from harness.scrape import Window, parse_metrics              # noqa: E402
from harness.wire import FrameReader, decode_response         # noqa: E402
from harness.wire import encode_request                       # noqa: E402
from reference import plainwaf                                # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "attack-repeated.json").read_text())
PARAMS = TRAFFIC["params"]
READERS = ("post.record_ms", "post.export_share", "post.dropped_share",
           "confirm.cache_hit_share")
#: the exporter's rate detections, which count something else than hits
RATE_CLASSES = ("brute", "dirbust")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metrics." + name.replace(".", "_"),
        BENCH / "layer_metrics" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ generator

def test_same_seed_same_pool_and_another_seed_another():
    a = scanner.generate(2**31 + 5, 256, PARAMS)
    b = scanner.generate(2**31 + 5, 256, PARAMS)
    c = scanner.generate(2**31 + 6, 256, PARAMS)
    frames = [encode_request(r, req_id=i) for i, r in enumerate(a)]
    assert frames == [encode_request(r, req_id=i) for i, r in enumerate(b)]
    assert frames != [encode_request(r, req_id=i) for i, r in enumerate(c)]


@pytest.mark.parametrize("seed", [3, 2**31 + 77])
def test_attack_share_and_sources_are_exact(seed):
    n = 4096
    reqs = scanner.generate(seed, n, PARAMS)
    scanners = {scanner.scanner_address(i) for i in range(PARAMS["scanners"])}
    clients = {scanner.client_address(i) for i in range(PARAMS["clients"])}
    marks = set()
    for p in attack_payloads():
        marks |= {p, p.replace(" ", "+"), json.dumps(p)[1:-1]}
    attacks = [r for r in reqs if r.headers["x-real-ip"] in scanners]
    assert len(attacks) == round(n * PARAMS["attack_fraction"])
    assert {r.headers["x-real-ip"] for r in attacks} == scanners
    benign = [r for r in reqs if r.headers["x-real-ip"] not in scanners]
    assert {r.headers["x-real-ip"] for r in benign} <= clients
    for r in attacks:
        blob = r.uri + "\n" + r.body.decode() + "\n" + r.headers["user-agent"]
        assert any(m in blob for m in marks), r
    # every placement of the sweep is there: query, form, JSON, path, agent
    kinds = {("query" if r.uri.startswith("/search?")
              else "path" if r.uri.startswith("/files/")
              else "agent" if r.uri == "/index.html"
              else r.headers["content-type"]) for r in attacks}
    assert kinds == {"query", "path", "agent", "application/json",
                     "application/x-www-form-urlencoded"}


@pytest.fixture(scope="module")
def full_deployment():
    return plainwaf.Deployment(BENCH / "rules" / "crs",
                               BENCH / "rules" / "sigpack.json")


def test_the_reference_walks_every_entry(full_deployment):
    """No entry of the pool is refused (`NotModelled`) by the
    reference, at the cell's rehearsal size; every attack entry is an
    attack to it."""
    n = TRAFFIC["rehearsal"]["pool"]
    reqs = scanner.generate(2**31 + 101, n, PARAMS)
    scanners = {scanner.scanner_address(i) for i in range(PARAMS["scanners"])}
    for i, r in enumerate(reqs):
        _idx, request = plainwaf.decode_frame(encode_request(r, req_id=i))
        attack, _blocked, _ids = plainwaf.verdict(full_deployment, request)
        if r.headers["x-real-ip"] in scanners:
            assert attack, r


# -------------------------------------------------------------- readers

_PARENT = """
ipt_requests_total 1000
ipt_stage_us_sum{stage="reply"} 90000
ipt_stage_us_count{stage="reply"} 1000
ipt_confirm_memo_hits_total 5
"""


def _node(requests, record_us, export_us, dropped, hit, miss):
    return """
ipt_requests_total %d
ipt_stage_us_sum{stage="post_record"} %d
ipt_stage_us_count{stage="post_record"} %d
ipt_stage_us_sum{stage="post_export"} %d
ipt_stage_us_count{stage="post_export"} 2
ipt_post_hits_total %d
ipt_post_dropped_total %d
ipt_confirm_cache_total{result="hit"} %d
ipt_confirm_cache_total{result="miss"} %d
""" % (requests, record_us, requests, export_us, requests, dropped, hit,
       miss)


def test_the_readers_on_a_node_with_the_series():
    w = Window(parse_metrics(_node(1000, 20_000, 100_000, 0, 100, 50)),
               parse_metrics(_node(3000, 60_000, 350_000, 0, 700, 250)))
    ctx = {"window": w, "seconds": 10.0}
    got = {name: reader(name).read(ctx) for name in READERS}
    assert got["post.record_ms"] == pytest.approx(0.02)
    assert got["post.export_share"] == pytest.approx(2.5)
    assert got["post.dropped_share"] == 0.0
    assert got["confirm.cache_hit_share"] == pytest.approx(75.0)
    dropped = Window(parse_metrics(_node(1000, 0, 0, 0, 0, 0)),
                     parse_metrics(_node(3000, 0, 0, 50, 0, 0)))
    assert reader("post.dropped_share").read(
        {"window": dropped, "seconds": 10.0}) == pytest.approx(2.5)
    # series there, nothing moved: a share of nothing is not read
    still = Window(parse_metrics(_node(1000, 0, 0, 0, 5, 5)),
                   parse_metrics(_node(1000, 0, 0, 0, 5, 5)))
    ctx = {"window": still, "seconds": 10.0}
    assert reader("post.record_ms").read(ctx) is None
    assert reader("post.export_share").read(ctx) == 0.0
    assert reader("post.dropped_share").read(ctx) is None
    assert reader("confirm.cache_hit_share").read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_a_parent_without_the_series_leaves_the_metric_out(name):
    w = Window(parse_metrics(_PARENT),
               parse_metrics(_PARENT.replace("1000", "3000")))
    assert reader(name).read({"window": w, "seconds": 10.0}) is None


# ---------------------------------------------------------- served run

def _response_classes(payload: bytes) -> list:
    """The class indices of a verdict frame (the harness's decoder
    returns the rule ids only)."""
    head = struct.Struct("<QBIBH")
    n_cls = head.unpack_from(payload)[3]
    return list(payload[head.size:head.size + n_cls])


def _metrics(port):
    return parse_metrics(launch.http_get(port, "/metrics").decode())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A CPU server of the SecLang tree the reference reads (as the
    crs-seclang configuration serves it) with the cell's postanalytics
    and cache options, two walker processes."""
    tmp = tmp_path_factory.mktemp("postanalytics")
    sock, port = str(tmp / "srv.sock"), launch.free_port()
    spool = tmp / "spool"
    log = tmp / "serve.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ingress_plus_tpu.serve",
         "--socket", sock, "--http-port", str(port), "--platform", "cpu",
         "--mode", "block", "--rules-dir", str(BENCH / "rules" / "crs"),
         "--max-batch", "8", "--max-delay-us", "2000",
         # a CPU scan is slow: the brownout ladder must not arm
         "--hard-deadline-ms", "60000",
         "--confirm-workers", "2", "--confirm-cache", "4096",
         "--spool-dir", str(spool), "--export-interval-s", "0.5"],
        cwd=str(REPO), env=env, stdout=subprocess.DEVNULL,
        stderr=log.open("w"))
    try:
        launch.wait_for("server socket", lambda: launch.sock_accepts(sock),
                        proc, log, timeout=900)
        yield {"proc": proc, "sock": sock, "port": port, "spool": spool,
               "log": log}
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)


def _send_waves(sock_path, frames, order, wave=8):
    """Send `order` (pool indices) in waves of `wave` frames on one
    connection; {send number: (pool index, verdict, class indices)}."""
    out = {}
    s = socket.socket(socket.AF_UNIX)
    s.connect(sock_path)
    s.settimeout(300)
    reader_ = FrameReader()
    try:
        for at in range(0, len(order), wave):
            ids = list(range(at, min(at + wave, len(order))))
            for k in ids:
                frame = bytearray(frames[order[k]])
                struct.pack_into("<Q", frame, 8, k)
                s.sendall(bytes(frame))
            got = 0
            while got < len(ids):
                for payload in reader_.feed(s.recv(1 << 16)):
                    v = decode_response(payload)
                    assert v["req_id"] not in out
                    out[v["req_id"]] = (order[v["req_id"]], v,
                                        _response_classes(payload))
                    got += 1
    finally:
        s.close()
    return out


def test_served_scanner_pool_against_the_reference(served):
    n, sends = 48, 4
    reqs = scanner.generate(2**31 + 2024, n, PARAMS)
    frames = [encode_request(r, req_id=i) for i, r in enumerate(reqs)]
    dep = plainwaf.Deployment(BENCH / "rules" / "crs", None)
    want = {}
    for i, f in enumerate(frames):
        _idx, request = plainwaf.decode_frame(f)
        a, b, ids = plainwaf.verdict(dep, request)
        want[i] = [a, b, sorted(ids)]
    assert any(w[0] for w in want.values())

    before = _metrics(served["port"])
    got = _send_waves(served["sock"], frames, list(range(n)) * sends)
    after = _metrics(served["port"])
    assert len(got) == n * sends
    for _k, (idx, v, _cls) in got.items():
        assert not v["fail_open"]
        assert [v["attack"], v["blocked"], sorted(v["rule_ids"])] \
            == want[idx], reqs[idx]

    w = Window(before, after)
    # (a) the walkers walked, and their caches answered repeats
    assert w.delta("ipt_confirm_requests_total", where="process") > 0
    assert w.delta("ipt_confirm_cache_total", result="hit") > 0
    assert w.delta("ipt_confirm_memo_hits_total") == 0
    # (b) one hit an answered request, none dropped, each timed
    assert w.delta_unlabelled("ipt_post_hits_total") == n * sends
    assert after[("ipt_post_dropped_total", ())] == 0
    assert w.stage_count("post_record") == n * sends
    # the cell's readers find their series on a live node
    ctx = {"window": w, "seconds": 1.0}
    values = {name: reader(name).read(ctx) for name in READERS}
    assert values["post.record_ms"] > 0
    assert values["post.dropped_share"] == 0.0
    assert 0 < values["confirm.cache_hit_share"] <= 100

    # the export: stop the server (its exporter's last flush), then
    # every attack verdict's client and classes are in the spool
    launch.stop(served["proc"], "server", served["log"], timeout=120)
    assert served["proc"].returncode == 0
    hits_by_client = defaultdict(int)
    classes_by_client = defaultdict(set)
    for _k, (idx, v, cls) in got.items():
        if v["attack"]:
            client = reqs[idx].headers["x-real-ip"]
            hits_by_client[client] += max(len(cls), 1)
            classes_by_client[client] |= set(cls) or {-1}
    assert hits_by_client
    exported = defaultdict(int)
    exported_classes = defaultdict(set)
    files = sorted(served["spool"].glob("attacks.*.jsonl"))
    assert files
    for f in files:
        for line in f.read_text().splitlines():
            rec = json.loads(line)
            if rec["class"] in RATE_CLASSES:
                continue
            exported[rec["client"]] += rec["count"]
            exported_classes[rec["client"]].add(rec["class"])
    assert dict(exported) == dict(hits_by_client)
    assert {c: len(s) for c, s in exported_classes.items()} \
        == {c: len(s) for c, s in classes_by_client.items()}
