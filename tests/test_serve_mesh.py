"""Mesh-backed serving (parallel/serve_mesh.MeshEngine): the DP x TP
sharded step behind the single-chip engine API, so the SAME pipeline /
batcher / confirm chain serves multi-chip.  Runs on the virtual 8-device
CPU mesh (conftest), the kind-cluster analog from SURVEY.md §4."""

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.parallel.serve_mesh import MeshEngine, parse_mesh_spec
from ingress_plus_tpu.serve.normalize import Request

RULES = """
SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" "id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,severity:CRITICAL,tag:'attack-sqli'"
SecRule ARGS|REQUEST_BODY "@rx (?i)<script[^>]*>" "id:941100,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|ARGS "@rx /etc/(?:passwd|shadow)" "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
SecRule ARGS "@pm sleep( benchmark( xp_cmdshell" "id:942150,phase:2,block,severity:ERROR,tag:'attack-sqli'"
"""


@pytest.fixture(scope="module")
def ruleset():
    return compile_ruleset(parse_seclang(RULES))


def _requests():
    return [
        Request(method="GET",
                uri="/p?q=1%27%20UNION%20SELECT%20password%20FROM%20users",
                headers={}, body=b""),
        Request(method="GET", uri="/index.html?page=3", headers={},
                body=b""),
        Request(method="GET",
                uri="/p?q=%3Cscript%3Ealert(1)%3C/script%3E",
                headers={}, body=b""),
        Request(method="GET", uri="/p?f=../../etc/passwd", headers={},
                body=b""),
        Request(method="POST", uri="/login", headers={},
                body=b"user=jo&pass=hunter2"),
    ]


def _vt(v):
    return (v.attack, v.blocked, tuple(sorted(v.rule_ids)))


def test_parse_mesh_spec():
    m = parse_mesh_spec("data=2,model=4")
    assert m.shape["data"] == 2 and m.shape["model"] == 4
    m = parse_mesh_spec("2x4")
    assert m.shape["data"] == 2 and m.shape["model"] == 4
    with pytest.raises(ValueError):
        parse_mesh_spec("data=0,model=4")
    with pytest.raises(ValueError):
        parse_mesh_spec("16x16")


def test_mesh_pipeline_verdict_parity(ruleset):
    reqs = _requests()
    ref = DetectionPipeline(ruleset, mode="block")
    want = [_vt(v) for v in ref.detect(reqs)]
    assert any(w[0] for w in want) and not all(w[0] for w in want)

    mp = DetectionPipeline(ruleset, mode="block", fail_open=False)
    mp.engine = MeshEngine(ruleset, parse_mesh_spec("2x4"))
    got = [_vt(v) for v in mp.detect(reqs)]
    assert got == want

    # and again with the sharded pair impl
    mp.engine.scan_impl = "pair"
    got = [_vt(v) for v in mp.detect(reqs)]
    assert got == want


def test_mesh_engine_survives_hot_swap(ruleset):
    from ingress_plus_tpu.serve.batcher import Batcher

    p = DetectionPipeline(ruleset, mode="block", fail_open=False)
    p.engine = MeshEngine(ruleset, parse_mesh_spec("2x4"))
    b = Batcher(p, max_batch=8, max_delay_s=0.0001)
    cr2 = compile_ruleset(parse_seclang(RULES))
    b.swap_ruleset(cr2)
    assert isinstance(b.pipeline.engine, MeshEngine)
    got = [_vt(v) for v in b.pipeline.detect(_requests())]
    ref = DetectionPipeline(ruleset, mode="block")
    want = [_vt(v) for v in ref.detect(_requests())]
    assert got == want


def test_mesh_autoselect_returns_timings(ruleset):
    mp = DetectionPipeline(ruleset, mode="block", fail_open=False)
    mp.engine = MeshEngine(ruleset, parse_mesh_spec("2x4"))
    timings = mp.engine.autoselect_scan_impl(B=16, L=128, iters=2)
    assert set(timings) >= {"take", "pair"}
    assert mp.engine.scan_impl in timings


def test_mesh_serving_over_wire(tmp_path):
    """Full wire e2e: serve subprocess with --mesh 2x4 (8 virtual CPU
    devices), UDS protocol roundtrip, verdicts from the sharded step."""
    import os
    import socket
    import subprocess
    import sys
    import time
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    rules_dir = tmp_path / "rules"
    rules_dir.mkdir()
    (rules_dir / "tiny.conf").write_text(RULES)
    sock_path = str(tmp_path / "mesh.sock")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ingress_plus_tpu.serve",
         "--socket", sock_path, "--http-port", "0",
         "--rules-dir", str(rules_dir), "--platform", "cpu",
         "--mesh", "2x4",
         "--max-delay-us", "1000", "--no-warmup"],
        cwd=str(repo), env=env, stderr=subprocess.PIPE, text=True)
    try:
        for _ in range(600):
            if Path(sock_path).exists():
                try:
                    s = socket.socket(socket.AF_UNIX)
                    s.connect(sock_path)
                    s.close()
                    break
                except OSError:
                    pass
            if proc.poll() is not None:
                raise RuntimeError("server died: %s" % proc.stderr.read())
            time.sleep(0.1)
        else:
            raise RuntimeError("server socket never appeared")

        from ingress_plus_tpu.serve.protocol import (
            RESP_MAGIC, FrameReader, decode_response, encode_request)

        s = socket.socket(socket.AF_UNIX)
        s.connect(sock_path)
        s.sendall(encode_request(
            Request(uri="/q?a=1+union+select+2"), req_id=9001))
        s.sendall(encode_request(Request(uri="/benign"), req_id=9002))
        reader = FrameReader(RESP_MAGIC)
        got = {}
        s.settimeout(120)
        while len(got) < 2:
            frames = reader.feed(s.recv(65536))
            for f in frames:
                r = decode_response(f)
                got[r["req_id"]] = r
        s.close()
        assert got[9001]["attack"] and got[9001]["blocked"]
        assert 942100 in got[9001]["rule_ids"]
        assert not got[9002]["attack"]
    finally:
        proc.terminate()
        proc.wait(timeout=10)
