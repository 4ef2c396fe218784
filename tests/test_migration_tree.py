"""Migration smoke: a real-world-shaped ModSecurity deployment tree —
entry config with Includes, crs-setup with SecActions, rule files with
@pmFromFile/@ipMatchFromFile data files, and a trailing exclusion file —
loads UNCHANGED through --rules-dir and serves verdicts over the wire.
This is the "a user of the reference can switch" test (task contract):
point the serve loop at your existing tree and go."""

import os
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _write_tree(root: Path) -> Path:
    rules = root / "rules"
    rules.mkdir()
    (root / "modsecurity.conf").write_text(
        "SecRuleEngine On\n"
        "SecRequestBodyAccess On\n"
        'SecDefaultAction "phase:2,log,pass"\n'
        "Include crs-setup.conf\n"
        "Include rules/*.conf\n")
    (root / "crs-setup.conf").write_text(
        'SecAction "id:900990,phase:1,pass,'
        'setvar:tx.crs_setup_version=330,'
        'setvar:tx.inbound_anomaly_score_threshold=5"\n')
    (rules / "910-ip.conf").write_text(
        'SecRule REMOTE_ADDR "@ipMatchFromFile scanner-ips.data" '
        '"id:910110,phase:1,deny,severity:CRITICAL,'
        "tag:'attack-generic'\"\n")
    (rules / "scanner-ips.data").write_text("# scanners\n203.0.113.0/24\n")
    (rules / "942-sqli.conf").write_text(
        'SecRule ARGS|REQUEST_BODY "@rx (?i)union[\\s/*]+select" '
        '"id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,'
        "severity:CRITICAL,tag:'attack-sqli'\"\n"
        'SecRule ARGS "@pmFromFile sqli-kw.data" '
        '"id:942160,phase:2,block,severity:ERROR,tag:\'attack-sqli\'"\n')
    (rules / "sqli-kw.data").write_text("xp_cmdshell\nbenchmark(\n")
    (rules / "999-exclusions.conf").write_text(
        "SecRuleRemoveById 942160\n")
    return root


def test_migration_tree_loads_and_serves(tmp_path):
    tree = _write_tree(tmp_path)
    sock_path = str(tmp_path / "m.sock")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ingress_plus_tpu.serve",
         "--socket", sock_path, "--http-port", "0",
         "--rules-dir", str(tree / "modsecurity.conf"),
         "--platform", "cpu",
         "--max-delay-us", "1000", "--no-warmup"],
        cwd=str(REPO), env=env, stderr=subprocess.PIPE, text=True)
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

        from ingress_plus_tpu.serve.normalize import Request
        from ingress_plus_tpu.serve.protocol import (
            RESP_MAGIC, FrameReader, decode_response, encode_request)

        s = socket.socket(socket.AF_UNIX)
        s.connect(sock_path)
        s.sendall(encode_request(
            Request(uri="/q?a=1+union+select+2"), req_id=1))
        s.sendall(encode_request(
            Request(uri="/q", client_ip="203.0.113.7"), req_id=2))
        # 942160 was removed by the exclusion file: its keyword alone
        # must NOT fire
        s.sendall(encode_request(
            Request(uri="/q?a=xp_cmdshell"), req_id=3))
        s.sendall(encode_request(Request(uri="/benign"), req_id=4))
        reader = FrameReader(RESP_MAGIC)
        got = {}
        s.settimeout(120)
        while len(got) < 4:
            for f in reader.feed(s.recv(65536)):
                r = decode_response(f)
                got[r["req_id"]] = r
        s.close()
        assert got[1]["attack"] and 942100 in got[1]["rule_ids"]
        assert got[2]["attack"] and 910110 in got[2]["rule_ids"]
        assert not got[3]["attack"], got[3]   # excluded rule stays dead
        assert not got[4]["attack"]
    finally:
        proc.terminate()
        proc.wait(timeout=10)
