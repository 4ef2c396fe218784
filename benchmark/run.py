#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in `BENCHMARK.json`: one configuration
(`configs[].file`) under one traffic mix (`benchmark/traffic/<mix>.json`).
The run starts the served path of that configuration on the chip (C++
sidecar -> `python -m ingress_plus_tpu.serve`), drives the mix through the
sidecar's socket for `--seconds`, compares every verdict of the window
with the plain reference's verdict for its frame (`reference/plainwaf.py`,
which shares no code with the program), and prints one JSON line:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with `--trace 1`).  See benchmark/README.md.

This parent never imports JAX (a parent that touches it holds the chip
its server needs) and nothing of the program.  It names no cell, no
configuration and no metric: those are data.

`BENCH_REHEARSAL=1` rehearses the same steps on CPU at a tiny size; the
line then says `"platform": "cpu"` and carries no device number.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

T_START = time.monotonic()
BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import launch, reduce, scrape                    # noqa: E402
from harness.launch import BenchFailure, check                # noqa: E402
from harness.loop import LOOPS                                # noqa: E402
from harness.wire import with_req_id                          # noqa: E402
from reference.walk import build_pool                         # noqa: E402

REHEARSAL = os.environ.get("BENCH_REHEARSAL") == "1"


def say(msg: str) -> None:
    print("[bench %6.1fs] %s" % (time.monotonic() - T_START, msg),
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ the data

def load_json(path: Path) -> dict:
    check(path.is_file(), "%s is missing" % path)
    return json.loads(path.read_text())


def load_by_name(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name."""
    path = BENCH / kind / (name + ".py")
    check(path.is_file(), "%s/%s.py is missing" % (kind, name))
    spec = importlib.util.spec_from_file_location(
        "%s.%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def resolve(bench_file: Path, workload: str) -> dict:
    """Everything the cell names, found and checked before a process is
    started: a missing file costs no chip time."""
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    check(workload in cells, "no workload %r in %s (has: %s)"
          % (workload, bench_file.name, ", ".join(sorted(cells))))
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    check(cell["config"] in configs,
          "workload %s names no configuration of %s"
          % (workload, bench_file.name))
    config = load_json(REPO / configs[cell["config"]]["file"])
    traffic_file = BENCH / "traffic" / (cell["traffic"] + ".json")
    traffic = load_json(traffic_file)
    if REHEARSAL:
        traffic.update(traffic.get("rehearsal", {}))
    check(traffic["loop"]["kind"] in LOOPS,
          "traffic %s: unknown loop kind %r (known: %s)"
          % (cell["traffic"], traffic["loop"]["kind"], sorted(LOOPS)))
    generator = load_by_name("generators", traffic["generator"])
    check(hasattr(generator, "generate"),
          "generators/%s.py has no generate()" % traffic["generator"])
    e2e = cell_metrics(bench, "end_to_end", workload)
    for m in e2e:
        check(m["name"] in ("setup_s",) + reduce.END_TO_END,
              "end-to-end metric %s has no reduction" % m["name"])
    readers = {}
    for m in cell_metrics(bench, "per_layer", workload):
        mod = load_by_name("layer_metrics", m["name"])
        check(hasattr(mod, "read"),
              "layer_metrics/%s.py has no read()" % m["name"])
        readers[m["name"]] = (m, mod)
    check((REPO / config["server_entry"]).is_file(),
          "%s is missing" % config["server_entry"])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "readers": readers}


def require_checkout() -> None:
    for rel in ("ingress_plus_tpu/serve/server.py",
                "native/sidecar/Makefile", "native/confirm/Makefile"):
        check((REPO / rel).exists(),
              "%s is missing: run from the root of a checkout" % rel)


# ------------------------------------------------------- the reference

def start_reference(spec: dict, seed: int, out_dir: Path, tag: str,
                    control: str, procs: list) -> list:
    """K children on the host, each walking a slice of the pool with the
    plain walker; they overlap the server's start-up."""
    traffic = spec["traffic"]
    reference = spec["config"]["reference"]
    k = int(traffic["reference_workers"])
    # the mix as this run uses it (rehearsal sizes applied), for the children
    traffic_file = out_dir / "traffic.json"
    traffic_file.write_text(json.dumps(traffic))
    outs = []
    for i in range(k):
        out = out_dir / ("%s.%d.json" % (tag, i))
        log = out_dir / ("%s.%d.log" % (tag, i))
        cmd = [sys.executable, str(BENCH / "reference" / "walk.py"),
               "--traffic", str(traffic_file),
               "--seed", str(seed), "--pool", str(traffic["pool"]),
               "--rules-dir", str(REPO / reference["rules_dir"]),
               "--slice", "%d/%d" % (i, k), "--out", str(out)]
        if reference.get("sigpack"):
            cmd += ["--sigpack", str(REPO / reference["sigpack"])]
        if control:
            cmd += ["--control", control]
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        p = subprocess.Popen(cmd, env=env, cwd=str(BENCH / "reference"),
                             stdout=log.open("w"), stderr=subprocess.STDOUT)
        procs.append((p, "%s child %d" % (tag, i), log))
        outs.append((p, out, log))
    return outs


def join_reference(outs: list, n_pool: int, what: str) -> dict:
    expected = {}
    for p, out, log in outs:
        try:
            p.wait(timeout=300)
        except subprocess.TimeoutExpired:
            raise BenchFailure("%s did not finish\n%s"
                               % (what, launch.tail(log)))
        check(p.returncode == 0, "%s failed\n%s" % (what, launch.tail(log)))
        expected.update(json.loads(out.read_text()))
        say(launch.tail(log, 1))
    check(len(expected) == n_pool, "%s walked %d of %d pool entries"
          % (what, len(expected), n_pool))
    return {int(k): v for k, v in expected.items()}


# ------------------------------------------------------------- one run

def fallback_counts(win: scrape.Window, side: scrape.Window) -> dict:
    """What the server's and the sidecar's own counters saw answered by
    a fallback inside the window."""
    return {
        "ipt_fail_open_total": win.delta_unlabelled("ipt_fail_open_total"),
        "ipt_shed_total": win.delta("ipt_shed_total"),
        "ipt_degraded_verdicts_total":
            win.delta_unlabelled("ipt_degraded_verdicts_total"),
        "ipt_cpu_fallback_batches_total":
            win.delta_unlabelled("ipt_cpu_fallback_batches_total"),
        "ipt_breaker_trips_total":
            win.delta_unlabelled("ipt_breaker_trips_total"),
        "sidecar.fail_open_deadline": side.delta("sidecar.fail_open_deadline"),
        "sidecar.fail_open_upstream": side.delta("sidecar.fail_open_upstream"),
        "sidecar.fail_open_overload": side.delta("sidecar.fail_open_overload"),
        "sidecar.late_responses": side.delta("sidecar.late_responses"),
    }


def unflagged_fallbacks(fb: dict) -> int:
    """Verdicts a fallback produced that say nothing of it: the server
    counts a shed or failed-open verdict as degraded too, and those carry
    the fail-open flag; what is left was served prefilter-only (the
    brown-out ladder) or by the CPU-fallback path."""
    return int(max(0.0, fb["ipt_degraded_verdicts_total"]
                   - fb["ipt_fail_open_total"])
               + fb["ipt_cpu_fallback_batches_total"])


def count_failed(records: list, fb: dict) -> tuple:
    """(failed, parts): every request a fallback answered.  The client
    sees the fail-open flag; the counters see what it cannot (a degraded
    or CPU-fallback batch carries no flag)."""
    flagged = sum(1 for r in records if r.verdict is not None
                  and r.verdict["fail_open"])
    unanswered = sum(1 for r in records if r.verdict is None)
    doubled = sum(1 for r in records if r.doubled)
    counted = (fb["ipt_fail_open_total"] + fb["sidecar.fail_open_deadline"]
               + fb["sidecar.fail_open_upstream"]
               + fb["sidecar.fail_open_overload"])
    unseen = max(0.0, counted - flagged) + unflagged_fallbacks(fb)
    parts = {"flagged_fail_open": flagged, "unanswered": unanswered,
             "doubled": doubled, "seen_only_by_counters": int(unseen)}
    # an estimate from counters, so capped: never more than was sent
    return min(len(records), flagged + unanswered + doubled + int(unseen)), parts


def compare(records: list, expected: dict) -> dict:
    """Every verdict of the window that the device path answered, against
    the reference's verdict for its pool entry."""
    compared = mismatched = ref_attacks = 0
    examples = []
    for r in records:
        if reduce.is_failed(r):
            continue
        want = expected[r.pool_index]
        have = [r.verdict["attack"], r.verdict["blocked"],
                sorted(r.verdict["rule_ids"])]
        compared += 1
        ref_attacks += bool(want[0])
        if have != want:
            mismatched += 1
            if len(examples) < 5:
                examples.append({"pool_index": r.pool_index,
                                 "served": have, "reference": want})
    return {"compared": compared, "mismatched": mismatched,
            "reference_attacks": ref_attacks, "examples": examples}


def trace_summary(trace_dir: Path, out_dir: Path) -> dict:
    """Reduce the server's profiler trace in a child of its own (reading
    it needs JAX, which this parent never imports), pinned to CPU, after
    the server has released the chip."""
    log = out_dir / "xplane.log"
    out = out_dir / "trace_summary.json"
    r = subprocess.run(
        [sys.executable, str(BENCH / "harness" / "xplane.py"),
         str(trace_dir), str(out)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(BENCH),
        stdout=log.open("w"), stderr=subprocess.STDOUT, timeout=200)
    check(r.returncode == 0, "trace reduction failed\n%s" % launch.tail(log))
    return json.loads(out.read_text())


def profiler(server, srv_log: Path, sig: int, said: str) -> None:
    """Ask `harness/served.py` to start or stop its trace; wait for it."""
    n = srv_log.read_text(errors="replace").count(said)
    server.send_signal(sig)
    launch.wait_for(
        said, lambda: srv_log.read_text(errors="replace").count(said) > n,
        server, srv_log, timeout=240)


def start_system(spec: dict, out_dir: Path, sock_dir: Path, trace: bool,
                 procs: list) -> dict:
    """The server (the one process that holds the chip), then the sidecar
    in front of it.  Fails unless the server's device is a TPU."""
    config = spec["config"]
    srv_sock, side_sock = str(sock_dir / "srv.sock"), str(sock_dir / "side.sock")
    port, side_port = launch.free_port(), launch.free_port()
    srv_log, side_log = out_dir / "serve.log", out_dir / "sidecar.log"
    cmd = [sys.executable, str(REPO / config["server_entry"])]
    cmd += config["server_argv"]
    if REHEARSAL:
        cmd += config.get("rehearsal", {}).get("server_argv_extra", [])
    cmd += ["--socket", srv_sock, "--http-port", str(port)]
    env = launch.child_env(REPO, REHEARSAL)
    if trace:
        env["BENCH_TRACE_DIR"] = str(out_dir / "trace")
    server = subprocess.Popen(cmd, env=env, cwd=str(REPO),
                              stdout=subprocess.DEVNULL,
                              stderr=srv_log.open("w"))
    procs.append((server, "server", srv_log))
    device = launch.wait_for(
        "server device report", lambda: launch.device_line(srv_log),
        server, srv_log, timeout=300)
    say("server reports %s" % json.dumps(device))
    check(REHEARSAL or device["platform"] == "tpu",
          "no accelerator: the server runs on %s" % device)
    check(device["device_count"] >= spec["cell"]["chips"],
          "the cell needs %d chips, JAX reports %d"
          % (spec["cell"]["chips"], device["device_count"]))
    launch.wait_for("server socket", lambda: launch.sock_accepts(srv_sock),
                    server, srv_log, timeout=1000)
    ready_s = time.monotonic() - T_START
    for prefix in ("warmup:", "scan impl auto-select:", "lane serving:"):
        line = launch.log_line(srv_log, prefix)
        if line:
            say("server: %s%s" % (prefix, line))
    sidecar = subprocess.Popen(
        [str(REPO / "native/sidecar/sidecar"), "--listen", side_sock,
         "--upstream", srv_sock, "--status-port", str(side_port)]
        + config["sidecar_argv"],
        stdout=subprocess.DEVNULL, stderr=side_log.open("w"))
    procs.append((sidecar, "sidecar", side_log))
    launch.wait_for("sidecar socket", lambda: launch.sock_accepts(side_sock),
                    sidecar, side_log, timeout=30)
    return {"server": server, "sidecar": sidecar, "srv_log": srv_log,
            "side_log": side_log, "port": port, "side_port": side_port,
            "listen": side_sock, "device": device, "ready_s": ready_s}


def scrapes(system: dict) -> tuple:
    return (scrape.parse_metrics(
                launch.http_get(system["port"], "/metrics").decode()),
            scrape.parse_sidecar(
                launch.http_get(system["side_port"], "/").decode()))


def frame_source(traffic: dict, seed: int):
    """The pool from the seed (the reference children build the same),
    and the order it is sent in: a seeded shuffle, cycled."""
    n_pool = int(traffic["pool"])
    frames = build_pool(traffic, seed, n_pool)
    order = list(range(n_pool))
    random.Random(seed).shuffle(order)
    sent = 0

    def next_frame() -> tuple:
        nonlocal sent
        sent += 1
        idx = order[(sent - 1) % n_pool]
        return sent, idx, with_req_id(frames[idx], sent)

    return next_frame


class TracedSlice(threading.Thread):
    """The last `slice_s` seconds of a traced run's window: switches the
    server's profiler on while the loop goes on driving, and scrapes the
    counters once it is on, so that what the trace shows and what the
    counters count cover the same stretch."""

    def __init__(self, system: dict, delay_s: float):
        super().__init__(daemon=True)
        self.system, self.delay_s = system, delay_s
        self.scrape = self.t_on = self.error = None

    def run(self) -> None:
        try:
            time.sleep(self.delay_s)
            profiler(self.system["server"], self.system["srv_log"],
                     signal.SIGUSR1, "profiler: started")
            self.scrape, _ = scrapes(self.system)
            self.t_on = time.monotonic()
        except BaseException as e:         # handed to the main thread
            self.error = e


def drive(spec: dict, system: dict, next_frame, seconds: float,
          trace: bool) -> dict:
    """Lead-in, scrape, the window, scrape: what the run measures.  A
    traced run drives the same whole window; the profiler is on for its
    last `traced_slice_s` seconds only (a trace of the whole window is
    too large to write and read inside a run)."""
    traffic = spec["traffic"]
    loop = LOOPS[traffic["loop"]["kind"]]
    loop_kw = {k: v for k, v in traffic["loop"].items() if k != "kind"}
    lead, _, _ = loop(system["listen"], next_frame,
                      seconds=traffic["lead_in_s"], **loop_kw)
    check(all(r.verdict is not None for r in lead),
          "the lead-in lost verdicts")
    before, side_before = scrapes(system)
    server, srv_log = system["server"], system["srv_log"]
    traced = None
    if trace:
        slice_s = min(seconds, float(traffic["traced_slice_s"]))
        traced = TracedSlice(system, seconds - slice_s)
        traced.start()
    setup_s = time.monotonic() - T_START
    records, t_start, t_close = loop(system["listen"], next_frame,
                                     seconds=seconds, **loop_kw)
    t_end = time.monotonic()
    if traced:
        traced.join(timeout=300)
        check(traced.error is None and traced.scrape is not None,
              "the profiler did not start: %s" % (traced.error,))
        profiler(server, srv_log, signal.SIGUSR2, "profiler: stopped")
        say("server: profiler: %s; it was on for the window's last %.2fs"
            % (launch.log_line(srv_log, "profiler: "),
               t_close - traced.t_on))
    after, side_after = scrapes(system)
    _, health = launch.http_json(system["port"], "/healthz")
    say("window: %d requests sent in %.2fs, drained %.2fs after the close; "
        "set-up %.1fs" % (len(records), t_close - t_start, t_end - t_close,
                          setup_s))
    return {"records": records, "t_start": t_start, "t_close": t_close,
            "t_end": t_end, "setup_s": setup_s, "lead_in_requests": len(lead),
            "window": scrape.Window(before, after),
            "slice": scrape.Window(traced.scrape, after) if traced else None,
            "slice_s": t_end - traced.t_on if traced else None,
            "slice_t_on": traced.t_on if traced else None,
            "sidecar": scrape.Window(side_before, side_after),
            "scan_impl": health["robustness"]["device_path"]["scan_impl"]}


def judge(records: list, fb: dict, expected: dict, control) -> tuple:
    """(correct, checks, comparison): the numbers compared, each beside
    its limit.  Exact comparisons, so the limits are 0; a window with
    nothing compared proves nothing."""
    if control is not None:
        # the control in the program's place: its verdicts for the frames
        # the window sent, compared exactly as served verdicts are
        for r in records:
            if not reduce.is_failed(r):
                a, b, ids = control[r.pool_index]
                r.verdict = dict(r.verdict, attack=a, blocked=b, rule_ids=ids)
    cmp_ = compare(records, expected)
    # a degraded or CPU-fallback verdict carries no flag: the server
    # counts it, the client cannot tell which it was, so it is compared
    # like any other.  A window in which either counter moved did not
    # measure the device path: it is not correct, whatever its verdicts.
    unflagged = unflagged_fallbacks(fb)
    lost = sum(1 for r in records if r.verdict is None or r.doubled)
    checks = {
        "mismatched": {"value": cmp_["mismatched"], "limit": 0},
        "lost_or_doubled": {"value": lost, "limit": 0},
        "unflagged_fallbacks": {"value": unflagged, "limit": 0},
        "compared_min": {"value": cmp_["compared"], "limit": 1},
    }
    correct = all(c["value"] >= c["limit"] if name.endswith("_min")
                  else c["value"] <= c["limit"]
                  for name, c in checks.items())
    return correct, checks, cmp_


def run(args) -> dict:
    spec = resolve(Path(args.benchmark_file), args.workload)
    require_checkout()
    traffic = spec["traffic"]
    launch.build_native(REPO)
    out_dir = REPO / "benchmark_out" / args.workload / (
        "seed%d-trace%d" % (args.seed, args.trace))
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    # Unix socket paths are short: they live under TMPDIR, logs do not
    sock_dir = Path(tempfile.mkdtemp(prefix="bench_"))
    procs: list = []
    try:
        ref = start_reference(spec, args.seed, out_dir, "reference", "", procs)
        ctl = (start_reference(spec, args.seed, out_dir, "control",
                               json.dumps(traffic["control"]), procs)
               if args.control else None)
        system = start_system(spec, out_dir, sock_dir, bool(args.trace), procs)
        next_frame = frame_source(traffic, args.seed)
        # joined before the window: a walk still running would take the
        # server's cores; the wait shows if the pool outgrows the start-up
        t_wait = time.monotonic()
        n_pool = int(traffic["pool"])
        expected = join_reference(ref, n_pool, "reference")
        control = join_reference(ctl, n_pool, "control") if ctl else None
        ref_wait_s = time.monotonic() - t_wait
        got = drive(spec, system, next_frame, args.seconds, bool(args.trace))
        launch.stop(system["sidecar"], "sidecar", system["side_log"])
        exit_s = launch.stop(system["server"], "server", system["srv_log"],
                             timeout=200)
        check(system["server"].returncode == 0,
              "server exited %d on SIGTERM\n%s"
              % (system["server"].returncode,
                 launch.tail(system["srv_log"])))
        say("server ready at %.1fs, reference waited for %.1fs, server "
            "stopped in %.1fs; the chip is free"
            % (system["ready_s"], ref_wait_s, exit_s))
    except BaseException:
        for _proc, name, log in procs:
            print("---- %s log (tail)\n%s" % (name, launch.tail(log, 25)),
                  file=sys.stderr)
        raise
    finally:
        for proc, _name, _log in reversed(procs):
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(sock_dir, ignore_errors=True)

    records = got["records"]
    fb = fallback_counts(got["window"], got["sidecar"])
    failed, failed_parts = count_failed(records, fb)
    correct, checks, cmp_ = judge(records, fb, expected, None)
    program = None
    if control is not None:
        # a control run judges the program's own verdicts first (so its
        # seed counts for the program too), then the control in its place
        program = {"correct": correct, "checks": checks}
        correct, checks, cmp_ = judge(records, fb, expected, control)

    device = system["device"]
    mem = launch.log_line(system["srv_log"], "device_memory: ")
    mem_peak = json.loads(mem)["memory_peak_bytes"] if mem else None
    check(REHEARSAL or mem_peak, "the server reported no device memory peak")
    dev = {"platform": device["platform"], "kind": device["device_kind"],
           "count": device["device_count"], "memory_peak_bytes": mem_peak}

    e2e = reduce.end_to_end(records, got["t_start"], got["t_close"],
                            got["t_end"])
    e2e["setup_s"] = got["setup_s"]
    lat_ms = reduce.latencies_ms(records, got["t_end"])
    result = {"correct": correct, "attempted": len(records), "failed": failed}
    metrics = {}
    trace = None
    if not args.trace:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        if not REHEARSAL:
            trace = trace_summary(out_dir / "trace", out_dir)
            check(trace["busy_s"] > 0, "no operation ran on the device in "
                                       "the traced window")
            dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(out_dir / "trace", ignore_errors=True)
    # the readers cost nothing, so every run reads them into its detail
    # file; only a traced run reports them (and only it has a trace)
    ctx = {"window": got["window"], "slice": got["slice"],
           "slice_s": got["slice_s"], "slice_t_on": got["slice_t_on"],
           "sidecar": got["sidecar"],
           "records": records, "latencies_ms": lat_ms, "trace": trace,
           "config": spec["config"], "traffic": traffic, "device": dev,
           "seconds": got["t_close"] - got["t_start"]}
    layers = {}
    for name, (m, mod) in spec["readers"].items():
        value = mod.read(ctx)
        if value is not None:
            layers[name] = {"value": value, "unit": m["unit"]}
    if args.trace:
        metrics = layers
    per_second: dict = {}
    for r in records:
        if r.t_recv is not None:
            k = int(r.t_recv - got["t_start"])
            per_second[k] = per_second.get(k, 0) + 1
    # verdicts come back in bursts, one per dispatch: how large they are
    # says how the requests in flight have grouped themselves
    bursts: dict = {}
    size, last = 0, None
    for t in sorted(r.t_recv for r in records if r.t_recv is not None):
        if last is not None and t - last > 1e-3:
            bursts[size] = bursts.get(size, 0) + 1
            size = 0
        size, last = size + 1, t
    detail = {"fallback_counters": fb, "failed_parts": failed_parts,
              "comparison": cmp_, "end_to_end": e2e, "requests": len(records),
              "window_s": got["t_close"] - got["t_start"],
              "traced_slice_s": got["slice_s"],
              "drain_s": got["t_end"] - got["t_close"],
              "server_ready_s": system["ready_s"],
              "reference_wait_s": ref_wait_s, "server_exit_s": exit_s,
              "lead_in_requests": got["lead_in_requests"], "pool": n_pool,
              "seed": args.seed, "scan_impl": got["scan_impl"],
              "per_layer": {k: v["value"] for k, v in layers.items()},
              "verdicts_in_each_second": [per_second.get(k, 0) for k in
                                          range(max(per_second, default=-1) + 1)],
              "verdict_burst_sizes": dict(sorted(
                  bursts.items(), key=lambda kv: -kv[1])[:8])}
    (out_dir / "detail.json").write_text(json.dumps(detail, indent=1))
    (out_dir / "latencies_ms.json").write_text(json.dumps(lat_ms))
    say("detail: %s" % json.dumps(detail))
    if REHEARSAL:
        # a CPU timing is never written under a metric's name
        result["rehearsal_values"], metrics = metrics, {}
    if program is not None:
        result["program"] = program
        print("the program's own verdicts in this window: correct %s, %s"
              % (program["correct"], json.dumps(
                  {k: c["value"] for k, c in program["checks"].items()})),
              file=sys.stderr)
    result.update(metrics=metrics, device=dev, checks=checks)
    check("jax" not in sys.modules, "the parent imported JAX")
    print("compared with the plain reference%s:"
          % (" (CONTROL in the program's place)" if control is not None
             else ""), file=sys.stderr)
    print("  %d of the verdicts compared are attacks by the reference"
          % cmp_["reference_attacks"], file=sys.stderr)
    for name, c in checks.items():
        print("  %-22s %8d   limit %s %d"
              % (name, c["value"], ">=" if name.endswith("_min") else "<=",
                 c["limit"]), file=sys.stderr)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the traffic file's control in the "
                         "program's place (must come out not correct)")
    ap.add_argument("--benchmark-file", default=str(REPO / "BENCHMARK.json"))
    args = ap.parse_args()
    try:
        result = run(args)
    except (BenchFailure, subprocess.SubprocessError, OSError) as e:
        print("benchmark run failed: %s" % e, file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
