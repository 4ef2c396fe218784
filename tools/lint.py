"""Unified lint + static-analysis gate — the single CI entry point.

    python tools/lint.py           # run everything, report, exit status
    python tools/lint.py --ci      # same + write reports/RULECHECK.json

Four gates, one verdict:

  ruff       style/correctness lint per [tool.ruff] in pyproject.toml
             (zero-warning baseline: the selected rule set must be
             clean; new violations fail the gate)
  mypy       targeted type check of compiler/, analysis/, serve/ (+ the
             detection-telemetry modules) per [tool.mypy] in
             pyproject.toml
  rulecheck  the ruleset static analyzer (ingress_plus_tpu/analysis/,
             docs/ANALYSIS.md) over the bundled CRS tree: zero
             unsuppressed error-severity findings required
  concheck   the serve-plane CONCURRENCY static analyzer
             (docs/ANALYSIS.md "Concurrency analysis"): thread-boundary
             map, guarded-by inference + unguarded-mutation findings,
             lock-order cycles, thread-lifecycle lint — zero
             unsuppressed error-severity findings required
             (reports/CONCHECK.json)
  evasiongate the evasion-closure pair (docs/ANALYSIS.md "Evasion
             analysis"): evadecheck — the static analyzer deciding per
             rule whether detection is closed under the modeled evasion
             families — must have zero unsuppressed findings at warning
             or above (every accepted weakness carries a reason in
             analysis/evadecheck-baseline.json), AND the utils/evasion.py
             seeded mutation harness replaying the golden corpus through
             detect_cpu_only must retain >= 95% detection in EVERY
             mutation family (reports/EVASION.json)
  deadrules  the RUNTIME twin of rulecheck (docs/OBSERVABILITY.md,
             detection-plane telemetry): the bench corpus runs through
             a CPU pipeline and any runtime-dead rule (confirm regex
             the runtime cannot evaluate) not suppressed in
             rulecheck-baseline.json fails the gate
  faultmatrix the fail-safe serve plane (docs/ROBUSTNESS.md): a real
             CPU batcher runs under every deterministic FaultPlan
             scenario (dispatch_hang/raise, recompile_storm, swap_fail,
             export_5xx, slow_confirm, the rollout-phase faults
             shadow_diverge/lkg_corrupt/promote-boundary swap_fail,
             the lane/confirm-worker isolation scenarios, and the
             tenant-isolation floods tenant_flood /
             tenant_flood_during_canary) plus a synthetic overload
             burst; the invariant "every admitted request gets exactly
             one verdict, and no fault becomes an unhandled exception
             or a block" must hold, the breaker must trip and recover
  swapdrill  the guarded-rollout state machine (docs/ROBUSTNESS.md
             "Guarded rollout"): a known-good pack is driven through
             the full staged rollout to LIVE, a rulecheck-dirty pack
             (dead-regex fixture) to REJECTED with zero traffic
             impact, and a forced mid-canary failure auto-rolls back
             to the incumbent — exactly-one-verdict throughout
  modelgate  the learned scoring lane (docs/LEARNED_SCORING.md): a
             deterministic seeded retrain on the exported golden-corpus
             feature dataset must reproduce the artifact hash, replay
             with zero new false negatives vs the fixed CRS weights,
             and flag strictly fewer benign requests at the calibrated
             threshold (reports/MODELGATE.json)
  promlint   Prometheus exposition hygiene (analysis/promlint.py):
             /metrics scraped from an in-process server after real
             multi-tenant traffic — ipt_ prefix, _total on counters,
             HELP/TYPE pairs, bounded label cardinality (fails on the
             first unbounded per-rule/per-tenant series)
  retunegate profile-guided retuning loop (ISSUE 15, docs/RETUNE.md):
             a deterministic mini-retune on the bundled pack — profile
             built once from a bench-corpus telemetry replay, compiled
             twice (fingerprint must reproduce), zero lost candidates
             vs the exact compile, zero new false negatives on the
             golden replay, and the retuned pack's measured candidate
             load must not exceed the static pack's
             (reports/RETUNE.json)
  fleetgate  the fleet telemetry plane (ISSUE 18,
             docs/OBSERVABILITY.md "Fleet telemetry"): three
             in-process serve loops under replayed corpus traffic,
             one aggregator — counter conservation (fleet == Σ
             per-node == counted traffic, including with one node
             faulted stale mid-run via the scrape_5xx site),
             MeasuredProfile.merge content-hash reproducibility, and
             a promlint-clean aggregated /fleet/metrics exposition
             (reports/FLEETOBS.json)
  fleetdrill the fleet control plane (ISSUE 19, docs/SERVING.md
             "Fleet serving"): a 3-node in-process fleet behind the
             shared admission front — one node killed mid-wave with
             zero verdict loss, the good pack staged node-by-node to
             LIVE with the fleet LKG pointer written, the broken pack
             stopped at central admission, a mid-wave node death
             rolling the whole fleet back to LKG, and one forced
             retune-daemon cycle landing fleet-wide
             (reports/FLEETDRILL.json)
  benchtrend the checked-in BENCH_r*.json req/s/chip trajectory
             (tools/bench_trend.py): >10% regression vs the previous
             snapshot fails; SKIPPED with fewer than two artifacts

The container policy is "no new installs": when ruff or mypy are not
present, those gates report SKIPPED (recorded in the CI report so the
absence is auditable) instead of failing — rulecheck always runs, it
has no external dependency.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # script execution puts tools/ first
    sys.path.insert(0, str(REPO))
#: the mypy gate is TARGETED: the correctness-critical planes first;
#: widen as modules gain annotations (zero-warning baseline per scope).
#: ISSUE 11 widened models/ to the whole package (pipeline.py and every
#: tenant_guard caller) — ops/ stays out (device-kernel code).
MYPY_SCOPE = ["ingress_plus_tpu/compiler", "ingress_plus_tpu/analysis",
              "ingress_plus_tpu/serve",   # includes serve/lanes.py
              "ingress_plus_tpu/models",  # pipeline + tenant_guard callers
              "ingress_plus_tpu/post/topk.py",
              "ingress_plus_tpu/control/rollout.py",
              "ingress_plus_tpu/control/fleetobs.py",
              "ingress_plus_tpu/control/fleetctl.py",
              "ingress_plus_tpu/control/retuned.py",
              "ingress_plus_tpu/parallel/serve_mesh.py",
              "ingress_plus_tpu/learn",
              "ingress_plus_tpu/utils/promparse.py",
              "ingress_plus_tpu/utils/slo.py"]


def _tool_available(module: str, binary: str) -> bool:
    return importlib.util.find_spec(module) is not None or \
        shutil.which(binary) is not None


def _run_tool(module: str, binary: str, args: list) -> dict:
    """Run a lint tool as `python -m module` (preferred: pinned to this
    interpreter) or the bare binary; SKIPPED when neither exists."""
    if not _tool_available(module, binary):
        return {"status": "SKIPPED",
                "detail": "%s not installed in this environment "
                          "(no-install policy); gate not evaluated"
                          % binary}
    if importlib.util.find_spec(module) is not None:
        cmd = [sys.executable, "-m", module] + args
    else:
        cmd = [binary] + args
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    out = (proc.stdout + proc.stderr).strip()
    return {"status": "OK" if proc.returncode == 0 else "FAIL",
            "exit_code": proc.returncode,
            "seconds": round(time.time() - t0, 2),
            "detail": out[-4000:]}


def run_ruff() -> dict:
    return _run_tool("ruff", "ruff", ["check", "ingress_plus_tpu",
                                      "tools", "tests"])


def run_mypy() -> dict:
    return _run_tool("mypy", "mypy", MYPY_SCOPE)


def run_rulecheck(write_report: bool) -> dict:
    from ingress_plus_tpu.analysis import run_rulecheck as rc
    t0 = time.time()
    report = rc()
    gating = report.gating("error")
    result = {
        "status": "OK" if not gating else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "counts": report.counts(),
        "suppressed": sum(report.counts(suppressed=True).values()),
        "detail": "; ".join("%s %s (rule %s)" % (f.severity, f.check,
                                                 f.rule_id or f.subject)
                            for f in gating) or
                  "%d findings, 0 unsuppressed errors"
                  % len(report.findings),
    }
    if write_report:
        out = REPO / "reports" / "RULECHECK.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(report.to_json())
        result["report"] = str(out.relative_to(REPO))
    return result


def run_concheck_gate(write_report: bool) -> dict:
    """Concurrency static analysis of the serve-plane sources (ISSUE
    11, docs/ANALYSIS.md "Concurrency analysis"): zero unsuppressed
    error-severity findings — unguarded cross-thread mutations,
    live-view escapes, lock-order cycles, lifecycle lint."""
    from ingress_plus_tpu.analysis.concheck import run_concheck as cc
    t0 = time.time()
    report = cc()
    gating = report.gating("error")
    meta = report.meta or {}
    result = {
        "status": "OK" if not gating else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "counts": report.counts(),
        "suppressed": sum(report.counts(suppressed=True).values()),
        "functions": meta.get("functions"),
        "thread_roots": len(meta.get("thread_roots", ())),
        "lock_order_edges": len(meta.get("lock_order_edges", ())),
        "detail": "; ".join("%s %s (%s)" % (f.severity, f.check,
                                            f.subject)
                            for f in gating) or
                  "%d findings, 0 unsuppressed errors over %d functions"
                  % (len(report.findings), meta.get("functions", 0)),
    }
    if write_report:
        out = REPO / "reports" / "CONCHECK.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(report.to_json())
        result["report"] = str(out.relative_to(REPO))
    return result


#: per-family retention floor for the mutation harness (ISSUE 17): a
#: rule-pack or normalizer change that lets any modeled evasion family
#: strip >5% of detected attacks fails CI before it ships
EVASION_RETENTION_FLOOR = 0.95


def run_evasiongate(write_report: bool) -> dict:
    """Evasion-closure gate (ISSUE 17, docs/ANALYSIS.md "Evasion
    analysis"): the static evadecheck findings gate at WARNING (every
    accepted weakness must carry a reasoned baseline entry), and the
    seeded mutation harness must hold the per-family retention floor
    on the bundled pack.  The harness escapes feed back into the
    static report as corroboration, so a real runtime escape both
    drops retention and escalates its static finding to error."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.analysis import run_evadecheck as ec
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.utils.evasion import mutation_harness

    pipe = DetectionPipeline(compile_ruleset(load_bundled_rules()),
                             mode="monitoring")
    harness = mutation_harness(pipe)
    escapes = [e for fam in harness["families"].values()
               for e in fam["escapes"]]
    report = ec(escapes=escapes)
    gating = report.gating("warning")

    weak = {fam: st["retention"]
            for fam, st in harness["families"].items()
            if st["retention"] < EVASION_RETENTION_FLOOR}
    problems = ["%s %s (rule %s)" % (f.severity, f.check,
                                     f.rule_id or f.subject)
                for f in gating]
    problems += ["family %s retention %.3f < %.2f"
                 % (fam, r, EVASION_RETENTION_FLOOR)
                 for fam, r in sorted(weak.items())]
    result = {
        "status": "OK" if not problems else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "counts": report.counts(),
        "suppressed": sum(report.counts(suppressed=True).values()),
        "corroborated": (report.meta or {}).get("corroborated", 0),
        "min_retention": harness["min_retention"],
        "retention_floor": EVASION_RETENTION_FLOOR,
        "detail": "; ".join(problems) or
                  "%d findings all baselined, min retention %.3f over "
                  "%d families (%d base-detected attacks)"
                  % (len(report.findings), harness["min_retention"],
                     len(harness["families"]),
                     harness["corpus"]["base_detected"]),
    }
    if write_report:
        out = REPO / "reports" / "EVASION.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "static": json.loads(report.to_json()),
            "harness": harness,
            "retention_floor": EVASION_RETENTION_FLOOR,
        }
        out.write_text(json.dumps(payload, indent=2) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_dead_rules() -> dict:
    """Runtime dead-rule gate (ISSUE 3): compile the bundled pack,
    drive the bench corpus through a CPU pipeline, and fail on any
    runtime-dead or latent-dead rule (confirm regex the runtime cannot
    evaluate — the runtime twin of rulecheck's
    ``regex.confirm-unparsable``) that is not already suppressed in the
    CRS tree's rulecheck-baseline.json.  This is the dynamic
    counterpart of the rulecheck gate: a rule the static audit missed
    still fails CI the moment real traffic candidates it."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.analysis import BUNDLED_RULES
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.utils.corpus import generate_corpus

    cr = compile_ruleset(load_bundled_rules())
    pipe = DetectionPipeline(cr, mode="monitoring")
    reqs = [lr.request for lr in
            generate_corpus(n=256, attack_fraction=0.2, seed=42)]
    for i in range(0, len(reqs), 64):
        pipe.detect(reqs[i:i + 64])
    health = pipe.rule_stats.health()

    suppressed = set()
    baseline = BUNDLED_RULES / "rulecheck-baseline.json"
    if baseline.exists():
        spec = json.loads(baseline.read_text())
        for e in spec.get("suppressions", []):
            if e.get("check") in ("regex.confirm-unparsable",
                                  "runtime.dead-rule"):
                suppressed.add(e.get("rule_id"))
    dead = [d for d in health["runtime_dead"] + health["latent_dead"]
            if d["rule_id"] not in suppressed]
    return {
        "status": "FAIL" if dead else "OK",
        "seconds": round(time.time() - t0, 2),
        "requests": health["requests"],
        "detail": "; ".join(
            "rule %d dead at runtime (%s)" % (d["rule_id"], d["reason"])
            for d in dead) or
            "0 unsuppressed runtime-dead rules over %d corpus requests"
            % health["requests"],
    }


def run_faultmatrix(write_report: bool) -> dict:
    """Fail-safe serve-plane gate (docs/ROBUSTNESS.md): every fault
    scenario + the overload burst against a real CPU batcher; any
    invariant violation fails CI.

    Runs with InstrumentedLock debugging ON (docs/ANALYSIS.md
    "Concurrency analysis"): every batcher the 15 scenarios build gets
    order-asserting locks, so the fault matrix doubles as a race/
    deadlock stress harness — any lock-pair observed in both orders
    fails the gate."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.utils.faults import run_fault_matrix
    from ingress_plus_tpu.utils.trace import (
        debug_locks_enabled,
        enable_debug_locks,
        lock_registry,
    )

    lock_registry.reset()
    was_on = debug_locks_enabled()
    enable_debug_locks(True)
    try:
        report = run_fault_matrix()
    finally:
        enable_debug_locks(was_on)
    locks = lock_registry.snapshot()
    report["lock_order"] = locks
    lock_violations = locks["violation_count"]
    failed = {name: r["violations"]
              for name, r in report["scenarios"].items() if not r["ok"]}
    if lock_violations:
        failed["lock_order"] = ["%s <-> %s" % tuple(v["pair"])
                                for v in locks["violations"]]
    result = {
        "status": ("OK" if report["passed"] and not lock_violations
                   else "FAIL"),
        "seconds": round(time.time() - t0, 2),
        "scenarios": {name: r["ok"]
                      for name, r in report["scenarios"].items()},
        "lock_acquisitions": locks["acquisitions"],
        "lock_order_violations": lock_violations,
        "detail": "; ".join("%s: %s" % (n, "; ".join(v))
                            for n, v in failed.items()) or
                  "%d scenarios, invariant held under every fault; "
                  "%d instrumented lock acquisitions, 0 order "
                  "violations"
                  % (len(report["scenarios"]), locks["acquisitions"]),
    }
    if write_report:
        out = REPO / "reports" / "FAULTMATRIX.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2, default=str) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_swapdrill(write_report: bool) -> dict:
    """Guarded-rollout gate (ISSUE 5): the rollout state machine proven
    on a real CPU batcher — good pack to LIVE, dirty pack REJECTED with
    zero traffic impact, forced mid-canary failure ROLLED_BACK — with
    the exactly-one-verdict invariant held throughout."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.control.rollout import run_swap_drill

    report = run_swap_drill()
    failed = {name: r["violations"]
              for name, r in report["drills"].items()
              if "ok" in r and not r["ok"]}
    result = {
        "status": "OK" if report["passed"] else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "drills": {name: r["ok"] for name, r in report["drills"].items()
                   if "ok" in r},
        "detail": "; ".join("%s: %s" % (n, "; ".join(v))
                            for n, v in failed.items()) or
                  "good pack LIVE, dirty pack REJECTED, mid-canary "
                  "fault ROLLED_BACK — one verdict per request held",
    }
    if write_report:
        out = REPO / "reports" / "SWAPDRILL.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2, default=str) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_modelgate(write_report: bool) -> dict:
    """Learned-scorer gate (ISSUE 8, docs/LEARNED_SCORING.md): a
    deterministic seeded retrain on the exported golden-corpus feature
    dataset must (1) reproduce the artifact hash across two trains
    (determinism + hash stability), (2) replay with ZERO new false
    negatives vs the fixed CRS weights, and (3) flag strictly fewer
    benign requests at the calibrated threshold (the ModSec-Learn
    claim) — the comparison lands in reports/MODELGATE.json."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.learn.train import (
        compare_scorers, train_from_dataset)
    from ingress_plus_tpu.utils.export_corpus import build_feature_dataset

    ds = build_feature_dataset(n=1024, seed=20260729)
    head_a = train_from_dataset(ds)
    head_b = train_from_dataset(ds)
    violations = []
    if head_a.fingerprint() != head_b.fingerprint():
        violations.append(
            "retrain not deterministic: %s != %s"
            % (head_a.fingerprint(), head_b.fingerprint()))
    cmp = compare_scorers(ds, head_a)
    if cmp["new_fn_vs_fixed"] != 0:
        violations.append("learned head lost %d attack(s) the fixed "
                          "weights caught" % cmp["new_fn_vs_fixed"])
    if cmp["learned"]["fn"] > cmp["fixed"]["fn"]:
        violations.append("learned fn %d > fixed fn %d"
                          % (cmp["learned"]["fn"], cmp["fixed"]["fn"]))
    if cmp["fixed"]["fp"] == 0:
        violations.append(
            "fixed weights produced 0 benign flags on this corpus — "
            "the FP-reduction claim is unmeasurable (corpus drifted?)")
    elif cmp["learned"]["fp"] >= cmp["fixed"]["fp"]:
        violations.append("learned fp %d not strictly below fixed fp %d"
                          % (cmp["learned"]["fp"], cmp["fixed"]["fp"]))
    report = {
        "passed": not violations,
        "violations": violations,
        "dataset": {"fingerprint": ds.fingerprint(), "rows": ds.n,
                    "attacks": int(ds.y.sum()),
                    "ruleset": ds.meta.get("ruleset")},
        "artifact": {"version": head_a.version,
                     "threshold": round(float(head_a.threshold), 6),
                     "retrain_stable":
                         head_a.fingerprint() == head_b.fingerprint()},
        "comparison": cmp,
    }
    result = {
        "status": "OK" if report["passed"] else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "detail": "; ".join(violations) or
                  "retrain stable (%s); fixed fp=%d -> learned fp=%d at "
                  "zero new FNs over %d rows"
                  % (head_a.version, cmp["fixed"]["fp"],
                     cmp["learned"]["fp"], ds.n),
    }
    if write_report:
        out = REPO / "reports" / "MODELGATE.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2, default=str) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_promlint() -> dict:
    """Prometheus exposition hygiene gate (ISSUE 12 satellite,
    analysis/promlint.py): scrape /metrics from an IN-PROCESS serve
    loop after real multi-tenant traffic — naming (ipt_ prefix, _total
    on counters), HELP/TYPE pairs, bounded label cardinality
    (bounded_counter_series respected), histogram shape.  Fails on the
    first unbounded per-rule or per-tenant series that slips into the
    text exposition."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.analysis.promlint import check_exposition
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.serve.server import ServeLoop
    from ingress_plus_tpu.utils.corpus import generate_corpus

    cr = compile_ruleset(load_bundled_rules())
    pipe = DetectionPipeline(cr, mode="monitoring")
    batcher = Batcher(pipe, max_batch=32)
    try:
        # multi-tenant traffic so the per-tenant/per-family folds are
        # EXERCISED, not vacuously bounded: 48 distinct tenants is past
        # the 30-series budget, so the "other" fold must engage
        reqs = [lr.request for lr in
                generate_corpus(n=96, attack_fraction=0.3, seed=7)]
        for i, r in enumerate(reqs):
            r.tenant = i % 48
        futs = [batcher.submit(r) for r in reqs]
        for f in futs:
            f.result(timeout=120)
        serve = ServeLoop(batcher, socket_path="/tmp/ipt-promlint.sock")
        text = serve._metrics_text()
    finally:
        batcher.close()
    findings = check_exposition(text)
    return {
        "status": "FAIL" if findings else "OK",
        "seconds": round(time.time() - t0, 2),
        "series_lines": sum(1 for ln in text.splitlines()
                            if ln and not ln.startswith("#")),
        "detail": "; ".join(findings[:20]) or
        "exposition clean: %d series lines, every TYPE has HELP, all "
        "label sets bounded"
        % sum(1 for ln in text.splitlines()
              if ln and not ln.startswith("#")),
    }


def run_fleetgate(write_report: bool) -> dict:
    """Fleet telemetry gate (ISSUE 18, control/fleetobs.py): three
    IN-PROCESS serve loops, replayed corpus traffic, one aggregator.
    Asserts the fleet plane's three contracts: (1) counter
    conservation — the aggregated ipt_requests_total equals the sum of
    per-node counters equals the independently counted traffic, and
    keeps holding over the reachable subset when a node is faulted
    stale mid-run (scrape_5xx site); (2) merge determinism —
    MeasuredProfile.merge over the scraped per-node profiles
    reproduces the same content hash twice, argument order shuffled;
    (3) the aggregated /fleet/metrics exposition passes promlint in
    fleet mode.  Writes reports/FLEETOBS.json."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.analysis.promlint import check_exposition
    from ingress_plus_tpu.compiler.profile import MeasuredProfile
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.control.fleetobs import (
        FleetObserver,
        serve_loop_transport,
    )
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.serve.server import ServeLoop
    from ingress_plus_tpu.utils import faults
    from ingress_plus_tpu.utils.corpus import generate_corpus

    n_nodes = 3
    checks: dict = {}
    failures: list = []
    cr = compile_ruleset(load_bundled_rules())
    batchers = [Batcher(DetectionPipeline(cr, mode="monitoring"),
                        max_batch=16) for _ in range(n_nodes)]
    saved_plan = faults.active()
    try:
        serves = [ServeLoop(b, socket_path="/tmp/ipt-fleetgate-%d.sock"
                            % i) for i, b in enumerate(batchers)]
        obs = FleetObserver()
        for i, s in enumerate(serves):
            obs.add_node("n%d" % i, transport=serve_loop_transport(s))

        def wave(seed: int, per_node: int = 32) -> int:
            futs = []
            for i, b in enumerate(batchers):
                reqs = [lr.request for lr in generate_corpus(
                    n=per_node, attack_fraction=0.25,
                    seed=seed * 10 + i)]
                for j, r in enumerate(reqs):
                    r.tenant = j % 8
                futs += [b.submit(r) for r in reqs]
            for f in futs:
                f.result(timeout=120)
            return len(futs)

        # leg 1: full fleet conservation
        sent = wave(1)
        obs.scrape()
        counters, per_node = obs.counters_snapshot()
        fleet_req = counters.get("ipt_requests_total")
        node_sum = sum(per_node.get("ipt_requests_total", {}).values())
        checks["conservation_full"] = {
            "submitted": sent, "fleet": fleet_req, "node_sum": node_sum,
            "ok": fleet_req == node_sum == float(sent)}
        if not checks["conservation_full"]["ok"]:
            failures.append("conservation (full fleet): fleet=%s "
                            "node_sum=%s submitted=%d"
                            % (fleet_req, node_sum, sent))

        # leg 2: aggregated exposition is promlint-clean (fleet mode)
        findings = check_exposition(obs.fleet_metrics(), fleet=True)
        checks["promlint_fleet"] = {"findings": findings[:10],
                                    "ok": not findings}
        if findings:
            failures.append("aggregate exposition: %s"
                            % "; ".join(findings[:5]))

        # leg 3: merge determinism (same inputs, shuffled order,
        # twice -> same canonical bytes, same content hash)
        profs = [n.profile for n in obs.nodes if n.profile is not None]
        h1 = MeasuredProfile.merge(profs).content_hash()
        h2 = MeasuredProfile.merge(list(reversed(profs))).content_hash()
        checks["merge_determinism"] = {
            "hash_1": h1, "hash_2": h2,
            "profiles": len(profs), "ok": h1 == h2 and len(profs) == 3}
        if not checks["merge_determinism"]["ok"]:
            failures.append("profile merge not deterministic: %s vs %s"
                            % (h1, h2))

        # leg 4: one node faulted stale mid-run — conservation must
        # hold over the reachable subset, stale node out of rollups
        faults.install(faults.FaultPlan.from_spec("scrape_5xx:times=1"))
        sent += wave(2)
        health = obs.scrape()
        counters, per_node = obs.counters_snapshot()
        reach = {k: v for k, v in
                 per_node.get("ipt_requests_total", {}).items()}
        checks["conservation_faulted"] = {
            "nodes_up": health["nodes_up"],
            "nodes_stale": health["nodes_stale"],
            "fleet": counters.get("ipt_requests_total"),
            "reachable_sum": sum(reach.values()),
            "stale_excluded": "n0" not in reach,
            "ok": (health["nodes_up"] == n_nodes - 1
                   and health["nodes_stale"] == 1
                   and "n0" not in reach
                   and counters.get("ipt_requests_total")
                   == sum(reach.values()))}
        if not checks["conservation_faulted"]["ok"]:
            failures.append("conservation (faulted): %r"
                            % checks["conservation_faulted"])

        # leg 5: recovery — plan exhausted, full fleet again
        faults.clear()
        health = obs.scrape()
        counters, _pn = obs.counters_snapshot()
        checks["recovery"] = {
            "nodes_up": health["nodes_up"],
            "fleet": counters.get("ipt_requests_total"),
            "ok": (health["nodes_up"] == n_nodes
                   and counters.get("ipt_requests_total")
                   == float(sent))}
        if not checks["recovery"]["ok"]:
            failures.append("recovery: %r" % checks["recovery"])
    finally:
        faults.install(saved_plan)
        for b in batchers:
            b.close()

    report = {"nodes": n_nodes, "checks": checks,
              "skew_findings": health.get("skew_findings", []),
              "passed": not failures}
    result = {
        "status": "FAIL" if failures else "OK",
        "seconds": round(time.time() - t0, 2),
        "detail": "; ".join(failures[:5]) or
        "conservation holds (full + 1-node-stale + recovery), merge "
        "hash %s reproduced, aggregate exposition clean"
        % checks["merge_determinism"]["hash_1"],
    }
    if write_report:
        out = REPO / "reports" / "FLEETOBS.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_retunegate(write_report: bool) -> dict:
    """Profile-guided retuning gate (ISSUE 15, docs/RETUNE.md): a
    deterministic mini-retune on the bundled pack.  The profile is
    built ONCE from a bench-corpus telemetry replay (profile TIMINGS
    are measurements and legitimately differ between replays — the
    determinism contract is same profile BYTES → same pack), then the
    compiler runs twice from those bytes and must (1) reproduce the
    pack fingerprint, (2) lose ZERO candidates vs the exact compile,
    (3) replay the golden corpus with ZERO new false negatives vs the
    static-model pack, and (4) not exceed the static pack's measured
    candidate load (the deterministic throughput proxy — fewer
    candidates IS the mechanism of the confirm-stage win)."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    sys.path.insert(0, str(REPO / "tools"))
    import retune as rt

    from ingress_plus_tpu.compiler.profile import MeasuredProfile
    from ingress_plus_tpu.compiler.reduce import (
        ReductionConfig,
        measure_inflation,
    )
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.normalize import merge_rows, \
        rows_for_requests

    rules = rt._load_rules()
    prof = rt.build_profile(rules, corpus_n=192, seed=42)
    prof_bytes = prof.to_json()

    cr_a = compile_ruleset(rules, reduction=ReductionConfig(profile=prof))
    cr_b = compile_ruleset(rules, reduction=ReductionConfig(
        profile=MeasuredProfile.from_json(prof_bytes)))
    static_cr = compile_ruleset(rules)
    exact_cr = compile_ruleset(rules, reduction=ReductionConfig.off())

    rows = merge_rows(rows_for_requests(rt._corpus(192, 43)))[0]
    infl_static = measure_inflation(exact_cr.tables, static_cr.tables,
                                    rows)
    infl = measure_inflation(exact_cr.tables, cr_a.tables, rows)

    replay = rt._replay_fns(DetectionPipeline(static_cr, mode="detect"),
                            DetectionPipeline(cr_a, mode="detect"),
                            rt._corpus(192, 20260804,
                                       attack_fraction=0.5))

    checks = {
        "fingerprint_stable": cr_a.version == cr_b.version,
        "zero_lost_candidates": infl["lost_candidates"] == 0,
        "zero_new_fns": replay["new_fns"] == 0,
        "candidate_load_not_worse":
            infl["candidates_reduced"]
            <= infl_static["candidates_reduced"],
    }
    report = {
        "profile_hash": prof.content_hash(),
        "profile_rules": len(prof.rules),
        "static_fingerprint": static_cr.version,
        "retuned_fingerprint": cr_a.version,
        "retrain_fingerprint": cr_b.version,
        "inflation": {"static": infl_static, "retuned": infl},
        "replay": replay,
        "reduction": cr_a.reduction,
        "checks": checks,
        "passed": all(checks.values()),
    }
    failed = [k for k, ok in checks.items() if not ok]
    result = {
        "status": "OK" if report["passed"] else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "detail": ("; ".join(failed) if failed else
                   "profile %s -> pack %s reproducible, lost=0, "
                   "new_fns=0, candidates %d <= static %d"
                   % (report["profile_hash"], cr_a.version,
                      infl["candidates_reduced"],
                      infl_static["candidates_reduced"])),
    }
    if write_report:
        out = REPO / "reports" / "RETUNE.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2, default=str) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def run_benchtrend() -> dict:
    """Bench trajectory gate (ISSUE 12 satellite, tools/bench_trend.py):
    the latest checked-in BENCH_r*.json must not regress >10% vs the
    previous snapshot.  SKIPPED cleanly when fewer than two artifacts
    exist (a fresh tree has nothing to compare)."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_trend.py"),
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        return {"status": "FAIL", "seconds": round(time.time() - t0, 2),
                "detail": "bench_trend emitted no JSON (rc=%d): %s"
                          % (proc.returncode,
                             (proc.stderr or proc.stdout)[-300:])}
    status = report.get("status", "FAIL")
    return {
        "status": {"OK": "OK", "SKIP": "SKIPPED"}.get(status, "FAIL"),
        "seconds": round(time.time() - t0, 2),
        "latest": report.get("latest"),
        "latest_value": report.get("latest_value"),
        "delta_vs_prev": report.get("delta_vs_prev"),
        "detail": report.get("detail", ""),
    }


def run_fleetdrill(write_report: bool) -> dict:
    """Fleet control-plane gate (ISSUE 19, control/fleetctl.py): the
    whole fleet choreography proven in one process — a 3-node front
    wave with one node killed mid-send (zero verdict loss), the good
    candidate promoted node by node to LIVE with the fleet LKG written,
    the broken pack stopped at central admission, a mid-wave node
    failure rolling the WHOLE fleet back to LKG, and one forced
    retune-daemon cycle end to end (profile → four gates →
    fleet-staged rollout).  Writes reports/FLEETDRILL.json."""
    t0 = time.time()
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    from ingress_plus_tpu.control.fleetctl import run_fleet_drill

    report = run_fleet_drill()
    failed = {name: leg for name, leg in report["legs"].items()
              if not leg["ok"]}
    result = {
        "status": "OK" if report["passed"] else "FAIL",
        "seconds": round(time.time() - t0, 2),
        "legs": {name: leg["ok"] for name, leg in report["legs"].items()},
        "detail": "; ".join("%s: %s" % (n, leg.get("violations")
                                        or leg.get("reason")
                                        or leg.get("result"))
                            for n, leg in failed.items()) or
                  "front kill zero-loss, fleet LIVE + LKG, bad pack "
                  "stopped, mid-wave death rolled the fleet back, "
                  "daemon cycle to LIVE",
    }
    if write_report:
        out = REPO / "reports" / "FLEETDRILL.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2, default=str) + "\n")
        result["report"] = str(out.relative_to(REPO))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools/lint.py")
    ap.add_argument("--ci", action="store_true",
                    help="CI mode: also write reports/RULECHECK.json")
    ap.add_argument("--only",
                    choices=["ruff", "mypy", "rulecheck", "concheck",
                             "evasiongate", "deadrules", "faultmatrix",
                             "swapdrill", "modelgate",
                             "promlint", "benchtrend", "retunegate",
                             "fleetgate", "fleetdrill"],
                    default=None)
    args = ap.parse_args(argv)

    gates = {}
    if args.only in (None, "ruff"):
        gates["ruff"] = run_ruff()
    if args.only in (None, "mypy"):
        gates["mypy"] = run_mypy()
    if args.only in (None, "rulecheck"):
        gates["rulecheck"] = run_rulecheck(write_report=args.ci)
    if args.only in (None, "concheck"):
        gates["concheck"] = run_concheck_gate(write_report=args.ci)
    if args.only in (None, "evasiongate"):
        gates["evasiongate"] = run_evasiongate(write_report=args.ci)
    if args.only in (None, "deadrules"):
        gates["deadrules"] = run_dead_rules()
    if args.only in (None, "faultmatrix"):
        gates["faultmatrix"] = run_faultmatrix(write_report=args.ci)
    if args.only in (None, "swapdrill"):
        gates["swapdrill"] = run_swapdrill(write_report=args.ci)
    if args.only in (None, "modelgate"):
        gates["modelgate"] = run_modelgate(write_report=args.ci)
    if args.only in (None, "promlint"):
        gates["promlint"] = run_promlint()
    if args.only in (None, "retunegate"):
        gates["retunegate"] = run_retunegate(write_report=args.ci)
    if args.only in (None, "fleetgate"):
        gates["fleetgate"] = run_fleetgate(write_report=args.ci)
    if args.only in (None, "fleetdrill"):
        gates["fleetdrill"] = run_fleetdrill(write_report=args.ci)
    if args.only in (None, "benchtrend"):
        gates["benchtrend"] = run_benchtrend()

    failed = False
    for name, r in gates.items():
        print("%-10s %-8s %s" % (name, r["status"],
                                 r.get("detail", "").splitlines()[0]
                                 if r.get("detail") else ""))
        if r["status"] == "FAIL":
            failed = True
            detail = r.get("detail", "")
            if detail:
                print("  " + "\n  ".join(detail.splitlines()[:40]))
    if args.ci:
        summary = REPO / "reports" / "LINT.json"
        summary.parent.mkdir(parents=True, exist_ok=True)
        # persist without per-run wall-clock noise: the checked-in
        # summary should only diff when a gate's outcome changes
        stable = {name: {k: v for k, v in r.items() if k != "seconds"}
                  for name, r in gates.items()}
        summary.write_text(json.dumps(stable, indent=2) + "\n")
        print("gate summary -> %s" % summary.relative_to(REPO))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
