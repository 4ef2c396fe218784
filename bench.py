"""Round benchmark — prints ONE JSON line (driver contract).

Headline metric (BASELINE.md north star): requests/second/chip running the
full bundled CRS-v3-shaped ruleset (~1.4k rules) over a realistic labeled
request corpus.  The measured program is the complete TPU detection step —
normalization rows scanned by the bitap engine + factor→rule→class verdict
heads — exactly what replaces the reference's in-process libproton call.

Timing method: K state-chained repetitions of the batch inside ONE jit
dispatch, reported as the K-difference (see utils/microbench.py), so
per-dispatch overhead cancels.  vs_baseline is value / 100_000 (the
north-star target; the reference publishes no numbers — BASELINE.json
"published": {}).

The default run measures the accelerator JAX selects and FAILS (non-zero
exit, no JSON) when there is none; ``BENCH_PLATFORM=cpu`` is the explicit
CPU run tests use.  Every result names platform, device_kind and device
count.  Secondary diagnostics go to stderr; stdout carries only the JSON
line.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _require_unheld_chip(leg: str) -> None:
    """Legs that start JAX child processes are CPU-pinned by design and
    must not run under a parent that has initialised an accelerator
    backend: that parent holds the chip."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() != "cpu":
        raise RuntimeError(
            "%s starts JAX child processes; run it as its own command, "
            "not from a process that holds the %s backend"
            % (leg, jax.default_backend()))


def _widen_k(timed, d_lo: float, d_hi: float, it: int, tag: str,
             budget_frac: float = 0.5, cap: int = 2048):
    """Grow K 4x at a time until the K-diff clears dispatch jitter
    (0.2s) or the budget share runs out — the ONE widening loop shared
    by the live-pack and fixed-pack legs (review finding: two
    hand-synced copies).  The guard uses the measured MARGINAL cost,
    not d_lo, which includes the per-dispatch overhead and would block
    widening too early.  Returns (d_hi, it)."""
    marginal = max((d_hi - d_lo) / (it - 1), 1e-6)
    while (d_hi - d_lo < 0.2 and it < cap
           and 4 * d_lo + 16 * it * marginal
           < _budget_left() * budget_frac):
        it *= 4
        log("[%s] widening K to %d (diff %.1f ms too small)"
            % (tag, it, (d_hi - d_lo) * 1e3))
        d_hi = timed(it)
        marginal = max((d_hi - d_lo) / (it - 1), 1e-6)
    return d_hi, it


def load_fixed_pack():
    """The FROZEN round-3 rule pack (VERDICT r04 item #3): the r03 conf
    tree plus the r03 sigpack generator, both committed verbatim under
    ``bench_fixtures/pack_r03/`` at commit 3c10aaf's content.  Compiles
    to exactly the pack BENCH_r03 measured — 1405 rules / 1233 factors /
    343 scan words — so a throughput number on it is comparable across
    rounds regardless of how the live pack grows (r04's 2.4x CPU drop
    was unattributable because only the current pack was measured).

    Compiled with ``ReductionConfig.off()``: the frozen leg must keep
    producing the BIT-IDENTICAL legacy tables r03 measured — the
    approximate reduction (compiler/reduce.py) applies to the live pack
    only, so the fixed leg keeps isolating code drift from pack size."""
    import importlib.util

    from ingress_plus_tpu.compiler.reduce import ReductionConfig
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import load_seclang_dir

    fix = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "bench_fixtures", "pack_r03")
    spec = importlib.util.spec_from_file_location(
        "bench_sigpack_r03", os.path.join(fix, "sigpack_r03.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rules = load_seclang_dir(os.path.join(fix, "crs"))
    return compile_ruleset(rules + mod.generate_signature_rules(),
                           reduction=ReductionConfig.off())


#: BENCH_r03.json's measured CPU anchor on this frozen pack (scan_impl
#: pair, 2048-req corpus) — the cross-round comparison point
R03_REFERENCE = {"req_per_s": 5013.3, "platform": "cpu",
                 "scan_impl": "pair"}


def bucket_rows_np(data_list, req_list, sv_list, n_sv, edges):
    """The ONE L-tier bucket/pad/row_sv assembly (numpy) shared by the
    live-pack, fixed-pack and PACKSCALE legs — mirrors
    DetectionPipeline.prefilter's bucketing so every leg measures the
    geometry the serving path actually dispatches (review finding:
    hand-synced copies of this drifted between legs once already)."""
    from ingress_plus_tpu.ops.scan import pad_rows

    bks: dict = {}
    for i, d in enumerate(data_list):
        for edge in edges:
            if len(d) <= edge or edge == edges[-1]:
                bks.setdefault(edge, []).append(i)
                break
    out = []
    for edge, idxs in sorted(bks.items()):
        rws = [data_list[i][:edge] for i in idxs]
        tokens, lengths = pad_rows(rws, max_len=edge, round_to=edge)
        row_sv = np.zeros((len(rws), n_sv), np.int8)
        for j, i in enumerate(idxs):
            row_sv[j, sv_list[i]] = 1
        out.append((edge, tokens, lengths,
                    np.asarray([req_list[i] for i in idxs], np.int32),
                    row_sv))
    return out


def fused_map_fold(tabs, matches, bufs, n_req: int):
    """Concatenate per-bucket sticky match words and run the
    factor→rule mapping ONCE — the shared core of every detect_k
    variant (docs/SCAN_KERNEL.md single-mapping contract; review
    finding: three near-copies of this fold risked drifting from the
    serving path).  Traced inside jit."""
    import jax.numpy as jnp

    from ingress_plus_tpu.models.engine import map_match_words

    rule_hits, _, _ = map_match_words(
        tabs, jnp.concatenate(matches, axis=0),
        jnp.concatenate([b[2] for b in bufs]),
        jnp.concatenate([b[3] for b in bufs]), n_req)
    return rule_hits


def run_pack_scale(scales=(0.5, 1.0, 1.5, 2.0), n_req: int = 1024,
                   out_path: str | None = None) -> dict:
    """PACKSCALE leg: compile synthetic packs at multiples of the
    bundled CRS-shaped ruleset (compiler/packgen.py growth model),
    measure fused-pair detect throughput per point, and write
    reports/PACKSCALE.json.  The 2x point is the pack-size-invariance
    gate: with interning + shared-prefix merging + budgeted reduction
    (docs/SCAN_KERNEL.md), 2x rules must cost < 1.5x throughput — a
    superlinear curve is warned about LOUDLY, never silently recorded.

    Per point the candidate inflation of the reduced tables over an
    exact compile is MEASURED on a corpus row sample (the budget is a
    model; the measurement is the truth the acceptance gate reads)."""
    import jax
    import jax.numpy as jnp

    from ingress_plus_tpu.compiler.packgen import scale_rules
    from ingress_plus_tpu.compiler.reduce import (
        ReductionConfig,
        measure_inflation,
    )
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.engine import EngineTables
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.ops.scan import scan_pairs
    from ingress_plus_tpu.serve.normalize import merge_rows, rows_for_requests
    from ingress_plus_tpu.utils.corpus import generate_corpus
    from ingress_plus_tpu.utils.microbench import best_time

    base = load_bundled_rules()
    corpus = generate_corpus(n=n_req, attack_fraction=0.2, seed=42)
    requests = [lr.request for lr in corpus]

    @functools.partial(jax.jit, static_argnames=("k",))
    def detect_k(k: int, tabs, bufs):
        W = tabs.scan.n_words

        def body(i, carry):
            acc, states = carry
            matches = []
            for (tok, lens, rreq, rsv), match in zip(bufs, states):
                match, _ = scan_pairs(tabs.scan, tok, lens, None, match)
                matches.append(match)
                acc = acc + match.sum()
            rule_hits = fused_map_fold(tabs, matches, bufs, n_req)
            return (acc + rule_hits.sum().astype(jnp.uint32),
                    tuple(matches))

        states = tuple(jnp.zeros((b[0].shape[0], W), jnp.uint32)
                       for b in bufs)
        acc, _ = jax.lax.fori_loop(
            0, k, body, (jnp.zeros((), jnp.uint32), states))
        return acc

    points = []
    sample_rows = None
    for scale in scales:
        if _budget_left() < 60:
            log("PACKSCALE: %.0fs budget left — stopping before %sx"
                % (_budget_left(), scale))
            break
        t0 = time.time()
        rules_s = scale_rules(base, scale)
        cr = compile_ruleset(rules_s)
        cr_exact = compile_ruleset(
            rules_s, reduction=ReductionConfig.off())
        pipe = DetectionPipeline(cr)
        rows = rows_for_requests(requests, needed_sv=pipe.needed_sv)
        data_list, req_list, sv_list = merge_rows(rows)
        if sample_rows is None:
            sample_rows = data_list[:512]
        infl = measure_inflation(cr_exact.tables, cr.tables, sample_rows)
        # close the provenance loop (ISSUE 15): the artifact's own
        # reduction block carries the MEASURED inflation next to the
        # modeled spend, so rulecheck/retune never read a None where a
        # measurement exists
        if cr.reduction is not None:
            cr.reduction["measured_inflation"] = infl["inflation"]
        n_sv = cr.rule_sv_mask.shape[1]
        bufs = tuple(
            (jax.device_put(tokens),   # uint8: raw-byte contract
             jax.device_put(lengths), jax.device_put(rreq),
             jax.device_put(row_sv))
            for _edge, tokens, lengths, rreq, row_sv in bucket_rows_np(
                data_list, req_list, sv_list, n_sv,
                DetectionPipeline.L_BUCKETS))
        tables = EngineTables.from_ruleset(cr)

        def timed(kk: int) -> float:
            return best_time(
                lambda k2, rep: detect_k(k2, tables, bufs), kk, n=4)

        # the 2x sublinearity gate sits near 1.5x, so each point needs a
        # LOW-variance estimate: best-of-4 and a K-diff of at least ~1s
        # of pure compute before we accept the number (run-to-run noise
        # on a busy 1-core host flipped the gate at a 0.2s target)
        d_lo = timed(1)
        it = max(5, min(65, int(max(15.0, _budget_left() * 0.12)
                                / (5 * max(d_lo, 1e-4)))))
        d_hi = timed(it)
        while (d_hi - d_lo < 1.0 and it < 257
               and 5 * (d_lo + it * max((d_hi - d_lo) / (it - 1), 1e-6))
               < _budget_left() * 0.3):
            it *= 2
            log("[packscale-%sx] widening K to %d (diff %.0f ms)"
                % (scale, it, (d_hi - d_lo) * 1e3))
            d_hi = timed(it)
        delta = d_hi - d_lo
        rps = n_req / (delta / (it - 1)) if delta > 0.05 else None
        point = {
            "scale": scale,
            "rules": int(cr.n_rules),
            "factors": int(cr.tables.n_factors),
            "words": int(cr.tables.n_words),
            "head_words": int(cr.tables.n_head_words),
            "factors_exact": int(cr_exact.tables.n_factors),
            "words_exact": int(cr_exact.tables.n_words),
            "req_per_s": round(rps, 1) if rps else None,
            "candidate_inflation": infl,
            "reduction": cr.reduction,
            "compile_s": round(time.time() - t0, 1),
        }
        points.append(point)
        log("PACKSCALE %.1fx: %d rules -> %d words (%d exact), "
            "%s req/s, inflation %s, lost=%d"
            % (scale, point["rules"], point["words"], point["words_exact"],
               point["req_per_s"], infl["inflation"],
               infl["lost_candidates"]))
        if infl["lost_candidates"]:
            log("PACKSCALE ERROR: reduced pack LOST %d candidates at "
                "%.1fx — the reduction is UNSOUND, fix before shipping"
                % (infl["lost_candidates"], scale))
        budget = (cr.reduction or {}).get("budget", 0.0)
        if budget and infl["inflation"] > budget:
            log("=" * 64)
            log("PACKSCALE WARNING: measured inflation %.3f at %.1fx "
                "EXCEEDS the configured budget %.2f (modeled spend "
                "%.3f) — the byte-frequency model underprices this "
                "corpus; feed a MeasuredProfile to the compiler "
                "(tools/retune.py) or lower the budget"
                % (infl["inflation"], scale, budget,
                   (cr.reduction or {}).get("spent", 0.0)))
            log("=" * 64)

    result = {"metric": "req/s vs pack scale (fused pair detect step, "
                        "%d-req corpus, CPU-or-live backend)" % n_req,
              # per-leg backend tag (ISSUE 13 satellite): numbers from
              # different backends must never be compared as a trend
              "platform": jax.default_backend(),
              "points": points}
    one = next((p for p in points if p["scale"] == 1.0
                and p["req_per_s"]), None)
    two = next((p for p in points if p["scale"] == 2.0
                and p["req_per_s"]), None)
    if one and two:
        slowdown = one["req_per_s"] / two["req_per_s"]
        result["scale_2x"] = {
            "rules_ratio": round(two["rules"] / one["rules"], 3),
            "slowdown": round(slowdown, 3),
            "sublinear": slowdown < 1.5,
        }
        if slowdown >= 1.5:
            log("=" * 64)
            log("PACKSCALE WARNING: SUPERLINEAR SCALING — 2x rules cost "
                "%.2fx throughput (gate: < 1.5x).  The pack-size-"
                "invariance claim does NOT hold on this build/host."
                % slowdown)
            log("=" * 64)
        else:
            log("PACKSCALE: 2x rules -> %.2fx slowdown (sublinear, "
                "gate < 1.5x)" % slowdown)
    else:
        log("PACKSCALE WARNING: missing 1x/2x points — the scaling "
            "curve is INCOMPLETE this round (budget or signal loss); "
            "the sublinearity gate was NOT evaluated")
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reports", "PACKSCALE.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        log("PACKSCALE written to %s" % out_path)
    except OSError as e:
        log("PACKSCALE write failed (non-fatal): %r" % (e,))
    return result


def mesh_point_main(n_devices: int) -> None:
    """Subprocess entry for one mesh-scale point (``--mesh-point=K``):
    pin K virtual CPU devices (the device count is fixed at backend
    init, which is why every point needs its own interpreter), compile
    the bundled pack, run the lane-sharded serve measurement, and print
    the result dict as ONE JSON line (the parent collects it)."""
    from ingress_plus_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(n_devices)
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.parallel.serve_mesh import run_lane_measurement

    cr = compile_ruleset(load_bundled_rules())
    n_req = int(os.environ.get("MESH_POINT_REQS", "1024"))
    m = run_lane_measurement(cr, n_lanes=n_devices, n_req=n_req,
                             max_batch=32, tier_warmup=False)
    print(json.dumps(m), flush=True)


def run_mesh_scale(points=(1, 2, 4, 8),
                   out_path: str | None = None) -> dict:
    """MESHSCALE leg (ISSUE 7): aggregate serve-plane req/s at 1/2/4/8
    simulated devices (``--xla_force_host_platform_device_count`` via a
    fresh subprocess per point), through the REAL lane-sharded batcher
    — the measured trajectory of ROADMAP item 2, not a smoke test.
    Writes reports/MESHSCALE.json; scaling efficiency at 8 devices
    below 0.7 is warned about LOUDLY, never silently recorded.  On a
    host with fewer cores than devices the virtual chips serialize and
    the warning explains WHY — the number is still honest."""
    import subprocess

    _require_unheld_chip("--mesh-scale")
    here = os.path.abspath(__file__)
    results = []
    for k in points:
        budget = _budget_left()
        if budget < 90:
            log("MESHSCALE: %.0fs budget left — stopping before %d "
                "devices" % (budget, k))
            break
        try:
            out = subprocess.run(
                [sys.executable, here, "--mesh-point=%d" % k],
                capture_output=True, text=True,
                timeout=max(90, min(300, budget - 10)))
        except subprocess.TimeoutExpired:
            log("MESHSCALE: %d-device point timed out (non-fatal)" % k)
            continue
        sys.stderr.write(out.stderr[-1500:])
        line = (out.stdout.strip().splitlines() or [""])[-1]
        if out.returncode != 0 or not line.startswith("{"):
            log("MESHSCALE: %d-device point rc=%d (non-fatal)"
                % (k, out.returncode))
            continue
        try:
            m = json.loads(line)
        except json.JSONDecodeError:
            # a point killed mid-write emits truncated JSON — skip the
            # point like every other per-point failure, never abort
            # the whole curve
            log("MESHSCALE: %d-device point emitted malformed JSON "
                "(non-fatal)" % k)
            continue
        results.append(m)
        log("MESHSCALE %d devices: %s req/s (util %s, recompiles %s)"
            % (k, m.get("req_per_s_mesh"),
               m.get("per_device_utilization"),
               m.get("serve_time_recompiles")))
    result = {
        "metric": "aggregate serve-plane req/s vs simulated device "
                  "count (lane-sharded batcher, bundled CRS pack, "
                  "virtual CPU devices)",
        # per-leg backend tag (ISSUE 13 satellite)
        "platform": "cpu-virtual",
        "host_cpus": os.cpu_count(),
        "points": results,
    }
    base = next((m for m in results
                 if m["n_lanes"] == 1 and m.get("req_per_s_mesh")), None)
    if base:
        scaling = {}
        for m in results:
            if not m.get("req_per_s_mesh"):
                continue
            k = m["n_lanes"]
            sp = m["req_per_s_mesh"] / base["req_per_s_mesh"]
            scaling[str(k)] = {"speedup": round(sp, 3),
                               "efficiency": round(sp / k, 3)}
        result["scaling"] = scaling
        eight = scaling.get("8")
        if eight is not None:
            result["efficiency_8dev"] = eight["efficiency"]
            if eight["efficiency"] < 0.7:
                log("=" * 64)
                log("MESHSCALE WARNING: scaling efficiency at 8 devices "
                    "is %.2f (gate: >= 0.7) — the mesh serve plane is "
                    "NOT near-linear on this host." % eight["efficiency"])
                if (os.cpu_count() or 1) < 8:
                    log("  (host has %d CPU core(s) for 8 virtual "
                        "devices: the simulated chips SERIALIZE — this "
                        "measures dispatch overhead, not chip-parallel "
                        "capacity; rerun on >=8 cores or a real mesh "
                        "for the capacity number)" % (os.cpu_count() or 1))
                log("=" * 64)
            else:
                log("MESHSCALE: 8-device efficiency %.2f (gate >= 0.7)"
                    % eight["efficiency"])
    else:
        log("MESHSCALE WARNING: no 1-device baseline point — the "
            "scaling curve is INCOMPLETE this round (budget or point "
            "failure); the efficiency gate was NOT evaluated")
    # confirm-stage share (docs/CONFIRM_PLANE.md): the serialized-
    # residue gauge — when the CPU confirm stage dominates the widest
    # point's pipeline time, more chips cannot raise mesh throughput
    # (Amdahl); the warning names the knob that can.
    widest = max((m for m in results if m.get("confirm_share")
                  is not None), key=lambda m: m["n_lanes"], default=None)
    if widest is not None:
        result["confirm_share_widest"] = widest["confirm_share"]
        if widest["confirm_share"] >= 0.5:
            log("=" * 64)
            log("MESHSCALE WARNING: CONFIRM BOUNDS MESH THROUGHPUT — "
                "the CPU confirm stage is %.0f%% of pipeline time at "
                "%d lanes (confirm workers: %s).  Adding chips cannot "
                "help past this point; raise --confirm-workers (the "
                "parallel confirm plane, docs/CONFIRM_PLANE.md) or "
                "improve quick-reject coverage."
                % (widest["confirm_share"] * 100, widest["n_lanes"],
                   widest.get("confirm_workers")))
            log("=" * 64)
        else:
            log("MESHSCALE: confirm share at %d lanes is %.0f%% "
                "(bound-warning gate: >= 50%%)"
                % (widest["n_lanes"], widest["confirm_share"] * 100))
    else:
        log("MESHSCALE WARNING: no point carried a confirm_share — "
            "the confirm-bound check was NOT evaluated this round")
    # measured overlap structure (ISSUE 12): every point carries the
    # flight recorder's pipeline_overlap; the widest point's block is
    # promoted and checked against the PR 7/9 design claims — a
    # contradiction is LOUD, never a silently-recorded number
    widest_po = max((m for m in results if m.get("pipeline_overlap")),
                    key=lambda m: m["n_lanes"], default=None)
    if widest_po is not None:
        from ingress_plus_tpu.utils.overlap import check_claims
        po = widest_po["pipeline_overlap"]
        result["pipeline_overlap_widest"] = po
        log("MESHSCALE overlap at %d lanes: scan<->confirm=%s "
            "drain_occ=%s critical=%s"
            % (widest_po["n_lanes"], po.get("scan_confirm_overlap"),
               po.get("drain_occupancy"),
               "/".join("%s:%d" % kv
                        for kv in (po.get("critical_path") or {})
                        .items())))
        for w in check_claims(po):
            log("=" * 64)
            log("MESHSCALE PIPELINE OVERLAP WARNING: %s" % w)
            log("=" * 64)
    else:
        log("MESHSCALE WARNING: no point carried a pipeline_overlap — "
            "the flight recorder measured nothing this round (overlap "
            "claims unverified)")
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reports", "MESHSCALE.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        log("MESHSCALE written to %s" % out_path)
    except OSError as e:
        log("MESHSCALE write failed (non-fatal): %r" % (e,))
    return result


def run_tenant_iso(n_tenants: int = 100, phase_s: float = 6.0,
                   victim_rps: int = 120,
                   out_path: str | None = None) -> dict:
    """TENANTFAIR leg (ISSUE 10): victim-isolation measurement for the
    tenant-fair serve plane (docs/ROBUSTNESS.md "Tenant isolation").

    100+ simulated tenants send paced "victim" traffic through a real
    batcher (bundled CRS pack, CPU); one hostile tenant then floods
    flat-out.  The leg reports the victims' p50/p99 and goodput (real,
    un-degraded verdicts/s) in both phases: SOLO (no flood — the
    baseline) and FLOOD.  The isolation claim is quantitative: victim
    p99 within 25% of its solo baseline while the flooding tenant is
    being shed — inflation past that is warned about LOUDLY, never
    silently recorded.  Writes reports/TENANTFAIR.json."""
    import dataclasses

    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import (
        DetectionPipeline, warm_sizes)
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.utils.corpus import generate_corpus

    log("TENANTFAIR: compiling the bundled pack...")
    cr = compile_ruleset(load_bundled_rules())
    pipeline = DetectionPipeline(cr, mode="block")
    b = Batcher(pipeline, max_batch=32, max_delay_s=0.0005,
                hard_deadline_s=0.25, tenant_queue_cap=64)
    base_reqs = [lr.request for lr in generate_corpus(n=512, seed=7)]
    log("TENANTFAIR: warming serve shapes...")
    for size in warm_sizes(32):
        pipeline.detect(base_reqs[:size])
    b.reset_latency_observations()
    hostile_tenant = n_tenants + 1

    def run_phase(flood: bool) -> dict:
        lock = threading.Lock()
        lat: list = []
        good = [0]
        hostile_sent = [0]
        hostile_curbed = [0]
        stop = threading.Event()

        def flooder():
            j = 0
            while not stop.is_set():
                for _ in range(64):
                    r = dataclasses.replace(
                        base_reqs[j % len(base_reqs)],
                        tenant=hostile_tenant,
                        request_id="h%d" % j)
                    fut = b.submit(r)

                    def _hb(f):
                        try:
                            v = f.result()
                        except Exception:
                            return
                        if v.fail_open or v.degraded:
                            with lock:
                                hostile_curbed[0] += 1
                    fut.add_done_callback(_hb)
                    j += 1
                hostile_sent[0] = j
                time.sleep(0.01)

        ft = None
        if flood:
            ft = threading.Thread(target=flooder, daemon=True,
                                  name="ipt-flood")
            ft.start()
        t_end = time.time() + phase_s
        i = 0
        batch_sz = 6
        period = batch_sz / victim_rps
        pending: list = []
        while time.time() < t_end:
            tick = time.perf_counter()
            for _ in range(batch_sz):
                r = dataclasses.replace(
                    base_reqs[i % len(base_reqs)],
                    tenant=1 + (i % n_tenants),
                    request_id="v%d" % i)
                t0 = time.perf_counter()
                fut = b.submit(r)

                def _cb(f, t0=t0):
                    dt = time.perf_counter() - t0
                    try:
                        v = f.result()
                    except Exception:
                        return
                    with lock:
                        lat.append(dt)
                        if not v.fail_open and not v.degraded:
                            good[0] += 1
                fut.add_done_callback(_cb)
                pending.append(fut)
                i += 1
            sleep = period - (time.perf_counter() - tick)
            if sleep > 0:
                time.sleep(sleep)
        for fut in pending:
            try:
                fut.result(timeout=30)
            except Exception:
                pass
        stop.set()
        if ft is not None:
            ft.join(timeout=5)
        with lock:
            xs = sorted(lat)
        n = len(xs)

        def pct(p):
            return int(xs[min(int(p * n), n - 1)] * 1e6) if n else None
        return {
            "victims_sent": i,
            "victims_measured": n,
            "victim_p50_us": pct(0.50),
            "victim_p99_us": pct(0.99),
            "victim_goodput_rps": round(good[0] / phase_s, 1),
            "hostile_sent": hostile_sent[0],
            "hostile_curbed": hostile_curbed[0],
        }

    try:
        # unmeasured pacing warm: the first paced waves pay cold-cache
        # effects (small-Q executables, allocator warmup) that would
        # inflate the SOLO baseline and flatter the flood phase —
        # measured on this host as a ~4x p99 asymmetry between an
        # unwarmed first phase and the second
        log("TENANTFAIR: pacing warm...")
        _save = phase_s
        try:
            phase_s = 2.0
            run_phase(flood=False)
        finally:
            phase_s = _save
        log("TENANTFAIR: solo phase (%d tenants, no flood)..." % n_tenants)
        solo = run_phase(flood=False)
        time.sleep(1.0)   # settle: queues drain, EWMAs decay
        log("TENANTFAIR: flood phase (tenant %d flat-out)..."
            % hostile_tenant)
        flood = run_phase(flood=True)
    finally:
        b.close()
    g = b.tenant_guard
    result = {
        "metric": "victim p99 under a one-tenant flood vs solo "
                  "baseline (tenant-fair admission + flood guard, "
                  "bundled CRS pack, CPU)",
        "n_tenants": n_tenants,
        "platform": "cpu",   # per-leg backend tag (ISSUE 13 satellite)
        "host_cpus": os.cpu_count(),
        "phase_s": phase_s,
        "victim_rps_offered": victim_rps,
        "solo": solo,
        "flood": flood,
        "guard": g.brief() if g is not None else None,
        "ladder_steps_up": pipeline.load_controller.steps_up,
        "shed": dict(pipeline.stats.shed),
    }
    if solo.get("victim_p99_us") and flood.get("victim_p99_us"):
        infl = flood["victim_p99_us"] / solo["victim_p99_us"]
        result["victim_p99_inflation"] = round(infl, 3)
        if solo.get("victim_goodput_rps"):
            result["victim_goodput_ratio"] = round(
                flood["victim_goodput_rps"] / solo["victim_goodput_rps"],
                3)
        if not flood.get("hostile_curbed"):
            log("TENANTFAIR WARNING: the flood was never shed or "
                "degraded — the leg measured contention, not "
                "isolation (flood too weak for this host?)")
        if infl > 1.25:
            log("=" * 64)
            log("TENANTFAIR WARNING: victim p99 inflated %.2fx under a "
                "one-tenant flood (gate: <= 1.25x solo baseline) — "
                "tenant isolation is NOT holding on this host "
                "(solo p99 %dus -> flood p99 %dus; hostile curbed "
                "%d/%d)." % (infl, solo["victim_p99_us"],
                             flood["victim_p99_us"],
                             flood["hostile_curbed"],
                             flood["hostile_sent"]))
            if (os.cpu_count() or 1) < 2:
                log("  (1-core host: the flooder, dispatch thread and "
                    "victim pacer share one CPU — some inflation is "
                    "scheduling contention, not unfairness; rerun on "
                    ">=2 cores for the isolation number)")
            log("=" * 64)
        else:
            log("TENANTFAIR: victim p99 inflation %.2fx (gate <= "
                "1.25x); goodput ratio %s" %
                (infl, result.get("victim_goodput_ratio")))
    else:
        log("TENANTFAIR WARNING: a phase measured no victim latencies "
            "— the inflation gate was NOT evaluated this round")
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reports", "TENANTFAIR.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        log("TENANTFAIR written to %s" % out_path)
    except OSError as e:
        log("TENANTFAIR write failed (non-fatal): %r" % (e,))
    return result


#: rules for the --fleet-obs nodes: every node serves the sqli rule;
#: the LAST node also loads the xss file, so its pack generation
#: differs and the aggregator's cross-check must flag exactly it
_FLEET_TINY_RULES = """
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)union\\s+select" \\
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
"""
_FLEET_EXTRA_RULES = """
SecRule REQUEST_URI|ARGS "@rx (?i)<script" \\
    "id:941100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-xss'"
"""


def run_fleet_obs(n_nodes: int = 3, out_path: str | None = None) -> dict:
    """FLEETOBS leg (ISSUE 18): the fleet telemetry plane measured over
    REAL serve processes — ``n_nodes`` subprocess serve loops on the
    UDS protocol, each exposing its own HTTP observability surface, and
    a FleetObserver scraping/merging them from this process.  The one
    JSON line proves, on live traffic:

    * **conservation** — fleet ``ipt_requests_total`` equals the sum of
      the per-node addends equals the requests this driver counted on
      the wire, three times over: full fleet, a cycle with one node
      faulted stale mid-run (``scrape_5xx`` site), and post-recovery;
    * **merge determinism** — the traffic-weighted MeasuredProfile
      merge reproduces the same content hash with the argument order
      reversed;
    * **skew** — the last node serves one extra rule file on purpose,
      so the generation cross-check must flag it (and only it);
    * **SLO burn** — two scrape cycles with traffic between them give
      the burn engine real deltas; ``ipt_slo_*`` series must appear on
      the aggregated exposition;
    * **scrape overhead** — best-of-N A/B wall time of an identical
      wave with and without a 0.2s-interval background scraper; the
      budget is < 3% (being observed must cost ~nothing).

    Writes reports/FLEETBENCH.json."""
    import shutil
    import socket as socket_mod
    import subprocess
    import tempfile

    from ingress_plus_tpu.compiler.profile import MeasuredProfile
    from ingress_plus_tpu.control.fleetobs import FleetObserver
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)
    from ingress_plus_tpu.utils import faults
    from ingress_plus_tpu.utils.faults import FaultPlan

    _require_unheld_chip("--fleet-obs")
    base_port = 19961
    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="ipt-fleetbench-")
    procs: list = []
    socks: list = []
    sent = [0] * n_nodes
    rid_ctr = [5000]
    saved_plan = faults.active()
    faults.clear()
    obs = FleetObserver()
    try:
        log("FLEETOBS: launching %d serve nodes..." % n_nodes)
        for i in range(n_nodes):
            rules_dir = os.path.join(tmp, "rules%d" % i)
            os.makedirs(rules_dir)
            with open(os.path.join(rules_dir, "tiny.conf"), "w") as f:
                f.write(_FLEET_TINY_RULES)
            if i == n_nodes - 1:
                with open(os.path.join(rules_dir, "extra.conf"),
                          "w") as f:
                    f.write(_FLEET_EXTRA_RULES)
            sock = os.path.join(tmp, "n%d.sock" % i)
            env = dict(os.environ)
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ingress_plus_tpu.serve",
                 "--socket", sock, "--http-port", str(base_port + i),
                 "--rules-dir", rules_dir, "--platform", "cpu",
                 "--max-delay-us", "1000", "--no-warmup"],
                cwd=repo, env=env))
            socks.append(sock)
            obs.add_node("n%d" % i,
                         target="127.0.0.1:%d" % (base_port + i))
        for i, sock in enumerate(socks):
            for _ in range(600):
                if os.path.exists(sock):
                    try:
                        s = socket_mod.socket(socket_mod.AF_UNIX)
                        s.connect(sock)
                        s.close()
                        break
                    except OSError:
                        pass
                if procs[i].poll() is not None:
                    raise RuntimeError("fleet node %d died at startup"
                                       % i)
                time.sleep(0.1)
            else:
                raise RuntimeError("fleet node %d socket never appeared"
                                   % i)

        def wave(per_node: int = 32) -> float:
            """One identical traffic wave to every node (mixed benign +
            sqli); returns wall seconds and counts what was SENT — the
            independent side of the conservation audit."""
            t0 = time.perf_counter()
            for i, sock in enumerate(socks):
                reqs = []
                for j in range(per_node):
                    rid = rid_ctr[0]
                    rid_ctr[0] += 1
                    uri = ("/q?a=1+union+select+%d" % rid if j % 5 == 0
                           else "/item/%d?q=benign" % rid)
                    reqs.append((Request(uri=uri,
                                         headers={"Host": "fleet.example"},
                                         tenant=1 + j % 8,
                                         request_id=str(rid)), rid))
                s = socket_mod.socket(socket_mod.AF_UNIX)
                s.connect(sock)
                s.settimeout(120)
                for req, rid in reqs:
                    s.sendall(encode_request(req, req_id=rid))
                reader, got = FrameReader(RESP_MAGIC), 0
                while got < len(reqs):
                    for fr in reader.feed(s.recv(65536)):
                        decode_response(fr)
                        got += 1
                s.close()
                sent[i] += per_node
            return time.perf_counter() - t0

        def conservation() -> dict:
            fleet, per_node = obs.counters_snapshot()
            addends = per_node.get("ipt_requests_total", {})
            reachable_sent = sum(c for i, c in enumerate(sent)
                                 if obs.nodes[i].up)
            total = fleet.get("ipt_requests_total", -1.0)
            return {
                "sent_reachable": reachable_sent,
                "fleet_total": total,
                "per_node": {k: addends[k] for k in sorted(addends)},
                "ok": (total == float(reachable_sent)
                       and sum(addends.values())
                       == float(reachable_sent)),
            }

        # --- leg 1: traffic, two scrape cycles (SLO deltas need two),
        # full-fleet conservation, skew, profile-merge determinism
        log("FLEETOBS: warm wave + scrape cycle 1...")
        wave()
        obs.scrape()
        time.sleep(0.3)
        log("FLEETOBS: wave + scrape cycle 2...")
        wave()
        health = obs.scrape()
        cons_full = conservation()
        gen_skew = [f for f in health["skew_findings"]
                    if f["kind"] == "generation_skew"]
        profs = [n.profile for n in obs.nodes if n.profile is not None]
        merged = obs.merged_profile()
        merge_hashes = []
        if len(profs) == n_nodes:
            merge_hashes = [
                MeasuredProfile.merge(profs).content_hash(),
                MeasuredProfile.merge(list(reversed(profs)))
                .content_hash()]
        fleet_text = obs.fleet_metrics()
        slo = obs.fleet_slo()

        # --- leg 2: one node faulted stale mid-run; conservation must
        # hold over the reachable subset, then recover to the full sum
        log("FLEETOBS: stale drill (scrape_5xx on the next cycle)...")
        faults.install(FaultPlan.from_spec("scrape_5xx:times=1"))
        wave()
        stale_health = obs.scrape()
        faults.clear()
        cons_stale = conservation()
        stale_names = [n.name for n in obs.nodes if n.stale]
        wave()
        obs.scrape()
        cons_recovered = conservation()

        # --- leg 3: A/B scrape overhead on an identical wave (nodes
        # are warm by now; best-of keeps host noise out of the number)
        log("FLEETOBS: A/B scrape-overhead wave (unscraped)...")
        best_off = min(wave(per_node=48) for _ in range(3))
        log("FLEETOBS: A/B scrape-overhead wave (scraped @0.2s)...")
        obs.start_scraping(interval_s=0.2)
        try:
            best_on = min(wave(per_node=48) for _ in range(3))
        finally:
            obs.close()
        overhead = best_on / best_off - 1.0

        result = {
            "metric": "fleet telemetry plane: counter conservation, "
                      "merge determinism, skew + SLO burn over %d "
                      "serve nodes" % n_nodes,
            "platform": "cpu",
            "n_nodes": n_nodes,
            "fleet": {
                "conservation_full": cons_full,
                "conservation_one_stale": cons_stale,
                "conservation_recovered": cons_recovered,
                "stale_drill": {
                    "nodes_up": stale_health["nodes_up"],
                    "nodes_stale": stale_health["nodes_stale"],
                    "stale_nodes": stale_names,
                },
                "skew_findings": health["skew_findings"],
                "generation_skew_nodes": sorted(
                    f["node"] for f in gen_skew),
                "merged_profile": health["merged_profile"],
                "merge_hashes": merge_hashes,
                "merge_deterministic": (len(merge_hashes) == 2
                                        and merge_hashes[0]
                                        == merge_hashes[1]),
                "slo": slo,
                "slo_series_exposed": "ipt_slo_burn_rate" in fleet_text,
                "scrape_overhead": {
                    "best_unscraped_s": round(best_off, 4),
                    "best_scraped_s": round(best_on, 4),
                    "overhead_frac": round(overhead, 4),
                    "budget_frac": 0.03,
                    "ok": overhead < 0.03,
                },
            },
        }
        ok = (cons_full["ok"] and cons_stale["ok"]
              and cons_recovered["ok"]
              and stale_health["nodes_stale"] == 1
              and result["fleet"]["merge_deterministic"]
              and bool(gen_skew)
              and result["fleet"]["slo_series_exposed"]
              and overhead < 0.03)
        result["fleet"]["ok"] = ok
        if not ok:
            log("=" * 64)
            log("FLEETOBS WARNING: an acceptance leg failed — see the "
                "fleet block (conservation %s/%s/%s, stale=%d, "
                "merge_det=%s, gen_skew=%s, slo_series=%s, "
                "overhead=%.4f)"
                % (cons_full["ok"], cons_stale["ok"],
                   cons_recovered["ok"], stale_health["nodes_stale"],
                   result["fleet"]["merge_deterministic"],
                   bool(gen_skew),
                   result["fleet"]["slo_series_exposed"], overhead))
            log("=" * 64)
        else:
            log("FLEETOBS: all legs ok (fleet total %s == sent %s; "
                "merge hash %s; scrape overhead %.2f%%)"
                % (cons_recovered["fleet_total"],
                   cons_recovered["sent_reachable"],
                   merge_hashes[0] if merge_hashes else "?",
                   overhead * 100.0))
        if out_path is None:
            out_path = os.path.join(repo, "reports", "FLEETBENCH.json")
        try:
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2)
            log("FLEETBENCH written to %s" % out_path)
        except OSError as e:
            log("FLEETBENCH write failed (non-fatal): %r" % (e,))
        return result
    finally:
        faults.clear()
        if saved_plan is not None:
            faults.install(saved_plan)
        obs.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:    # noqa: BLE001 — teardown best-effort
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def run_fleet(n_nodes: int = 4, out_path: str | None = None) -> dict:
    """FLEET leg (ISSUE 19): the shared admission front measured over
    REAL processes — ``n_nodes`` subprocess serve loops plus the front
    as its own subprocess (``serve --front``), driven through the
    front's one UDS listener.  The one JSON line proves, on live
    traffic:

    * **fan-out scaling** — aggregate req/s through the front with all
      nodes up is at least 3x the same wave pushed at ONE node directly
      (the front adds balancing, not a bottleneck);
    * **node kill mid-run** — one backend SIGKILLed while a wave is in
      flight: every request still gets EXACTLY one verdict (in-flight
      requests on the dead node come back as synthesized fail-open,
      everything else reroutes), and zero attack requests pass
      unblocked without carrying the fail-open flag — degradation is
      explicit, never silent;
    * **post-kill steady state** — the next wave over the surviving
      nodes serves zero fail-opens and blocks every attack (capacity
      degraded, service intact);
    * **re-admission** — the killed node restarted on the same socket
      is probed half-open, canaried, and re-admitted to UP without
      operator action.

    Writes reports/FLEET.json."""
    import shutil
    import socket as socket_mod
    import subprocess
    import tempfile
    import threading
    import urllib.request

    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.serve.protocol import (
        RESP_MAGIC, FrameReader, decode_response, encode_request)

    _require_unheld_chip("--fleet")
    base_port = 20061
    front_port = base_port + 50
    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="ipt-fleet-")
    procs: dict = {}
    node_threads = 8
    rid_ctr = [1]
    rid_lock = threading.Lock()

    def spawn_node(i: int) -> None:
        rules_dir = os.path.join(tmp, "rules%d" % i)
        if not os.path.isdir(rules_dir):
            os.makedirs(rules_dir)
            with open(os.path.join(rules_dir, "tiny.conf"), "w") as f:
                f.write(_FLEET_TINY_RULES)
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        procs["n%d" % i] = subprocess.Popen(
            [sys.executable, "-m", "ingress_plus_tpu.serve",
             "--socket", os.path.join(tmp, "n%d.sock" % i),
             "--http-port", str(base_port + i),
             "--rules-dir", rules_dir, "--platform", "cpu",
             "--max-delay-us", "1000", "--no-warmup"],
            cwd=repo, env=env)

    def wait_sock(path: str, proc, what: str) -> None:
        for _ in range(600):
            if os.path.exists(path):
                try:
                    s = socket_mod.socket(socket_mod.AF_UNIX)
                    s.connect(path)
                    s.close()
                    return
                except OSError:
                    pass
            if proc.poll() is not None:
                raise RuntimeError("%s died at startup" % what)
            time.sleep(0.1)
        raise RuntimeError("%s socket never appeared" % what)

    def front_nodes() -> dict:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/front/nodes" % front_port,
                timeout=5) as r:
            return {n["name"]: n for n in json.loads(r.read())}

    def wave(sock_path: str, per_thread: int, threads: int,
             attack_every: int = 4,
             mid_run=None) -> dict:
        """``threads`` client connections, each pipelining
        ``per_thread`` mixed requests; returns wall seconds + the full
        verdict ledger keyed by req_id.  ``mid_run`` (optional thunk)
        fires once from the driver after ~1/3 of the wave is in."""
        ledger: dict = {}
        attacks: set = set()
        errs: list = []
        led_lock = threading.Lock()
        started = threading.Barrier(threads + 1)

        def client() -> None:
            with rid_lock:
                rid0 = rid_ctr[0]
                rid_ctr[0] += per_thread
            reqs = []
            for j in range(per_thread):
                rid = rid0 + j
                if attack_every and j % attack_every == 0:
                    uri = "/q?a=1+union+select+%d" % rid
                    with led_lock:
                        attacks.add(rid)
                else:
                    uri = "/item/%d?q=benign" % rid
                reqs.append((Request(uri=uri,
                                     headers={"Host": "fleet.example"},
                                     tenant=1 + j % 8, mode=2,
                                     request_id=str(rid)), rid))
            s = socket_mod.socket(socket_mod.AF_UNIX)
            s.connect(sock_path)
            s.settimeout(120)
            started.wait()
            try:
                for req, rid in reqs:
                    s.sendall(encode_request(req, req_id=rid))
                reader, got = FrameReader(RESP_MAGIC), 0
                while got < len(reqs):
                    data = s.recv(65536)
                    if not data:
                        raise RuntimeError("front closed mid-wave")
                    for fr in reader.feed(data):
                        v = decode_response(fr)
                        with led_lock:
                            if v["req_id"] in ledger:
                                errs.append("dup verdict for %d"
                                            % v["req_id"])
                            ledger[v["req_id"]] = v
                        got += 1
            except Exception as e:  # noqa: BLE001 — audited below
                with led_lock:
                    errs.append("%s: %s" % (type(e).__name__, e))
            finally:
                s.close()

        ts = [threading.Thread(target=client) for _ in range(threads)]
        for t in ts:
            t.start()
        started.wait()
        t0 = time.perf_counter()
        if mid_run is not None:
            # ~1/3 into the wave: far enough in that requests are on
            # every node, early enough that plenty remain to reroute
            time.sleep(0.08)
            mid_run()
        for t in ts:
            t.join()
        wall = time.perf_counter() - t0
        n = per_thread * threads
        fail_open = [r for r, v in ledger.items() if v["fail_open"]]
        unblocked = [r for r in attacks
                     if r in ledger and not ledger[r]["blocked"]
                     and not ledger[r]["fail_open"]]
        return {
            "sent": n, "got": len(ledger),
            "wall_s": round(wall, 4),
            "rps": round(n / wall, 1),
            "attacks": len(attacks),
            "attacks_blocked": sum(
                1 for r in attacks
                if r in ledger and ledger[r]["blocked"]),
            "fail_open": len(fail_open),
            "attacks_unblocked_silent": len(unblocked),
            "errors": errs,
            "lost": n - len(ledger),
        }

    try:
        log("FLEET: launching %d serve nodes + front..." % n_nodes)
        for i in range(n_nodes):
            spawn_node(i)
        for i in range(n_nodes):
            wait_sock(os.path.join(tmp, "n%d.sock" % i),
                      procs["n%d" % i], "fleet node %d" % i)
        front_sock = os.path.join(tmp, "front.sock")
        backends = ["n%d=%s@127.0.0.1:%d"
                    % (i, os.path.join(tmp, "n%d.sock" % i),
                       base_port + i) for i in range(n_nodes)]
        procs["front"] = subprocess.Popen(
            [sys.executable, "-m", "ingress_plus_tpu.serve",
             "--front", "--socket", front_sock,
             "--http-port", str(front_port),
             "--probe-interval-s", "0.3"]
            + [a for b in backends for a in ("--backend", b)],
            cwd=repo, env=dict(os.environ))
        wait_sock(front_sock, procs["front"], "front")
        for _ in range(100):
            if sum(1 for n in front_nodes().values()
                   if n["state"] == "up") == n_nodes:
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("front never saw all %d nodes up"
                               % n_nodes)

        # --- leg 1: fan-out scaling, best-of-3 each way (one node
        # direct vs the full fleet through the front, same wave shape)
        log("FLEET: warmup wave...")
        wave(front_sock, 32, node_threads)
        log("FLEET: single-node baseline waves...")
        single = min((wave(os.path.join(tmp, "n0.sock"), 64,
                           node_threads) for _ in range(3)),
                     key=lambda w: w["wall_s"])
        log("FLEET: fleet waves through the front...")
        fleet_w = min((wave(front_sock, 64, node_threads)
                       for _ in range(3)),
                      key=lambda w: w["wall_s"])
        speedup = fleet_w["rps"] / single["rps"] if single["rps"] else 0.0
        # the ≥3x gate needs real parallel hardware: n_nodes detection
        # processes + the front + the driver on ONE core measures the
        # scheduler, not the fan-out.  Waive (loudly, recorded in the
        # artifact) when the host can't physically demonstrate scaling.
        host_cores = len(os.sched_getaffinity(0))
        speedup_enforced = host_cores >= n_nodes
        log("FLEET: single %.0f req/s, fleet %.0f req/s (%.2fx, "
            "%d-core host, 3x gate %s)"
            % (single["rps"], fleet_w["rps"], speedup, host_cores,
               "enforced" if speedup_enforced
               else "WAIVED: host too small"))

        # --- leg 2: SIGKILL one node mid-wave; exactly-one-verdict
        # must hold and no attack may pass silently unblocked
        log("FLEET: kill drill (SIGKILL n1 mid-wave)...")
        kill_w = wave(front_sock, 96, node_threads,
                      mid_run=lambda: procs["n1"].kill())
        procs["n1"].wait(timeout=10)
        log("FLEET: kill wave: %d/%d verdicts, %d fail-open, "
            "%d attacks silently unblocked"
            % (kill_w["got"], kill_w["sent"], kill_w["fail_open"],
               kill_w["attacks_unblocked_silent"]))

        # --- leg 3: post-kill steady state over the survivors
        for _ in range(50):   # let the front finish ejecting n1
            states = front_nodes()
            if states["n1"]["state"] != "up":
                break
            time.sleep(0.1)
        post_w = wave(front_sock, 64, node_threads)
        ejected = front_nodes()["n1"]["state"]

        # --- leg 4: restart n1 on the same socket; the front must
        # probe it half-open, canary it, and re-admit without help
        log("FLEET: restarting n1 for re-admission...")
        os.unlink(os.path.join(tmp, "n1.sock"))
        spawn_node(1)
        wait_sock(os.path.join(tmp, "n1.sock"), procs["n1"],
                  "restarted n1")
        for _ in range(300):
            n1 = front_nodes()["n1"]
            if n1["state"] == "up":
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("front never re-admitted n1: %r" % (n1,))
        readmit_w = wave(front_sock, 32, node_threads)
        n1_after = front_nodes()["n1"]

        result = {
            "metric": "shared admission front: fan-out scaling, node "
                      "kill mid-run, re-admission over %d serve nodes"
                      % n_nodes,
            "platform": "cpu",
            "n_nodes": n_nodes,
            "fleet_front": {
                "single_node": single,
                "fleet": fleet_w,
                "speedup": round(speedup, 2),
                "speedup_target": 3.0,
                "host_cores": host_cores,
                "speedup_gate": ("enforced" if speedup_enforced
                                 else "waived:%d-core host cannot "
                                      "demonstrate %d-way fan-out"
                                      % (host_cores, n_nodes)),
                "kill_wave": kill_w,
                "post_kill_wave": post_w,
                "ejected_state": ejected,
                "readmit_wave": readmit_w,
                "readmitted": {
                    "state": n1_after["state"],
                    "readmissions": n1_after["readmissions"],
                    "forwarded": n1_after["forwarded"],
                },
            },
        }
        ok = ((speedup >= 3.0 or not speedup_enforced)
              and kill_w["lost"] == 0 and not kill_w["errors"]
              and kill_w["attacks_unblocked_silent"] == 0
              and post_w["lost"] == 0 and post_w["fail_open"] == 0
              and post_w["attacks_blocked"] == post_w["attacks"]
              and n1_after["state"] == "up"
              and n1_after["readmissions"] >= 1)
        result["fleet_front"]["ok"] = ok
        if not ok:
            log("=" * 64)
            log("FLEET WARNING: an acceptance leg failed — speedup "
                "%.2fx (>=3.0), kill lost=%d errs=%d silent=%d, post "
                "lost=%d fo=%d, n1=%s/readmits=%d"
                % (speedup, kill_w["lost"], len(kill_w["errors"]),
                   kill_w["attacks_unblocked_silent"], post_w["lost"],
                   post_w["fail_open"], n1_after["state"],
                   n1_after["readmissions"]))
            log("=" * 64)
        else:
            log("FLEET: all legs ok (%.2fx fan-out, zero verdict loss "
                "through the kill, n1 re-admitted)" % speedup)
        if out_path is None:
            out_path = os.path.join(repo, "reports", "FLEET.json")
        try:
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2)
            log("FLEET written to %s" % out_path)
        except OSError as e:
            log("FLEET write failed (non-fatal): %r" % (e,))
        return result
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except Exception:    # noqa: BLE001 — teardown best-effort
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def run_bench() -> dict:
    """Measure and return the result dict on the accelerator JAX
    selects; without one it raises (``BENCH_PLATFORM=cpu`` is the
    explicit CPU run)."""
    import jax
    import jax.numpy as jnp

    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.engine import EngineTables
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.normalize import merge_rows, rows_for_requests
    from ingress_plus_tpu.utils.corpus import generate_corpus
    from ingress_plus_tpu.utils.microbench import best_time, k_diff_time
    from ingress_plus_tpu.utils.platform import (
        device_block,
        force_cpu_devices,
    )

    quick = "--quick" in sys.argv
    n_req = 256 if quick else 2048
    iters = 129 if quick else 65  # small batches need more reps for signal

    explicit_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    if explicit_cpu:
        force_cpu_devices(1)
    device = device_block()   # first backend touch: no chip raises here
    platform = device["platform"]
    if platform == "cpu" and not explicit_cpu:
        raise RuntimeError(
            "no accelerator: JAX selected %s — a CPU timing is not a "
            "device metric (BENCH_PLATFORM=cpu for an explicit CPU run)"
            % device)
    _arm_watchdog()
    log("device: %s" % device)

    t0 = time.time()
    cr = compile_ruleset(load_bundled_rules())
    log("ruleset: %d rules, %d factors, %d words (compiled in %.1fs)"
        % (cr.n_rules, cr.tables.n_factors, cr.tables.n_words, time.time() - t0))

    corpus = generate_corpus(n=n_req, attack_fraction=0.2, seed=42)
    requests = [lr.request for lr in corpus]
    pipeline = DetectionPipeline(cr)  # reuse its row prep config
    rows = rows_for_requests(requests, needed_sv=pipeline.needed_sv)
    data_list, req_list, sv_list = merge_rows(rows)
    total_bytes = sum(len(d) for d in data_list)
    log("corpus: %d requests -> %d scan rows, %.2f scanned KB/request"
        % (n_req, len(data_list), total_bytes / n_req / 1024))

    # Length bucketing: corpus rows average ~0.3KB with a long tail; one
    # padded (B, 512) batch would be ~85% padding.  The serve batcher does
    # the same bucketing online.
    edges = DetectionPipeline.L_BUCKETS  # identical tiers to production

    def build_device_buckets(cr_x, dat, req_ids, svs, verbose=False):
        """Bucket + pad + device_put merged rows for one ruleset — the
        ONE buffer-building path shared by the live-pack and fixed-pack
        legs (review finding: a copy diverging between legs would skew
        exactly the cross-round comparability the fixed leg exists
        for); the numpy assembly itself is bucket_rows_np, shared with
        the PACKSCALE leg too."""
        bufs = []
        for edge, tokens, lengths, rreq, row_sv in bucket_rows_np(
                dat, req_ids, svs, cr_x.rule_sv_mask.shape[1], edges):
            bufs.append((
                # uint8 end-to-end (ISSUE 13): the raw-byte device
                # contract — 4x less host→device transfer volume than
                # the old int32 upcast; every scan impl casts on-device
                jax.device_put(tokens),
                jax.device_put(lengths),
                jax.device_put(rreq),
                jax.device_put(row_sv),
            ))
            if verbose:
                log("bucket %4dB: %d rows" % (edge, tokens.shape[0]))
        return tuple(bufs)

    n_sv = cr.rule_sv_mask.shape[1]
    tables = EngineTables.from_ruleset(cr)
    device_buckets = build_device_buckets(cr, data_list, req_list,
                                          sv_list, verbose=True)

    from ingress_plus_tpu.models.engine import detect_rows

    def make_detect_k(impl: str):
        """K state-chained repetitions of the full multi-bucket batch for
        one scan lowering.

        Fused mapping (docs/SCAN_KERNEL.md, the serving path's
        detect_device_multi shape): every bucket scans at its own
        (B, L), the sticky match words concatenate, and the factor→rule
        mapping — the one stage whose cost scales with rule count — runs
        ONCE per batch instead of once per bucket.

        VERDICT round-2 item 1a: ``tabs`` and ``bufs`` are jit ARGUMENTS,
        not closure constants.  Closing over the device buckets made the
        whole scan chain (constant tokens -> constant match words ->
        segment_max scatter) compile-time constant, and XLA spent 2x33s
        constant-folding the scatter-max (BENCH_r02 tail).  As traced
        parameters nothing can fold and compiles stay in seconds."""
        from ingress_plus_tpu.ops.scan import scan_bytes, scan_pairs

        @functools.partial(jax.jit, static_argnames=("k",))
        def detect_k(k: int, tabs, bufs):
            W = tabs.scan.n_words

            # The returned value must depend on EVERY bucket's work, or
            # XLA's while-loop DCE deletes untouched loop-carry chains and
            # the benchmark times a fraction of the workload.  The match
            # carry per bucket keeps each iteration data-dependent on the
            # previous one (no loop-invariant hoisting).
            def body(i, carry):
                acc, states = carry
                out = []
                matches = []
                for (tok, lens, rreq, rsv), (state, match) in zip(
                        bufs, states):
                    if impl == "pair":
                        # pair path contract: state=None (request scans
                        # consume only the sticky match, which we chain)
                        match, state = scan_pairs(
                            tabs.scan, tok, lens, None, match)
                    else:
                        match, state = scan_bytes(
                            tabs.scan, tok, lens, state, match)
                    out.append((state, match))
                    matches.append(match)
                    acc = acc + match.sum()
                rule_hits = fused_map_fold(tabs, matches, bufs, n_req)
                acc = acc + rule_hits.sum().astype(jnp.uint32)
                return (acc, tuple(out))

            states = tuple(
                (jnp.zeros((b[0].shape[0], W), jnp.uint32),
                 jnp.zeros((b[0].shape[0], W), jnp.uint32))
                for b in bufs)
            acc, _ = jax.lax.fori_loop(
                0, k, body, (jnp.zeros((), jnp.uint32), states))
            return acc

        return detect_k

    log("backend: %s, devices: %s" % (jax.default_backend(), jax.devices()))
    global _HEADLINE
    # measured-winner-first ordering (pair won r01-r03 on BOTH platforms):
    # if the watchdog fires mid-loop the stashed best-so-far is already
    # the likely champion, not the warm-up act
    impls = ["pair", "take"]
    only = [a.split("=", 1)[1] for a in sys.argv if a.startswith("--impl=")]
    if only:
        bad = [i for i in only if i not in impls]
        if bad:
            raise SystemExit("unknown --impl value(s) %s (choose from "
                             "take/pair)" % bad)
        impls = only
    impl_stats: dict = {}
    best_impl, best_rps = None, -1.0
    for impl in impls:
        detect_k = make_detect_k(impl)

        def timed(k: int) -> float:
            return best_time(
                lambda kk, rep: detect_k(kk, tables, device_buckets),
                k, n=3)

        d_lo = timed(1)
        # size K against the time actually left: timed(k) costs about
        # 4*(overhead + k*marginal) (warm + best-of-3), and later
        # impls plus the latency/quality legs still need room — spend
        # at most ~30% of the remaining budget here.  d_lo is an
        # OVERESTIMATE of the marginal cost (it includes the dispatch
        # overhead), safe for the initial sizing only; the widening
        # guard below uses the measured marginal
        pb_est = max(d_lo, 1e-4)
        share = max(15.0, _budget_left() * 0.30)
        it = max(2, min(iters, int(share / (4 * pb_est))))
        d_hi = timed(it)
        d_hi, it = _widen_k(timed, d_lo, d_hi, it, impl,
                            budget_frac=0.5)
        delta = d_hi - d_lo
        if delta <= 0.05:
            # dispatch jitter swamps the compute delta (microbench
            # k_diff_time contract: <=0 delta is NO SIGNAL, never a
            # throughput) — record nothing rather than noise
            impl_stats[impl] = 0.0
            log("[%s] no signal (delta %.1f ms at K=%d, budget-"
                "bounded); skipping" % (impl, delta * 1e3, it))
            continue
        if delta < 0.2:
            log("[%s] WARNING: thin signal (delta %.1f ms at K=%d); "
                "number is noisier than usual" % (impl, delta * 1e3, it))
        per_batch = delta / (it - 1)
        rps = n_req / per_batch
        mbs = total_bytes / per_batch / 1e6
        impl_stats[impl] = round(rps, 1)
        log("[%s] per-batch %.2f ms -> %.0f req/s/chip, %.0f MB/s "
            "scanned" % (impl, per_batch * 1e3, rps, mbs))
        if rps > best_rps:
            best_impl, best_rps = impl, rps
            # stash best-so-far so the watchdog emits a REAL number even
            # if a later impl's compile overruns the deadline
            result = {
                "metric": "req/s/chip, full CRS-v3-shaped ruleset "
                          "(%s detect step, %d-req corpus, scan_impl=%s)"
                          % (platform, n_req, impl),
                "value": round(rps, 1),
                "unit": "req/s/chip",
                "vs_baseline": round(rps / 100_000.0, 4),
                **device,
                "scan_impl": impl,
                "impls": impl_stats,
                # cross-round auditability: r04 grew the pack 1405 -> 2002
                # rules (343 -> 533 scan words), so numbers are not
                # comparable to r03's without these
                "ruleset": {"rules": int(cr.n_rules),
                            "factors": int(cr.tables.n_factors),
                            "words": int(cr.tables.n_words)},
            }
            _HEADLINE = result
    if _HEADLINE is None:
        raise RuntimeError("every scan impl failed: %s" % impl_stats)
    result = _HEADLINE
    result["impls"] = impl_stats
    log("scan impl winner: %s (%s)" % (best_impl, impl_stats))

    # fixed-pack leg (VERDICT r04 item #3): the SAME throughput
    # measurement on the frozen r03 pack, always scan_impl=pair (the
    # r01-r04 winner on both platforms) so the number is comparable
    # round over round — this is what separates "the code got slower"
    # from "the pack got bigger".  Never fatal; headline already stashed.
    try:
        if _budget_left() < 75:
            log("fixed-pack leg skipped: %.0fs budget left" % _budget_left())
        else:
            t0f = time.time()
            cr_fix = load_fixed_pack()
            log("fixed pack: %d rules, %d factors, %d words (compiled "
                "in %.1fs)" % (cr_fix.n_rules, cr_fix.tables.n_factors,
                               cr_fix.tables.n_words, time.time() - t0f))
            pipe_fix = DetectionPipeline(cr_fix)
            rows_f = rows_for_requests(requests,
                                       needed_sv=pipe_fix.needed_sv)
            dlist, rlist, svlist = merge_rows(rows_f)
            tables_f = EngineTables.from_ruleset(cr_fix)
            bufs_f = build_device_buckets(cr_fix, dlist, rlist, svlist)
            dk_fix = make_detect_k("pair")

            def timed_f(k: int) -> float:
                return best_time(
                    lambda kk, rep: dk_fix(kk, tables_f, bufs_f), k, n=3)

            f_lo = timed_f(1)
            share = max(10.0, _budget_left() * 0.20)
            itf = max(2, min(iters, int(share / (4 * max(f_lo, 1e-4)))))
            f_hi = timed_f(itf)
            # same widening as the live leg (shared helper): f_lo
            # includes the per-dispatch overhead, so the initial K
            # sizing can cap too early and park the delta under the
            # no-signal threshold (review finding)
            f_hi, itf = _widen_k(timed_f, f_lo, f_hi, itf, "fixed-pack",
                                 budget_frac=0.4)
            f_delta = f_hi - f_lo
            if f_delta > 0.05:
                f_per_batch = f_delta / (itf - 1)
                f_rps = n_req / f_per_batch
                fixed = {
                    "pack": "bench_fixtures/pack_r03 (frozen r03 "
                            "ruleset: conf tree + r03 sigpack generator)",
                    "rules": int(cr_fix.n_rules),
                    "words": int(cr_fix.tables.n_words),
                    "scan_impl": "pair",
                    "req_per_s": round(f_rps, 1),
                    "platform": platform,
                    "r03_reference": R03_REFERENCE,
                }
                # pair-vs-pair only: comparing the fixed pack's pair
                # rate against another impl's live rate would conflate
                # impl choice with pack size (review finding)
                cur_pair = impl_stats.get("pair")
                if platform == "cpu" and cur_pair:
                    fixed["attribution"] = (
                        "frozen 1405-rule r03 pack on current code: %.0f "
                        "req/s vs r03's measured %.0f -> code delta "
                        "%.2fx; current %d-rule pack: %.0f req/s -> "
                        "pack-size delta %.2fx; the r03->r04 CPU "
                        "regression decomposes into exactly these two "
                        "factors"
                        % (f_rps, R03_REFERENCE["req_per_s"],
                           f_rps / R03_REFERENCE["req_per_s"],
                           cr.n_rules, cur_pair, f_rps / cur_pair))
                result["fixed_pack"] = fixed
                _HEADLINE = dict(result)
                log("fixed-pack (1405 rules, pair): %.2f ms/batch -> "
                    "%.0f req/s%s" % (f_per_batch * 1e3, f_rps,
                                      "; " + fixed.get("attribution", "")))
            else:
                log("fixed-pack leg: no signal (delta %.1f ms at K=%d)"
                    % (f_delta * 1e3, itf))
    except Exception as e:
        log("fixed-pack leg failed (non-fatal): %r" % (e,))

    # pack-scale leg (ISSUE 6): req/s vs synthetic pack size, the
    # sublinearity gate for the pack-size-invariant scan kernel.  Runs
    # inline only when the watchdog budget clearly allows; the
    # standalone `python bench.py --pack-scale` mode always runs it and
    # writes reports/PACKSCALE.json.
    try:
        if _budget_left() > 300:
            ps = run_pack_scale()
            result["pack_scale"] = {
                "scale_2x": ps.get("scale_2x"),
                "points": [{k: p[k] for k in
                            ("scale", "rules", "words", "req_per_s")}
                           for p in ps.get("points", [])],
                "artifact": "reports/PACKSCALE.json",
            }
            _HEADLINE = dict(result)
        else:
            log("pack-scale leg skipped inline (%.0fs budget left); "
                "run `python bench.py --pack-scale` for the full curve "
                "(reports/PACKSCALE.json carries the last run)"
                % _budget_left())
    except Exception as e:
        log("pack-scale leg failed (non-fatal): %r" % (e,))

    # retune leg (ISSUE 15): profile-guided pack retuning A/B — static
    # vs profile-priced pack crossed with the cross-cycle verdict cache,
    # recorded as the `retune` block.
    # The profile-priced pack LOSING to the static pricing on the mixed
    # corpus means the telemetry→compiler loop is feeding the pricer
    # garbage — warned about LOUDLY, never silently recorded.
    try:
        if _budget_left() > 240:
            from ingress_plus_tpu.utils.microbench import bench_retune

            # 1024-request replay minimum: a 512-request profile's
            # candidate-rate estimates are noisy enough to misprice the
            # re-tiering (measured: the retuned pack LOST 0.84x at 512,
            # won 1.03x/1.47x at 1024 on the same rules).
            rb = bench_retune(n_req=1024, iters=3)
            result["retune"] = rb
            mixed = rb.get("mixed/retuned/nocache", {})
            floodc = rb.get("flood/retuned/cache", {})
            if mixed.get("speedup_vs_static", 1.0) < 1.0:
                log("=" * 64)
                log("RETUNE WARNING: the profile-priced pack LOSES to "
                    "static pricing on the mixed corpus (%.3fx) — the "
                    "measured profile is mispricing the reduction "
                    "(profile %s); audit /rules/stats?format=profile "
                    "before feeding it to tools/retune.py"
                    % (mixed.get("speedup_vs_static", 0.0),
                       rb.get("profile_hash")))
                log("=" * 64)
            else:
                log("retune: profile-priced pack %.2fx on mixed, "
                    "%.2fx with verdict cache on flood (profile %s)"
                    % (mixed.get("speedup_vs_static", 0.0),
                       floodc.get("speedup_vs_static", 0.0),
                       rb.get("profile_hash")))
            _HEADLINE = dict(result)
        else:
            log("retune leg skipped inline (%.0fs budget left); run "
                "`python -m ingress_plus_tpu.utils.microbench --retune` "
                "for the A/B" % _budget_left())
    except Exception as e:
        log("retune leg failed (non-fatal): %r" % (e,))

    # per-bucket MB/s diagnostics (stderr only; never fatal)
    try:
        k_diag = 33

        # buckets passed as jit args (same constant-folding hazard as
        # detect_k — see make_detect_k docstring)
        @functools.partial(jax.jit, static_argnames=("k",))
        def one_bucket_k(k, tabs, tok, lens, rreq, rsv):
            W = tabs.scan.n_words

            def body(i, carry):
                acc, state, match = carry
                rh, ch, sc, match, state = detect_rows(
                    tabs, tok, lens, rreq, rsv,
                    num_requests=n_req, state=state, match=match)
                return (acc + match.sum() + rh.sum().astype(jnp.uint32),
                        state, match)

            z = jnp.zeros((tok.shape[0], W), jnp.uint32)
            acc, _, _ = jax.lax.fori_loop(
                0, k, body, (jnp.zeros((), jnp.uint32), z, z))
            return acc

        for (tok, lens, rreq, rsv) in device_buckets:
            nrows, edge = tok.shape
            dt = k_diff_time(
                lambda k, rep: one_bucket_k(k, tables, tok, lens, rreq, rsv),
                k_diag)
            if dt <= 0:
                log("bucket %5dB x %4d rows: no signal (K-diff <= 0,"
                    " jitter > compute)" % (edge, nrows))
            else:
                log("bucket %5dB x %4d rows: %7.2f us/batch, %8.1f MB/s"
                    % (edge, nrows, dt * 1e6, nrows * edge / dt / 1e6))
    except Exception as e:
        log("per-bucket diagnostics failed (non-fatal): %r" % (e,))

    # quality cross-check on a sample (full pipeline incl. confirm, CPU)
    sample = corpus[:512]
    verdicts = pipeline.detect([lr.request for lr in sample])
    tp = sum(1 for lr, v in zip(sample, verdicts) if lr.is_attack and v.attack)
    fn = sum(1 for lr, v in zip(sample, verdicts) if lr.is_attack and not v.attack)
    fp = sum(1 for lr, v in zip(sample, verdicts) if not lr.is_attack and v.attack)
    log("quality sample (%d req): tp=%d fn=%d fp=%d"
        % (len(sample), tp, fn, fp))
    result["quality_sample"] = {"requests": len(sample), "tp": tp,
                                "fn": fn, "fp": fp}
    # learned-scorer quality leg (ISSUE 8, docs/LEARNED_SCORING.md):
    # per-family precision/recall + the fixed-vs-learned comparison at
    # the calibrated threshold — the ModSec-Learn claim as a measured
    # block in the driver artifact, never an assertion.  A deterministic
    # seeded retrain on the golden corpus, so the block reproduces.
    try:
        from ingress_plus_tpu.utils.evalf1 import evaluate as _f1_eval
        from ingress_plus_tpu.utils.export_corpus import (
            build_feature_dataset)
        from ingress_plus_tpu.learn.train import train_from_dataset

        t_sc = time.time()
        ds = build_feature_dataset(n=1024, seed=20260729,
                                   ruleset=pipeline.ruleset)
        head = train_from_dataset(ds)
        rep = _f1_eval(n=1024, batch=128, seed=20260729,
                       pipeline=pipeline, warm=False, scoring_head=head)
        result["scorer_quality"] = {
            "head_version": head.version,
            "threshold": round(float(head.threshold), 6),
            "per_family_precision": rep.per_family,
            "per_class_recall": rep.per_class_recall,
            "comparison": rep.scorer_comparison,
            "train_eval_s": round(time.time() - t_sc, 1),
        }
        cmpb = rep.scorer_comparison or {}
        log("scorer quality: fixed fp=%s learned fp=%s new_fn=%s "
            "(threshold %.3f)"
            % (cmpb.get("fixed", {}).get("fp"),
               cmpb.get("learned", {}).get("fp"),
               cmpb.get("new_fn_vs_fixed"), head.threshold))
        if cmpb.get("new_fn_vs_fixed", 0):
            log("WARNING: learned head LOST attacks the fixed weights "
                "caught — the zero-new-FN calibration did not hold on "
                "this corpus")
    except Exception as e:
        log("scorer quality leg failed (non-fatal): %r" % (e,))
    # the full adversarial eval (non-self-referential: public classic
    # payloads x encoding evasions + 10k benign requests) is pinned by
    # tests/test_quality.py and written to reports/QUALITY.json — embed
    # its summary so the driver artifact carries the quality story
    try:
        qpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "reports", "QUALITY.json")
        with open(qpath) as f:
            q = json.load(f)
        result["quality"] = {
            "evasion_detection_rate": q["evasion"]["detection_rate"],
            "evasion_total": q["evasion"]["total"],
            "benign_fp_rate": q["benign"]["fp_rate"],
            "benign_total": q["benign"]["total"],
            "method": q.get("method", ""),
            "artifact": "reports/QUALITY.json",
        }
        _HEADLINE = dict(result)
    except Exception as e:
        log("quality artifact embed failed (non-fatal): %r" % (e,))

    # evasion-closure leg (ISSUE 17, docs/ANALYSIS.md "Evasion
    # analysis"): the seeded mutation harness replays the golden corpus
    # re-encoded per evasion family through detect_cpu_only — per-family
    # retention lands in the driver artifact next to the quality story.
    # A smaller corpus than the evasiongate CI run (this is a bench leg,
    # not the gate); the gate's full numbers live in
    # reports/EVASION.json.
    try:
        from ingress_plus_tpu.utils.evasion import mutation_harness

        t_ev = time.time()
        ev = mutation_harness(pipeline, n=600, attack_fraction=0.4)
        result["evasion"] = {
            "min_retention": ev["min_retention"],
            "per_family_retention": {
                fam: st["retention"]
                for fam, st in ev["families"].items()},
            "base_detected": ev["corpus"]["base_detected"],
            "escapes": sum(st["escapes_total"]
                           for st in ev["families"].values()),
            "harness_s": round(time.time() - t_ev, 1),
            "artifact": "reports/EVASION.json",
        }
        log("evasion retention: min %.3f over %d families (%d escapes)"
            % (ev["min_retention"], len(ev["families"]),
               result["evasion"]["escapes"]))
        _HEADLINE = dict(result)
    except Exception as e:
        log("evasion leg failed (non-fatal): %r" % (e,))

    # added-latency leg (BASELINE.md north star row 2: <2ms p99 added):
    # C++ loadgen -> C++ sidecar -> in-process serve loop on THIS
    # process's backend — the full production boundary chain, measured,
    # never estimated.  Never fatal; the throughput headline above is
    # already stashed.
    try:
        result.update(
            run_latency_leg(cr, result.get("scan_impl", "pair"), device))
        _HEADLINE = dict(result)
    except Exception as e:
        log("latency leg failed (non-fatal): %r" % (e,))
    if "rule_stats" not in result:
        # mirror the stage_breakdown contract: the absence of the
        # detection-efficiency block must be visible in the round log
        log("WARNING: BENCH json carries NO rule_stats block — "
            "per-family false-candidate rate and padding-waste ratio "
            "are unreported this round")
    return result


def scrape_stage_breakdown(serve) -> dict | None:
    """Serve-loop /metrics histograms → the BENCH json ``stage_breakdown``
    object: per-stage p50/p99 µs (queue/prep/scan/confirm/batch/e2e) plus
    a sum-check decomposing the serve-side end-to-end percentiles.

    Importable and runnable WITHOUT a running server (the tier-1 smoke
    test drives it on an in-process ServeLoop): ``serve`` is anything
    with a ``_metrics_text() -> str``.  Returns None when the histograms
    are missing or malformed — callers must treat that as a LOUD warning
    (ISSUE 1 satellite), never a silent absence."""
    from ingress_plus_tpu.utils.trace import stage_breakdown_from_metrics

    sb = stage_breakdown_from_metrics(serve._metrics_text())
    if not sb:
        return None
    out = {s: sb[s] for s in ("queue", "prep", "scan", "confirm",
                              "batch", "e2e") if s in sb}
    if not out:
        return None
    # decomposition check: queue+prep+scan+confirm should account for
    # the serve-side e2e percentiles within slack (stream work and queue
    # ops are the unattributed remainder)
    if "e2e" in out:
        check = {}
        for p in ("p50_us", "p99_us"):
            total = sum(out[s].get(p, 0.0)
                        for s in ("queue", "prep", "scan", "confirm")
                        if s in out)
            check["stage_sum_%s" % p] = round(total, 1)
            e2e = out["e2e"].get(p, 0.0)
            if e2e:
                check["stage_sum_over_e2e_%s" % p] = round(total / e2e, 3)
        out["sum_check"] = check
    return out


def run_latency_leg(cr, scan_impl: str, device: dict,
                    n_requests: int = 1024) -> dict:
    """p50/p99 verdict latency through loadgen -> sidecar -> serve loop,
    the serve loop in THIS process on ``device`` (utils/platform
    device_block — stamped on the leg).

    "Added latency" because the proxy (nginx module) waits exactly this
    round-trip before forwarding; everything else in the request path is
    untouched.  Measured at LOW concurrency (2 conns x 2 inflight) —
    the 2ms budget is per-request added cost at sane load, not the
    queueing delay of a saturated box (saturation p99 is the throughput
    leg's business).
    """
    import subprocess
    import tempfile
    import socket as socketmod

    platform = device["platform"]
    repo = os.path.dirname(os.path.abspath(__file__))
    sidecar_dir = os.path.join(repo, "native", "sidecar")
    subprocess.run(["make", "-s", "-C", sidecar_dir],
                   capture_output=True, timeout=180, check=True)

    import asyncio

    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.serve.server import ServeLoop
    from ingress_plus_tpu.utils.export_corpus import export

    tmp = tempfile.mkdtemp(prefix="ipt_lat_")
    srv_sock = os.path.join(tmp, "srv.sock")
    side_sock = os.path.join(tmp, "side.sock")
    pipeline = DetectionPipeline(cr, mode="block", scan_impl=scan_impl)
    batcher = Batcher(pipeline)
    serve = ServeLoop(batcher, srv_sock)
    loop = asyncio.new_event_loop()

    def runner():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(serve.start())
        loop.run_forever()

    t = threading.Thread(target=runner, daemon=True, name="ipt-lat-serve")
    t.start()

    def wait_sock(path, timeout_s=60):
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if os.path.exists(path):
                try:
                    s = socketmod.socket(socketmod.AF_UNIX)
                    s.connect(path)
                    s.close()
                    return True
                except OSError:
                    pass
            time.sleep(0.05)
        return False

    sidecar = None
    try:
        if not wait_sock(srv_sock):
            raise RuntimeError("serve loop socket never appeared")
        sidecar = subprocess.Popen(
            [os.path.join(sidecar_dir, "sidecar"), "--listen", side_sock,
             "--upstream", srv_sock, "--deadline-ms", "30000"],
            stderr=subprocess.DEVNULL)
        if not wait_sock(side_sock):
            raise RuntimeError("sidecar socket never appeared")
        corpus_path = os.path.join(tmp, "c.bin")
        export(corpus_path, n=512, seed=9, attack_fraction=0.2)
        loadgen = os.path.join(sidecar_dir, "loadgen")
        # warmup pass compiles the serving shapes (first-dispatch XLA
        # compile would otherwise land in p99); same concurrency profile
        # as the measurement so the same batch geometries are hit
        subprocess.run(
            [loadgen, "--socket", side_sock, "--corpus", corpus_path,
             "--connections", "2", "--inflight", "2",
             "--requests", "384"],
            capture_output=True, timeout=300)
        # the stage histograms must describe ONLY the measured pass —
        # drop the warmup's first-dispatch XLA compile observations.
        # The cumulative PipelineStats stage counters have no reset, so
        # baseline them here for the confirm_plane share (review catch:
        # lifetime totals would fold the warmup's compile wall into the
        # denominator and misstate the measured pass's confirm share)
        batcher.reset_latency_observations()
        _ps = batcher.pipeline.stats
        stage_base = (_ps.engine_us, _ps.confirm_us, _ps.prep_us,
                      _ps.confirm_memo_hits, _ps.confirm_memo_misses)
        out = subprocess.run(
            [loadgen, "--socket", side_sock, "--corpus", corpus_path,
             "--connections", "2", "--inflight", "2",
             "--requests", str(n_requests)],
            capture_output=True, text=True, timeout=300)
        if out.returncode != 0:
            raise RuntimeError("loadgen rc=%d: %s"
                               % (out.returncode, out.stderr[-300:]))
        r = json.loads(out.stdout)
        log("latency leg: p50=%dus p99=%dus rps=%.0f fail_open=%d (%s)"
            % (r["p50_us"], r["p99_us"], r["rps"], r["fail_open"],
               "loadgen->sidecar->serve"))
        lat = {
            "added_latency_p50_us": r["p50_us"],
            "added_latency_p99_us": r["p99_us"],
            "latency_leg": {
                "path": "loadgen->sidecar->serve(%s)" % platform,
                # per-leg backend tag (ISSUE 13 satellite)
                **device,
                "scan_impl": scan_impl,
                "requests": r["requests"], "rps": r["rps"],
                "p90_us": r["p90_us"], "p999_us": r["p999_us"],
                "fail_open": r["fail_open"],
                "vs_2ms_budget": round(r["p99_us"] / 2000.0, 3),
            },
        }
        # chain-overhead pass: the SAME boundary chain with mode-off
        # frames (serve loop answers without touching the pipeline) —
        # isolates framing/IPC/event-loop cost from scan compute
        try:
            off_path = os.path.join(tmp, "c_off.bin")
            export(off_path, n=512, seed=9, attack_fraction=0.2, mode=0)
            out2 = subprocess.run(
                [loadgen, "--socket", side_sock, "--corpus", off_path,
                 "--connections", "2", "--inflight", "2",
                 "--requests", str(n_requests)],
                capture_output=True, text=True, timeout=120)
            if out2.returncode == 0:
                c = json.loads(out2.stdout)
                log("chain overhead (mode off): p50=%dus p99=%dus"
                    % (c["p50_us"], c["p99_us"]))
                lat["chain_overhead_p50_us"] = c["p50_us"]
                lat["chain_overhead_p99_us"] = c["p99_us"]
        except Exception as e:
            log("chain-overhead pass failed (non-fatal): %r" % (e,))
        # stage-level latency attribution (ISSUE 1): decompose the
        # measured p50/p99 by pipeline stage from the serve loop's own
        # histograms.  Missing/malformed is LOUD, never silent — the
        # 6.4x budget miss is unexplainable without it.
        try:
            sb = scrape_stage_breakdown(serve)
        except Exception as e:
            sb = None
            log("WARNING: stage_breakdown scrape raised (%r)" % (e,))
        if not sb:
            log("WARNING: latency leg has NO stage_breakdown — the "
                "/metrics stage histograms are missing or malformed; "
                "this round's p99 cannot be decomposed by stage")
        else:
            lat["latency_leg"]["stage_breakdown"] = sb
            log("stage breakdown: " + ", ".join(
                "%s p50=%.0f p99=%.0f" % (s, v["p50_us"], v["p99_us"])
                for s, v in sb.items() if s != "sum_check"))
        # detection-plane telemetry (ISSUE 3): per-family false-
        # candidate rate + padding-waste gauges from the pipeline's
        # RuleStats, mirroring the stage_breakdown convention —
        # missing/None is a LOUD warning, never silently absent
        from ingress_plus_tpu.models.rule_stats import bench_block
        try:
            rsb = bench_block(batcher.pipeline)
        except Exception as e:
            rsb = None
            log("WARNING: rule_stats collection raised (%r)" % (e,))
        if not rsb:
            log("WARNING: latency leg has NO rule_stats — per-family "
                "false-candidate rate and padding-waste are "
                "unmeasured; the detection-efficiency axis is missing "
                "from this round's BENCH json")
        else:
            lat["rule_stats"] = rsb
            log("rule_stats: fc_rate=%s pad_waste=%s fill=%s "
                "runtime_dead=%s"
                % (rsb.get("false_candidate_rate"),
                   rsb.get("padding_waste_ratio"),
                   rsb.get("dispatch_fill"), rsb.get("runtime_dead")))
        # confirm plane (docs/CONFIRM_PLANE.md): the confirm stage's
        # share of pipeline time plus the work-reduction attribution
        # (quick-reject skip rate, flood-memo hits) — the serialized
        # residue the parallel confirm plane exists to shrink.
        # Missing/None is a LOUD warning like every other block.
        try:
            ps = batcher.pipeline.stats
            d_engine = ps.engine_us - stage_base[0]
            d_confirm = ps.confirm_us - stage_base[1]
            d_prep = ps.prep_us - stage_base[2]
            d_stages = d_engine + d_confirm + d_prep
            qr = batcher.pipeline.rule_stats.quick_reject_summary()
            cp = {
                "confirm_share": (round(d_confirm / d_stages, 4)
                                  if d_stages > 0 else None),
                "confirm_us": d_confirm,
                "confirm_workers":
                    batcher.pipeline.confirm_pool.n_workers,
                "quick_reject": qr,
                "memo_hits": ps.confirm_memo_hits - stage_base[3],
                "memo_misses": ps.confirm_memo_misses - stage_base[4],
            }
        except Exception as e:
            cp = None
            log("WARNING: confirm-plane collection raised (%r)" % (e,))
        if not cp or cp["confirm_share"] is None:
            log("WARNING: latency leg has NO confirm_plane block — the "
                "confirm-stage share of e2e is unmeasured this round")
        else:
            lat["confirm_plane"] = cp
            log("confirm plane: share=%.2f qr_skip_rate=%s "
                "memo_hits=%d workers=%d"
                % (cp["confirm_share"],
                   cp["quick_reject"].get("skip_rate"),
                   cp["memo_hits"], cp["confirm_workers"]))
        # cycle flight recorder (ISSUE 12, docs/OBSERVABILITY.md):
        # the MEASURED pipeline-overlap block — scan↔confirm overlap
        # fraction, per-lane idle share, drain occupancy, critical-path
        # ranking, serialized residue.  The recorder was reset with the
        # latency observations, so this describes only the measured
        # pass.  Missing is LOUD; a measured contradiction of the
        # PR 7/9 overlap claims (or one thread holding >60% of the
        # critical path) is LOUDER.
        from ingress_plus_tpu.utils.overlap import check_claims, collect
        po = collect(batcher)
        if not po:
            log("WARNING: latency leg has NO pipeline_overlap block — "
                "the flight recorder captured no cycles; the overlap "
                "structure is unmeasured this round")
        else:
            lat["pipeline_overlap"] = po
            top = (po["serialized_residue"] or [{}])[0]
            log("pipeline overlap: scan<->confirm=%s drain_occ=%.3f "
                "critical=%s bounding=%s(%.2f excl)"
                % (po["scan_confirm_overlap"], po["drain_occupancy"],
                   "/".join("%s:%d" % kv
                            for kv in po["critical_path"].items()),
                   top.get("thread"), top.get("exclusive_share", 0.0)))
            # measured host_prep share (ISSUE 13): the stage-level
            # ranking the raw-byte offload is judged by — check_claims
            # below warns when host_prep ranks above the device lanes
            ss = po.get("stage_shares") or {}
            log("stage shares (excl): " + " ".join(
                "%s=%.3f" % (k, v.get("exclusive_share", 0.0))
                for k, v in ss.items()))
            for w in check_claims(po):
                log("=" * 64)
                log("PIPELINE OVERLAP WARNING: %s" % w)
                log("=" * 64)
        # fail-safe plane sanity (docs/ROBUSTNESS.md): the CLEAN latency
        # leg must never shed, degrade, or trip the breaker — any of
        # those here means the fail-safe layer is costing the happy
        # path, which is a regression the p99 alone could hide
        rb = {
            "shed": dict(batcher.pipeline.stats.shed),
            "degraded_verdicts": batcher.pipeline.stats.degraded,
            "breaker": batcher.breaker.snapshot()["state"],
            "breaker_trips": batcher.breaker.snapshot()["trips"],
            "watchdog_hangs": batcher.stats.hangs,
        }
        lat["latency_leg"]["robustness"] = rb
        if (rb["shed"] or rb["degraded_verdicts"]
                or rb["breaker"] != "closed" or rb["watchdog_hangs"]):
            log("WARNING: fail-safe plane activated on the CLEAN "
                "latency leg (%s) — bounded admission / breaker / "
                "brownout are interfering with the happy path" % rb)
        return lat
    finally:
        if sidecar is not None:
            sidecar.terminate()

        async def _shutdown():
            for s in serve._servers:
                s.close()

        try:
            asyncio.run_coroutine_threadsafe(_shutdown(), loop).result(5)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        batcher.close()


_EMIT_LOCK = threading.Lock()
_EMITTED = False
_HEADLINE = None  # measured result stashed before the diagnostics tail
_WATCHDOG_TIMER = None
_WATCHDOG_ARMED_AT = None
_WATCHDOG_BUDGET = float(os.environ.get("BENCH_WATCHDOG_S", "540"))


def emit(result: dict) -> None:
    """Print the ONE JSON line, exactly once (the watchdog thread and the
    normal path can race at the deadline boundary)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        print(json.dumps(result), flush=True)


def _watchdog_fire() -> None:
    """Deadline: a headline that was measured is emitted (the
    diagnostics tail overran); with nothing measured there is no line
    to print.  Either way the run did not end cleanly: exit 3."""
    if _HEADLINE is not None:
        result = dict(_HEADLINE)
        result["note"] = ("watchdog fired during post-measurement"
                         " diagnostics; headline value is complete")
        emit(result)
    else:
        log("watchdog: bench exceeded %.0fs with nothing measured (hung "
            "backend init or dispatch?)" % _WATCHDOG_BUDGET)
    sys.stderr.flush()
    os._exit(3)


def _arm_watchdog() -> None:
    """(Re)start the deadline clock."""
    global _WATCHDOG_TIMER, _WATCHDOG_ARMED_AT
    if _WATCHDOG_TIMER is not None:
        _WATCHDOG_TIMER.cancel()
    _WATCHDOG_ARMED_AT = time.time()
    _WATCHDOG_TIMER = threading.Timer(_WATCHDOG_BUDGET, _watchdog_fire)
    _WATCHDOG_TIMER.daemon = True
    _WATCHDOG_TIMER.start()


def _budget_left() -> float:
    """Seconds until the watchdog fires — the measurement loop sizes its
    iteration counts against this so a slow platform (the 2k-rule pack
    on an explicit CPU run) still measures EVERY impl instead of blowing
    the whole budget on the first one."""
    if _WATCHDOG_ARMED_AT is None:
        return _WATCHDOG_BUDGET
    return max(0.0, _WATCHDOG_BUDGET - (time.time() - _WATCHDOG_ARMED_AT))


#: standalone CPU-pinned legs: flag -> (runner, pins CPU in this process)
_CPU_LEGS = {
    "--mesh-scale": (run_mesh_scale, False),   # its children pin
    "--tenant-iso": (run_tenant_iso, True),
    "--fleet": (run_fleet, True),
    "--fleet-obs": (run_fleet_obs, True),
    "--pack-scale": (run_pack_scale,
                     os.environ.get("BENCH_PLATFORM", "cpu") == "cpu"),
}


def main() -> int:
    """One JSON line on stdout and exit 0 when something was measured;
    otherwise the traceback on stderr, nothing on stdout, and a
    non-zero exit — a bench that found no chip, or whose run died,
    reports no number.  A watchdog thread covers a hung backend."""
    import traceback

    from ingress_plus_tpu.utils.platform import (
        enable_compile_cache,
        force_cpu_devices,
    )

    enable_compile_cache()
    point = [a.split("=", 1)[1] for a in sys.argv
             if a.startswith("--mesh-point=")]
    if point:
        mesh_point_main(int(point[0]))
        return 0
    _arm_watchdog()
    leg = next((f for f in _CPU_LEGS if f in sys.argv), None)
    try:
        if leg is not None:
            runner, pin_cpu = _CPU_LEGS[leg]
            if pin_cpu:
                force_cpu_devices(1)
            result = runner()
            result.setdefault("platform", "cpu")
            result["cpu_pinned_leg"] = leg
        else:
            result = run_bench()
    except BaseException as e:  # noqa: BLE001 — reported, then non-zero
        traceback.print_exc(file=sys.stderr)
        if _HEADLINE is None:
            return 1
        # died in the diagnostics tail only: the headline is complete
        result = dict(_HEADLINE)
        result["note"] = ("post-measurement diagnostics failed: %s: %s"
                          % (type(e).__name__, str(e)[:300]))
    finally:
        if _WATCHDOG_TIMER is not None:
            _WATCHDOG_TIMER.cancel()
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
