"""Microbenchmarks for the scan kernels on the current jax backend.

Usage:  python -m ingress_plus_tpu.utils.microbench [--batch 256] [--len 1024]

Prints MB/s scanned per configuration — the raw number behind the req/s
target (1KB average request ⇒ 100k req/s ≈ 100+ MB/s scanned per chip
counting normalization variants).
"""

from __future__ import annotations

import argparse
import time

import jax

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
from ingress_plus_tpu.ops.scan import ScanTables


def best_time(call, k: int, n: int = 2) -> float:
    """Best-of-n wall time of ``call(k, rep)`` after warming its compile.

    The canonical timing primitive (bench.py and every bench_* below
    share THIS copy).  ``rep`` increments per invocation so callers can
    feed fresh PRNG keys; best-of because one jittery dispatch otherwise
    skews (or even negates) a K-difference built from single samples."""
    jax.block_until_ready(call(k, 0))  # warm the compile
    best = float("inf")
    for i in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(call(k, 1 + i))
        best = min(best, time.perf_counter() - t0)
    return best


def k_diff_time(call, k: int, n: int = 2) -> float:
    """Per-iteration K-difference (t(K=k) - t(K=1)) / (k-1), built on
    best_time.  May legitimately return <= 0 when dispatch jitter swamps
    the compute delta — callers must treat that as NO SIGNAL (widen K or
    skip the report), never as a throughput."""
    return (best_time(call, k, n) - best_time(call, 1, n)) / (k - 1)


def bench_scan(tables: ScanTables, batch: int, length: int, gather: str,
               iters: int = 65, unroll: int = 16) -> float:
    """Returns MB/s, measured as the K-scan in-dispatch difference: K
    chained scans inside ONE jit dispatch (tokens generated on-device,
    tiny scalar output), reported as (t(K=iters) - t(K=1)) / (iters - 1)
    so per-dispatch overhead cancels.  iters must be large enough that
    the compute delta dwarfs dispatch jitter."""
    import functools

    import jax.numpy as jnp

    from ingress_plus_tpu.ops.scan import scan_bytes

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_k(key, k):
        tokens = jax.random.randint(key, (batch, length), 32, 127,
                                    dtype=jnp.int32)
        lengths = jnp.full((batch,), length, dtype=jnp.int32)

        def body(i, carry):
            s, m = carry
            m, s = scan_bytes(tables, tokens, lengths, state=s, match=m,
                              unroll=unroll, gather=gather)
            return (s, m)

        s = jnp.zeros((batch, tables.n_words), jnp.uint32)
        s, m = jax.lax.fori_loop(0, k, body, (s, jnp.zeros_like(s)))
        return m[0, 0]

    per_scan = k_diff_time(
        lambda k, rep: scan_k(jax.random.PRNGKey(100 * k + rep), k), iters)
    return batch * length / per_scan / 1e6


def bench_pairs(tables: ScanTables, batch: int, length: int,
                iters: int = 65, unroll: int = 16) -> float:
    """MB/s for the class-pair-stride scan (ops/scan.py scan_pairs),
    K-diff timed like bench_scan."""
    import functools

    import jax.numpy as jnp

    from ingress_plus_tpu.ops.scan import scan_pairs

    @functools.partial(jax.jit, static_argnames=("k",))
    def scan_k(key, k):
        tokens = jax.random.randint(key, (batch, length), 32, 127,
                                    dtype=jnp.int32)
        lengths = jnp.full((batch,), length, dtype=jnp.int32)

        def body(i, carry):
            s, m = carry
            m, s = scan_pairs(tables, tokens, lengths, state=s, match=m,
                              unroll=unroll)
            return (s, m)

        s = jnp.zeros((batch, tables.n_words), jnp.uint32)
        m = jnp.zeros((batch, tables.n_words), jnp.uint32)
        s, m = jax.lax.fori_loop(0, k, body, (s, m))
        return m.sum()

    per = k_diff_time(
        lambda k, rep: scan_k(jax.random.PRNGKey(100 * k + rep), k), iters)
    return batch * length / per / 1e6


def bench_confirm(n_req: int = 1024, iters: int = 5,
                  flood_dup: int = 4) -> dict:
    """Confirm-stage microbench (docs/CONFIRM_PLANE.md): full CPU
    ``pipeline.detect`` over the deterministic corpus with the
    quick-reject literals and the flood memo toggled independently, so
    the work-reduction win is reproducible in isolation from the serve
    plane.  Two corpora: the standard mixed corpus (quick-reject's
    home turf — unique requests, candidate-but-no-hit walks) and a
    flood corpus (each request repeated ``flood_dup`` times, shuffled —
    the replayed-flood shape the per-cycle memo exists for).  One
    pipeline serves every config — toggling attributes instead of
    rebuilding keeps the XLA executables warm, so config deltas are
    confirm-stage deltas."""
    import random

    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.utils.corpus import generate_corpus

    cr = compile_ruleset(load_bundled_rules())
    corpus = generate_corpus(n=n_req, attack_fraction=0.2, seed=42)
    reqs = [lr.request for lr in corpus]
    flood = [lr.request for lr in corpus[:max(1, n_req // flood_dup)]
             ] * flood_dup
    random.Random(7).shuffle(flood)

    pipe = DetectionPipeline(cr, mode="block")
    # chain links quick-reject too — the toggle must strip them as
    # well or the "off" baseline under-reports the qr win
    rules = [r for c in pipe.confirms for r in c.walk_chain()]
    saved = [(c.qr_literals, c._qr_rule_ok) for c in rules]

    def set_qr(on: bool) -> None:
        for c, (lits, ok) in zip(rules, saved):
            c.qr_literals = lits if on else None
            c._qr_rule_ok = ok if on else False

    # warm every compile tier + the cross-request transform memo once;
    # later configs all start from the same warm state
    pipe.detect(reqs[:256])
    pipe.detect(reqs)
    pipe.detect(flood)

    out: dict = {"n_req": n_req, "iters": iters, "flood_dup": flood_dup}
    for corpus_tag, batch in (("mixed", reqs), ("flood", flood)):
        base_rps = None
        for tag, qr, memo in (("off", False, False),
                              ("qr", True, False),
                              ("memo", False, True),
                              ("qr+memo", True, True)):
            set_qr(qr)
            pipe.confirm_memo_entries = 4096 if memo else 0
            best, conf_us, memo_hits = float("inf"), 0, 0
            for _ in range(iters):
                c0 = pipe.stats.confirm_us
                m0 = pipe.stats.confirm_memo_hits
                t0 = time.perf_counter()
                pipe.detect(batch)
                dt = time.perf_counter() - t0
                if dt < best:
                    best = dt
                    conf_us = pipe.stats.confirm_us - c0
                    memo_hits = pipe.stats.confirm_memo_hits - m0
            rps = len(batch) / best
            if tag == "off":
                base_rps = rps
            rec = {"req_per_s": round(rps, 1),
                   "confirm_ms": round(conf_us / 1e3, 1),
                   "memo_hits": memo_hits,
                   "speedup_vs_off": round(rps / base_rps, 3)}
            out["%s/%s" % (corpus_tag, tag)] = rec
            print("corpus=%-5s config=%-8s %8.1f req/s  confirm=%7.1f ms"
                  "  memo_hits=%-6d speedup=%.3fx"
                  % (corpus_tag, tag, rps, rec["confirm_ms"], memo_hits,
                     rec["speedup_vs_off"]))
    set_qr(True)
    qr_summary = pipe.rule_stats.quick_reject_summary()
    out["quick_reject"] = qr_summary
    print("quick-reject coverage: %s/%s rx rules, skip_rate=%s"
          % (qr_summary["rules_with_literals"], qr_summary["rx_rules"],
             qr_summary["skip_rate"]))
    return out


def bench_retune(n_req: int = 1024, iters: int = 5, flood_dup: int = 4,
                 cache_entries: int = 65536) -> dict:
    """Profile-guided retuning A/B (ISSUE 15, docs/RETUNE.md): static
    vs profile-priced pack, crossed with the cross-cycle verdict cache
    off/on, over the same mixed + flood corpora as ``bench_confirm``.
    The profile is bootstrapped from a telemetry replay of the mixed
    corpus through the static pack — the exact loop tools/retune.py
    closes — so the delta is the measured value of closing it.  Each
    arm gets its own pipeline (the pack IS the variable; attribute
    toggling can't swap tables), warmed before timing."""
    import random

    from ingress_plus_tpu.compiler.profile import MeasuredProfile
    from ingress_plus_tpu.compiler.reduce import ReductionConfig
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.utils.corpus import generate_corpus

    rules = load_bundled_rules()
    static_cr = compile_ruleset(rules)
    corpus = generate_corpus(n=n_req, attack_fraction=0.2, seed=42)
    reqs = [lr.request for lr in corpus]
    flood = [lr.request for lr in corpus[:max(1, n_req // flood_dup)]
             ] * flood_dup
    random.Random(7).shuffle(flood)

    # telemetry replay → profile → retuned pack (the closed loop)
    prof_pipe = DetectionPipeline(static_cr, mode="block")
    for i in range(0, len(reqs), 64):
        prof_pipe.detect(reqs[i:i + 64])
    prof = MeasuredProfile.from_rule_stats(prof_pipe.rule_stats)
    retuned_cr = compile_ruleset(
        rules, reduction=ReductionConfig(profile=prof))

    out: dict = {"n_req": n_req, "iters": iters, "flood_dup": flood_dup,
                 "profile_hash": prof.content_hash(),
                 "static_fingerprint": static_cr.version,
                 "retuned_fingerprint": retuned_cr.version,
                 "reduction": retuned_cr.reduction}
    base: dict = {}
    for pack_tag, cr in (("static", static_cr), ("retuned", retuned_cr)):
        for cache_tag, cache in (("nocache", 0),
                                 ("cache", cache_entries)):
            pipe = DetectionPipeline(cr, mode="block",
                                     confirm_cache_entries=cache)
            pipe.detect(reqs[:256])
            pipe.detect(reqs)
            pipe.detect(flood)
            if pipe.confirm_cache is not None:
                # warmup hits would flatter the timed runs unevenly
                pipe.confirm_cache.invalidate("bench_warm")
            for corpus_tag, batch in (("mixed", reqs), ("flood", flood)):
                best, conf_us, hits = float("inf"), 0, 0
                for _ in range(iters):
                    c0 = pipe.stats.confirm_us
                    m0 = pipe.stats.confirm_memo_hits
                    t0 = time.perf_counter()
                    pipe.detect(batch)
                    dt = time.perf_counter() - t0
                    if dt < best:
                        best = dt
                        conf_us = pipe.stats.confirm_us - c0
                        hits = pipe.stats.confirm_memo_hits - m0
                key = "%s/%s/%s" % (corpus_tag, pack_tag, cache_tag)
                rps = len(batch) / best
                if pack_tag == "static" and cache_tag == "nocache":
                    base[corpus_tag] = rps
                rec = {"req_per_s": round(rps, 1),
                       "confirm_ms": round(conf_us / 1e3, 1),
                       "cache_hits": hits,
                       "speedup_vs_static": round(rps / base[corpus_tag],
                                                  3)}
                out[key] = rec
                print("corpus=%-5s pack=%-7s cache=%-7s %8.1f req/s  "
                      "confirm=%7.1f ms  hits=%-6d speedup=%.3fx"
                      % (corpus_tag, pack_tag, cache_tag, rps,
                         rec["confirm_ms"], hits,
                         rec["speedup_vs_static"]))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--len", dest="length", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--only", default=None,
                    choices=[None, "take", "onehot", "pair"])
    ap.add_argument("--platform", default=None, choices=[None, "cpu"],
                    help="force CPU in-process (same as JAX_PLATFORMS=cpu "
                         "in the environment)")
    ap.add_argument("--confirm", action="store_true",
                    help="confirm-stage microbench instead of the scan "
                         "sweep: quick-reject / flood-memo toggles over "
                         "full pipeline.detect (docs/CONFIRM_PLANE.md); "
                         "always CPU")
    ap.add_argument("--retune", action="store_true",
                    help="profile-guided retuning A/B (docs/RETUNE.md): "
                         "static vs profile-priced pack x verdict cache "
                         "off/on over mixed + flood corpora; always CPU")
    ap.add_argument("--reqs", type=int, default=1024,
                    help="corpus size for --confirm / --retune")
    args = ap.parse_args()

    from ingress_plus_tpu.utils.platform import (
        enable_compile_cache,
        force_cpu_devices,
    )

    if args.platform == "cpu" or args.confirm or args.retune:
        force_cpu_devices(1)
    enable_compile_cache()

    if args.confirm:
        # --iters defaults are tuned for the K-chained scan; a confirm
        # pass is a full corpus detect, so clamp to a sane wall budget
        bench_confirm(n_req=args.reqs, iters=max(2, min(args.iters, 5)))
        return

    if args.retune:
        import json

        out = bench_retune(n_req=args.reqs,
                           iters=max(2, min(args.iters, 5)))
        print(json.dumps(out, indent=2))
        return

    cr = compile_ruleset(load_bundled_rules())
    tables = ScanTables.from_bitap(cr.tables)
    from ingress_plus_tpu.utils.platform import device_block

    print("%s  W=%d words  rules=%d" % (
        device_block(), tables.n_words, cr.n_rules))
    for gather in ("take", "onehot", "pair"):
        if args.only and gather != args.only:
            continue
        for batch in (args.batch, args.batch * 4):
            try:
                if gather == "pair":
                    mbs = bench_pairs(tables, batch, args.length,
                                      args.iters)
                else:
                    mbs = bench_scan(tables, batch, args.length, gather,
                                     args.iters)
                print("gather=%-7s batch=%-5d len=%-5d  %8.1f MB/s"
                      % (gather, batch, args.length, mbs))
            except Exception as e:  # keep sweeping on OOM etc.
                print("gather=%-7s batch=%-5d FAILED: %s"
                      % (gather, batch, str(e)[:120]))


if __name__ == "__main__":
    main()
