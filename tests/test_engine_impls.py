"""Scan-implementation selection: pair/take/pallas must be
indistinguishable at the rule-hit level, and the auto-select must
install a working impl (VERDICT round-1: the Pallas kernel must sit in
the serving path, not beside it)."""

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
from ingress_plus_tpu.models.engine import DetectionEngine
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils.corpus import generate_corpus


@pytest.fixture(scope="module")
def ruleset():
    return compile_ruleset(load_bundled_rules())


def _verdict_tuple(v):
    return (v.attack, v.blocked, tuple(sorted(v.rule_ids)), v.score)


@pytest.mark.parametrize("impl", ["take", "pallas", "pallas2",
                                  "pallas3"])
def test_impl_verdict_parity_with_pair(ruleset, impl):
    """Every impl produces identical verdicts on a mixed corpus (pallas
    runs in interpret mode on the CPU test backend — same kernel code
    path as the TPU lowering)."""
    reqs = [lr.request for lr in generate_corpus(n=48, seed=11)]

    ref = DetectionPipeline(ruleset, mode="block", scan_impl="pair")
    want = [_verdict_tuple(v) for v in ref.detect(reqs)]

    p = DetectionPipeline(ruleset, mode="block", scan_impl=impl,
                          fail_open=False)
    p.engine.pallas_interpret = True
    got = [_verdict_tuple(v) for v in p.detect(reqs)]
    assert got == want


def test_autoselect_installs_fastest(ruleset):
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    # CPU backend: pallas excluded by default; both remaining impls run
    timings = eng.autoselect_scan_impl(B=32, L=64, n=1)
    assert set(timings) == {"pair", "take"}
    assert eng.scan_impl == min(timings, key=timings.get)
    assert all(t > 0 for t in timings.values())


def test_autoselect_candidate_that_raises_propagates(ruleset, monkeypatch):
    """A kernel the backend refuses stops the bake-off (and with it the
    server's start-up): it is never scored out of the race so that
    `pair` serves in silence."""
    import pytest

    from ingress_plus_tpu.ops import pallas_scan

    class Refused(pallas_scan.PallasScanner):
        def __call__(self, *a, **kw):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pallas_scan, "PallasScanner", Refused)
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        eng.autoselect_scan_impl(B=8, L=64, k=2, n=1, include_pallas=True)
    assert eng.bakeoff is None


def test_server_startup_fails_on_a_refused_kernel(tmp_path, monkeypatch):
    """--scan-impl auto on a backend that runs the kernels: a candidate
    raising at compile fails build_default_batcher instead of serving
    `pair`."""
    import pytest

    from ingress_plus_tpu.ops import pallas_scan
    from ingress_plus_tpu.serve import server
    from ingress_plus_tpu.utils import platform

    (tmp_path / "tiny.conf").write_text(
        'SecRule ARGS "@rx (?i)union\\s+select" "id:1,phase:2,block,'
        "severity:CRITICAL,tag:'attack-sqli'\"\n")

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pallas_scan, "_pallas_scan", refused)
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        server.build_default_batcher(rules_dir=str(tmp_path),
                                     warmup=False, scan_impl="auto")


def test_autoselect_placeable_only_skips_unplaceable_kernels(ruleset):
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    timings = eng.autoselect_scan_impl(B=8, L=64, k=2, n=1,
                                       include_pallas=True,
                                       placeable_only=True)
    assert set(timings) == {"pair", "take", "pallas3"}
    assert eng.bakeoff == timings


def test_scan_impl_survives_hot_swap(ruleset):
    from ingress_plus_tpu.serve.batcher import Batcher

    p = DetectionPipeline(ruleset, mode="block", scan_impl="take")
    b = Batcher(p, max_batch=8, max_delay_s=0.001)
    try:
        b.swap_ruleset(ruleset)
        assert b.pipeline.engine.scan_impl == "take"
        v = b.submit(Request(uri="/q?a=1+union+select+2")).result(timeout=60)
        assert v.attack
    finally:
        b.close()


# ------------------------- one transfer + one program per bucket (ISSUE 28)

def _three_tier_buckets(pipe, bodies: bool):
    """Packed buckets of a mixed batch that fills at least three L
    tiers; without ``bodies`` no row carries a body stream-variant (the
    traffic a head-only dispatch is for)."""
    reqs = []
    for i, size in enumerate((20, 90, 200, 400)):
        filler = ("lorem ipsum dolor " * 30)[:size]
        reqs.append(Request(
            method="GET",
            uri="/q?a=%d+union+select+2&b=%s" % (i, filler),
            headers={"host": "h", "cookie": filler[:size // 2]}))
        if bodies:
            reqs.append(Request(
                method="POST", uri="/p?x=%d" % i,
                headers={"host": "h", "content-type": "text/plain"},
                body=(filler + "<script>alert(1)</script>").encode()))
    buckets, shapes, _head_ok, *_ = pipe._build_scan_buckets(reqs)
    assert len({L for _B, L in shapes}) >= 3
    return tuple(buckets), pipe._pad_q(len(reqs))


@pytest.mark.parametrize("head_only", [False, True])
@pytest.mark.parametrize("impl", DetectionEngine.SCAN_IMPLS)
def test_bucket_programs_equal_map_match_words(ruleset, impl, head_only):
    """The served launch shape — one packed buffer and one scan+fold
    program per bucket, one expansion — gives bit for bit the (Q, R)
    rule hits of ``map_match_words`` on the concatenated rows scanned
    by the plain reference (ops/scan.py), and enqueues exactly
    ``buckets + 1`` programs."""
    import jax.numpy as jnp
    import numpy as np

    from ingress_plus_tpu.models.engine import (
        bucket_views, map_match_words_jit)
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    pipe = DetectionPipeline(ruleset, mode="block", scan_impl=impl)
    eng = pipe.engine
    eng.pallas_interpret = True
    buckets, Q = _three_tier_buckets(pipe, bodies=not head_only)
    before = eng.device_launches
    got = np.asarray(eng.detect_device_multi(buckets, Q,
                                             head_only=head_only))
    assert eng.device_launches - before == len(buckets) + 1

    tabs = (eng.head_tables if head_only and eng.head_slicing_active()
            else eng.tables)
    rows = [[np.ascontiguousarray(v) for v in bucket_views(b)]
            for b in buckets]
    match = np.concatenate([
        np.asarray(scan_bytes_jit(tabs.scan, tok, ln)[0])
        for tok, ln, _rr, _rs in rows])
    want = np.asarray(map_match_words_jit(
        tabs, jnp.asarray(match),
        jnp.asarray(np.concatenate([r[2] for r in rows])),
        jnp.asarray(np.concatenate([r[3] for r in rows])), Q)[0])
    assert got.shape == want.shape == (Q, ruleset.n_rules)
    assert want.any()
    assert np.array_equal(got, want)


def test_warm_bucket_shapes_combine_without_a_compile(ruleset):
    """The executable space is additive: bucket programs key on their
    own (B, L), the expansion on Q — so a combination of already-warm
    bucket shapes at a Q tier already seen compiles nothing, by JAX's
    own backend-compile event."""
    import numpy as np

    from ingress_plus_tpu.models.engine import empty_bucket
    from ingress_plus_tpu.utils.platform import backend_compiles

    eng = DetectionEngine(ruleset)
    eng.request_capacity = 16          # what a warm-up reserves
    backend_compiles()                 # start counting
    shapes = [(8, 64), (16, 128), (8, 256)]
    for i, shape in enumerate(shapes):
        np.asarray(eng.detect_device_multi(
            (empty_bucket(*shape),), (4, 8, 16)[i]))
    warm = backend_compiles()
    assert warm > 0
    before = eng.device_launches
    for q in (4, 8, 16):
        for combo in (shapes, shapes[::-1], shapes[:2], shapes[1:]):
            np.asarray(eng.detect_device_multi(
                tuple(empty_bucket(*s) for s in combo), q))
    assert backend_compiles() == warm
    assert eng.device_launches - before == 3 * (4 + 4 + 3 + 3)
    assert eng.request_capacity == 16


def test_bucket_program_name_is_found_by_the_scan_roofline(ruleset):
    """The profiler prints a program as ``jit_<function name>``, and
    benchmark/layer_metrics/scan_hbm_roofline.py finds the scan
    programs by the word ``scan`` in it: the bucket program's lowered
    module carries such a name, the expansion's does not."""
    import re
    from pathlib import Path

    import jax.numpy as jnp

    from ingress_plus_tpu.models import engine as E

    src = (Path(__file__).resolve().parent.parent / "benchmark"
           / "layer_metrics" / "scan_hbm_roofline.py").read_text()
    pattern, flags = re.search(
        r'SCAN_PROGRAM = re\.compile\(r"([^"]+)"(?:, (re\.I))?\)',
        src).groups()
    scan_program = re.compile(pattern, re.I if flags else 0)

    eng = DetectionEngine(ruleset)
    acc = eng._accumulators(eng.tables, 4, None)
    bucket = E.scan_fold_bucket.lower(
        eng.tables, None, jnp.asarray(E.empty_bucket(8, 64)), *acc,
        impl="pair").as_text()
    expand = E.expand_requests_jit.lower(
        eng.tables, *acc, num_requests=4).as_text()
    name = re.search(r"module @(\w+)", bucket).group(1)
    assert name == "jit_" + E.scan_fold_bucket.__name__
    assert scan_program.search(name)
    assert not scan_program.search(
        re.search(r"module @(\w+)", expand).group(1))
