"""The scan lowerings behind the engine: pair/take/pallas must be
indistinguishable at the rule-hit level, at every tier the serve path
dispatches, and "auto" is a rule read off the pack's tables — nothing
is timed at start-up."""

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


@pytest.fixture
def tiny_rules_dir(tmp_path):
    (tmp_path / "tiny.conf").write_text(
        'SecRule ARGS "@rx (?i)union\\s+select" "id:1,phase:2,block,'
        "severity:CRITICAL,tag:'attack-sqli'\"\n")
    return str(tmp_path)


def _verdict_tuple(v):
    return (v.attack, v.blocked, tuple(sorted(v.rule_ids)), v.score)


@pytest.mark.parametrize("impl", ["take", "pallas"])
def test_impl_verdict_parity_with_pair(ruleset, impl):
    """Every impl produces identical verdicts on a mixed corpus (pallas
    runs through the Pallas interpreter on the CPU test backend — same
    kernel code path as the TPU lowering)."""
    reqs = [lr.request for lr in generate_corpus(n=48, seed=11)]

    ref = DetectionPipeline(ruleset, mode="block", scan_impl="pair")
    want = [_verdict_tuple(v) for v in ref.detect(reqs)]

    p = DetectionPipeline(ruleset, mode="block", scan_impl=impl,
                          fail_open=False)
    got = [_verdict_tuple(v) for v in p.detect(reqs)]
    assert got == want


@pytest.mark.parametrize("with_pair_reach", [True, False],
                         ids=["with_pair_reach", "without"])
def test_auto_resolves_from_the_tables(ruleset, with_pair_reach, monkeypatch):
    """``auto`` is ``pair`` where the pack's scan tables carry
    ``pair_reach`` and ``take`` where they do not; the engine holds the
    resolved name, and the lowering it names is what a launch runs."""
    import numpy as np

    from ingress_plus_tpu.models import engine as E
    from ingress_plus_tpu.ops.scan import ScanTables

    if not with_pair_reach:
        from_bitap = ScanTables.from_bitap.__func__
        monkeypatch.setattr(
            ScanTables, "from_bitap",
            classmethod(lambda cls, t: from_bitap(cls, t, classes=False)))
    eng = DetectionEngine(ruleset, scan_impl="auto")
    assert (eng.tables.scan.pair_reach is not None) == with_pair_reach
    want = "pair" if with_pair_reach else "take"
    assert eng.scan_impl == want
    assert E.resolve_scan_impl("auto", eng.tables.scan) == want
    assert eng.device_info()["scan_impl"] == want
    for named in E.SCAN_IMPLS:
        assert E.resolve_scan_impl(named, eng.tables.scan) == named
    with pytest.raises(ValueError, match="pallas3"):
        DetectionEngine(ruleset, scan_impl="pallas3")
    # the resolved lowering launches (a pack without pair tables would
    # fail inside scan_pairs if "pair" had been installed)
    packed = E.empty_bucket(8, 64)
    tok, lengths, _req, sv = E.bucket_views(packed)
    tok[0, :20] = np.frombuffer(b"q=1 union select 2 -", np.uint8)
    lengths[0], sv[0] = 20, 1
    assert np.asarray(eng.detect_device_multi((packed,), 4))[0].any()


def test_building_the_server_times_nothing(tiny_rules_dir, monkeypatch):
    """``build_default_batcher(scan_impl="auto")`` resolves the lowering
    without a measurement: it never enters utils.microbench and launches
    no scan program."""
    import sys

    from ingress_plus_tpu.serve import server

    monkeypatch.setitem(sys.modules, "ingress_plus_tpu.utils.microbench",
                        None)   # an import of it now raises ImportError
    b = server.build_default_batcher(rules_dir=tiny_rules_dir,
                                     warmup=False, scan_impl="auto")
    try:
        assert b.pipeline.engine.scan_impl == "pair"
        assert b.pipeline.engine.device_launches == 0
        assert b.device_path_snapshot()["scan_impl"] == "pair"
    finally:
        b.close()


def test_server_startup_fails_on_a_refused_kernel(tiny_rules_dir,
                                                  monkeypatch):
    """A bucket program the backend refuses to compile in the warm-up
    stops the start-up: build_default_batcher raises instead of serving
    a path that will compile (and fail) in front of traffic."""
    from ingress_plus_tpu.models import engine as E
    from ingress_plus_tpu.serve import server

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(E, "scan_fold_bucket", refused)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        server.build_default_batcher(rules_dir=tiny_rules_dir,
                                     warmup=True, max_batch=8,
                                     scan_impl="auto")


def test_server_scan_impl_flag_accepts_auto_alone(capsys):
    """The server's ``--scan-impl`` stays only because the benchmark's
    configurations pass ``auto``: a lowering's name is refused at the
    parser, before anything is built."""
    from ingress_plus_tpu.serve import server

    for name in ("pallas3", "pair"):
        with pytest.raises(SystemExit) as exc:
            server.main(["--scan-impl", name])
        assert exc.value.code == 2
        assert "--scan-impl" in capsys.readouterr().err


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
        eng.tables, jnp.asarray(E.empty_bucket(8, 64)), *acc,
        impl="pair").as_text()
    expand = E.expand_requests_jit.lower(
        eng.tables, *acc, num_requests=4).as_text()
    name = re.search(r"module @(\w+)", bucket).group(1)
    assert name == "jit_" + E.scan_fold_bucket.__name__
    assert scan_program.search(name)
    assert not scan_program.search(
        re.search(r"module @(\w+)", expand).group(1))


@pytest.mark.parametrize("head", [False, True], ids=["full", "head"])
@pytest.mark.parametrize("L", DetectionPipeline.L_BUCKETS)
@pytest.mark.parametrize("impl", DetectionEngine.SCAN_IMPLS)
def test_bucket_program_equals_reference_at_every_tier(ruleset, impl, L,
                                                       head):
    """One packed bucket of every L tier the serve path dispatches —
    ragged rows with an empty row, a full row and an odd length, attacks
    inside the scanned prefix of some rows and inside the padding of
    others (ops/parity.ragged_batch) — through the served programs
    (``scan_fold_bucket`` + ``expand_requests``), on the full and the
    head-sliced tables: bit for bit the (Q, R) rule hits of the plain
    reference (``scan_bytes`` + ``map_match_words``)."""
    import jax.numpy as jnp
    import numpy as np

    from ingress_plus_tpu.compiler.ruleset import N_HEAD_SV, N_SV
    from ingress_plus_tpu.models import engine as E
    from ingress_plus_tpu.ops.parity import ragged_batch
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    B, Q = (4 if L >= 2048 else 16), 4   # a CPU scans the long tiers
    eng = DetectionEngine(ruleset, scan_impl=impl)
    assert eng.head_slicing_active()
    tabs = eng.head_tables if head else eng.tables
    tokens, lengths = ragged_batch(B, L, seed=L)
    packed = E.empty_bucket(B, L)
    tok, ln, row_req, row_sv = E.bucket_views(packed)
    tok[:], ln[:], row_req[:] = tokens, lengths, np.arange(B) % Q
    row_sv[:, :N_HEAD_SV if head else N_SV] = 1

    fh, sv = E.scan_fold_bucket(tabs, packed,
                                *eng._accumulators(tabs, Q, None),
                                impl=impl)
    got = np.asarray(E.expand_requests_jit(tabs, fh, sv,
                                           num_requests=Q)[0])

    match = scan_bytes_jit(tabs.scan, tokens, lengths)[0]
    assert np.asarray(match).any()        # non-vacuous
    assert not np.asarray(match)[1].any()   # the empty row
    want = np.asarray(E.map_match_words_jit(
        tabs, match, jnp.asarray(row_req), jnp.asarray(row_sv), Q)[0])
    assert got.shape == want.shape == (Q, ruleset.n_rules)
    assert np.array_equal(got, want)
