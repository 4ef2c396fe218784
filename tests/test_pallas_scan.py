"""Pallas scan kernel — bit-for-bit equivalence vs the XLA scan path.

Runs in Pallas interpret mode so CI needs no TPU (the fake-backend analog
of the reference's kind-cluster e2e tier, SURVEY.md §4).
"""

import numpy as np
import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.ops.pallas_scan import pallas_scan_bytes
from ingress_plus_tpu.ops.scan import ScanTables, pad_rows, scan_bytes

RULES = """
SecRule ARGS "@rx (?i)union\\s+select" "id:1,phase:2,block,severity:CRITICAL,tag:'attack-sqli'"
SecRule ARGS "@rx (?i)<script[^>]*>" "id:2,phase:2,block,severity:CRITICAL,tag:'attack-xss'"
SecRule ARGS "@rx /etc/(?:passwd|shadow)" "id:3,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
SecRule ARGS "@pm sleep( benchmark( xp_cmdshell load_file(" "id:4,phase:2,block,severity:ERROR,tag:'attack-sqli'"
SecRule ARGS "@rx (?:;|\\|)\\s*(?:cat|ls|id)\\b" "id:5,phase:2,block,severity:ERROR,tag:'attack-rce'"
"""


@pytest.fixture(scope="module")
def tables():
    cr = compile_ruleset(parse_seclang(RULES))
    return ScanTables.from_bitap(cr.tables)


def _mixed_rows(n, seed=0):
    rng = np.random.default_rng(seed)
    rows = []
    attacks = [b"1 union  select password from users",
               b"<script>alert(1)</script>",
               b"../../etc/passwd", b"; cat /etc/hosts",
               b"sleep(5) or benchmark(9,1)"]
    for i in range(n):
        body = bytes(rng.integers(32, 127, size=int(rng.integers(1, 300))))
        if i % 3 == 0:
            a = attacks[i % len(attacks)]
            pos = int(rng.integers(0, max(1, len(body) - len(a))))
            body = body[:pos] + a + body[pos + len(a):]
        rows.append(body)
    return rows


def test_matches_xla_scan(tables):
    rows = _mixed_rows(13)
    tokens, lengths = pad_rows(rows)
    want_m, want_s = scan_bytes(tables, tokens, lengths)
    got_m, got_s = pallas_scan_bytes(tables, tokens, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_odd_shapes_and_empty_rows(tables):
    rows = [b"", b"x", b"1 union select 2", b"a" * 700]
    tokens, lengths = pad_rows(rows, round_to=64)
    want_m, want_s = scan_bytes(tables, tokens, lengths)
    got_m, got_s = pallas_scan_bytes(tables, tokens, lengths,
                                     TB=8, CL=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_streaming_carry_chunks(tables):
    """Split rows at a chunk boundary and carry (state, match) across —
    must equal one whole-row scan (benchmark config #5 contract)."""
    full = [b"AAAA union  sel" + b"ect BBBB", b"hello /etc/pas" + b"swd zz"]
    a = [r[:14] for r in full]
    b = [r[14:] for r in full]

    tokens, lengths = pad_rows(full, round_to=64)
    want_m, _ = scan_bytes(tables, tokens, lengths)

    ta, la = pad_rows(a, round_to=64)
    tb, lb = pad_rows(b, round_to=64)
    m1, s1 = pallas_scan_bytes(tables, ta, la, interpret=True)
    m2, _ = pallas_scan_bytes(tables, tb, lb, state=s1, match=m1,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(want_m))


# ---------------------------------------------- class-pair kernel (round 4)

def test_pallas_pair_matches_reference(tables):
    """Bit-for-bit: the class-pair Pallas kernel's match mask equals the
    XLA byte scan on mixed-length rows (interpret mode on CPU — the
    fake-backend tier)."""
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner

    rows = _mixed_rows(13)
    tokens, lengths = pad_rows(rows)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    ps = PallasPairScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = ps(tokens, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))


def test_pallas_pair_sticky_match_chaining(tables):
    """Chained calls must accumulate the sticky match exactly like the
    serving K-rep contract."""
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner

    rows = _mixed_rows(9, seed=3)
    tokens, lengths = pad_rows(rows, round_to=64)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    ps = PallasPairScanner(tables, TB=8, CL=16, MR=8)
    m1, _ = ps(tokens, lengths, interpret=True)
    m2, _ = ps(tokens, lengths, match=m1, interpret=True)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(want_m))


def test_pallas_pair_odd_lengths_and_empty(tables):
    """Odd-length rows end on the pair's FIRST byte (the FA1 collection
    path); empty rows must scan clean."""
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner

    rows = [b"", b"x", b"1 union select 2", b"a" * 701,
            b"; cat /etc/hosts!"]
    tokens, lengths = pad_rows(rows, round_to=64)
    odd = np.asarray([0, 1, 15, 701, 17], np.int32)
    want_m, _ = scan_bytes(tables, tokens, odd)
    ps = PallasPairScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = ps(tokens, odd, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))


def test_pallas_pair_multi_chunk_double_buffer(tables):
    """Rows spanning many CL-chunks exercise the double-buffered
    prefetch: chunk k+1's reach must land in the OTHER buffer than the
    one chunk k's chain is reading."""
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner

    rng = np.random.default_rng(11)
    long = bytes(rng.integers(32, 127, size=900))
    rows = [long[:813] + b"1 union select password from users" + long[:77],
            long, b"short ; cat /etc/hosts", long[:500]]
    tokens, lengths = pad_rows(rows, round_to=64)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    ps = PallasPairScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = ps(tokens, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))


def test_pallas_pair_odd_remainder_stale_scratch(tables):
    """Round-4 review repro: when the tile's remaining length is odd, the
    chain's last pair reads the PADDING position's reach row — stage1
    must compute it (all-zero dead class), not leave two-chunks-stale
    scratch behind it.  49-byte row, 'd' planted at the same in-chunk
    offset two chunks before a '/etc/passw' tail."""
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner

    row = bytearray(b"a" * 49)
    row[17] = ord("d")
    row[39:49] = b"/etc/passw"
    tokens, lengths = pad_rows([bytes(row)], round_to=64)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    ps = PallasPairScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = ps(tokens, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))


# ------------------------------------- raw-byte fused kernel (ISSUE 13)

def test_byte_scanner_interpret_matches_xla_scan(tables):
    """The raw-byte fused kernel (pallas3) in Mosaic interpret mode:
    uint8 tokens + lengths in, match words bit-identical to the XLA
    byte scan — no host-side class mapping anywhere."""
    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner

    rows = _mixed_rows(13)
    tokens, lengths = pad_rows(rows)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    sc = PallasByteScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = sc(tokens, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))


def test_byte_scanner_reference_matches_interpret(tables):
    """The CPU reference lowering and the Mosaic interpreter are the
    SAME math (the plane-composition identity): match words must be
    bit-identical between the two modes — this is what makes
    `--scan-impl pallas3` a flag flip between CPU and TPU."""
    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner

    rows = _mixed_rows(11, seed=5)
    tokens, lengths = pad_rows(rows, round_to=64)
    sc = PallasByteScanner(tables, TB=8, CL=16, MR=8)
    km, _ = sc(tokens, lengths, interpret=True)
    rm, _ = sc(tokens, lengths, mode="reference")
    np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))


def test_byte_scanner_ragged_odd_and_empty(tables):
    """Ragged batches: empty rows, odd lengths (the pair fold's FA1
    path), and a length far past the padded width — the dead-index
    padding select must kill exactly the right positions."""
    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner

    rows = [b"", b"x", b"1 union select 2", b"a" * 701,
            b"; cat /etc/hosts!"]
    tokens, _ = pad_rows(rows, round_to=64)
    odd = np.asarray([0, 1, 15, 701, 17], np.int32)
    want_m, _ = scan_bytes(tables, tokens, odd)
    sc = PallasByteScanner(tables, TB=8, CL=16, MR=8)
    got_m, _ = sc(tokens, odd, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    ref_m, _ = sc(tokens, odd, mode="reference")
    np.testing.assert_array_equal(np.asarray(ref_m), np.asarray(want_m))


def test_byte_scanner_sticky_match_chaining(tables):
    """Chained calls accumulate the sticky match exactly like the
    serving K-rep contract, in both modes."""
    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner

    rows = _mixed_rows(9, seed=3)
    tokens, lengths = pad_rows(rows, round_to=64)
    want_m, _ = scan_bytes(tables, tokens, lengths)
    sc = PallasByteScanner(tables, TB=8, CL=16, MR=8)
    m1, _ = sc(tokens, lengths, interpret=True)
    m2, _ = sc(tokens, lengths, match=m1, interpret=True)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(want_m))
    r1, _ = sc(tokens, lengths, mode="reference")
    r2, _ = sc(tokens, lengths, match=r1, mode="reference")
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(want_m))


def test_byte_scanner_full_pack_geometry():
    """Reference-mode parity at the REAL bundled-pack geometry — the
    multi-tile Wp/K1p padding the serving ruleset hits (the interpret
    twin of this case runs in the devicegate CI gate)."""
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner

    cr = compile_ruleset(load_bundled_rules())
    t = ScanTables.from_bitap(cr.tables)
    rng = np.random.default_rng(3)
    B, L = 6, 192
    tokens = rng.integers(32, 127, (B, L)).astype(np.uint8)
    atk = b"1' union select password from users -- "
    tokens[0, :len(atk)] = np.frombuffer(atk, np.uint8)
    tokens[4, 100:100 + len(atk)] = np.frombuffer(atk, np.uint8)
    lengths = np.asarray([L, 37, 0, 5, L, 64], np.int32)
    want_m, _ = scan_bytes(t, tokens, lengths)
    got_m, _ = PallasByteScanner(t)(tokens, lengths, mode="reference")
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    assert np.asarray(want_m)[0].any()   # non-vacuous


def test_byte_scanner_exec_shape_and_tiling(tables):
    """Bad tilings are rejected loudly, and classless tables are
    refused (the reference lowering needs the pair tables)."""
    import pytest as _pytest

    from ingress_plus_tpu.ops.pallas_scan import PallasByteScanner
    from ingress_plus_tpu.ops.scan import ScanTables as _ST

    PallasByteScanner(tables, TB=8, CL=16, MR=8)   # a tiling that holds
    with _pytest.raises(ValueError):
        PallasByteScanner(tables, TB=7, CL=16)   # TB % 8
    with _pytest.raises(ValueError):
        PallasByteScanner(tables, TB=8, CL=15)   # CL odd
    classless = _ST.from_bitap(
        compile_ruleset(parse_seclang(RULES)).tables, classes=False)
    with _pytest.raises(ValueError):
        PallasByteScanner(classless)


def test_pipeline_pallas3_verdicts_across_tiers_and_swap():
    """Verdict-level pin (ISSUE 13 satellite): raw-bytes-in pallas3
    serving produces BYTE-IDENTICAL verdicts to the host-prepped pair
    path across the L-bucket tiers, a truncated oversized row, and a
    hot swap."""
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.normalize import Request
    from ingress_plus_tpu.utils.corpus import generate_corpus

    cr = compile_ruleset(load_bundled_rules())
    reqs = [lr.request for lr in generate_corpus(n=40, seed=13)]
    # force rows into every bucket tier incl. the 16KB truncation lane
    reqs.append(Request(uri="/big?q=" + "A" * 600 + "+union+select+1"))
    reqs.append(Request(uri="/huge", body=b"B" * 3000 + b"<script>x</script>",
                        headers={"content-type": "text/plain"}))
    reqs.append(Request(uri="/over", body=b"C" * 20000 +
                        b" 1 union select password from users",
                        headers={"content-type": "text/plain"}))

    def vt(v):
        return (v.attack, v.blocked, tuple(sorted(v.rule_ids)), v.score)

    ref = DetectionPipeline(cr, mode="block", scan_impl="pair")
    want = [vt(v) for v in ref.detect(reqs)]
    p3 = DetectionPipeline(cr, mode="block", scan_impl="pallas3",
                           fail_open=False)
    assert [vt(v) for v in p3.detect(reqs)] == want
    # hot swap: new generation, fresh scanner tables, parity holds
    p3.swap_ruleset(cr)
    ref.swap_ruleset(cr)
    assert [vt(v) for v in p3.detect(reqs)] == \
        [vt(v) for v in ref.detect(reqs)]


def test_devicegate_parity_gate(tmp_path):
    """The devicegate CI gate: interpret kernels vs the XLA reference,
    bit-identical, report written."""
    import tools.lint as lint

    res = lint.run_devicegate(write_report=False)
    assert res["status"] == "OK", res["detail"]
    assert res["cases"] >= 10


def test_sharded_pair_odd_length_padded():
    """ShardedEngine(pair) must accept odd-L host batches (one dead-class
    padding column, the pre-pair contract)."""
    from ingress_plus_tpu.parallel import ShardedEngine, make_mesh

    cr = compile_ruleset(parse_seclang(RULES))
    mesh = make_mesh(n_data=2, n_model=4)
    eng = ShardedEngine(cr, mesh, scan_impl="pair")
    row = b"q=1 union  select password from users"
    tokens, lengths = pad_rows([row], round_to=64)
    tokens = np.asarray(tokens)[:, :63]          # force odd L
    lengths = np.minimum(np.asarray(lengths), 63)
    from ingress_plus_tpu.compiler.ruleset import N_SV
    tokens = np.repeat(tokens, 2, axis=0)        # one row per data shard
    lengths = np.repeat(lengths, 2)
    sv = np.ones((2, N_SV), np.int8)
    rh, ch, sc = eng.detect(tokens, lengths,
                            np.zeros((2,), np.int32), sv,
                            np.zeros((2,), np.int32), 2)
    assert rh[0].any()


def test_pallas_pair_full_pack_geometry():
    """Interpret parity at the REAL bundled-pack geometry (500+ words,
    100+ byte classes, padded K1p/Wp tiles) — the small fixture cannot
    exercise the multi-tile padding paths the serving ruleset hits."""
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.ops.pallas_scan import PallasPairScanner
    from ingress_plus_tpu.ops.scan import scan_pairs

    from ingress_plus_tpu.compiler.reduce import ReductionConfig

    # exact compile: this test exists to exercise the 500+-word
    # multi-tile geometry, which the approximate reduction deliberately
    # shrinks — disable it here, the kernel must still handle the width
    cr = compile_ruleset(load_bundled_rules(),
                         reduction=ReductionConfig.off())
    t = ScanTables.from_bitap(cr.tables)
    assert t.n_words > 400   # the point of this test
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    B, L = 4, 192
    tokens = rng.integers(32, 127, (B, L)).astype(np.uint8)
    atk = b"1' union select password from users -- "
    tokens[0, :len(atk)] = np.frombuffer(atk, np.uint8)
    tokens[2, 100:100 + len(atk)] = np.frombuffer(atk, np.uint8)
    lengths = np.asarray([L, 37, L, 0], np.int32)

    want_m, _ = scan_pairs(t, jnp.asarray(tokens), jnp.asarray(lengths))
    ps = PallasPairScanner(t)
    got_m, _ = ps(jnp.asarray(tokens), jnp.asarray(lengths),
                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    assert np.asarray(want_m)[0].any()   # non-vacuous
