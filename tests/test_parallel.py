"""Multi-chip sharding on the virtual 8-device CPU mesh (the kind-cluster
analog, SURVEY.md §4): TP ruleset sharding must be bit-identical to the
single-device engine; SP ring scan must equal a contiguous scan."""

import numpy as np
import pytest

import jax

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
from ingress_plus_tpu.compiler.bitap import reference_scan
from ingress_plus_tpu.models.engine import DetectionEngine
from ingress_plus_tpu.ops.scan import ScanTables, pad_rows
from ingress_plus_tpu.parallel import ShardedEngine, make_mesh
from ingress_plus_tpu.parallel.stream import ring_scan


@pytest.fixture(scope="module")
def ruleset():
    return compile_ruleset(load_bundled_rules())


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8, jax.devices()


def _mk_batch(ruleset, n_req=8, rows_per_req=2):
    """Rows laid out data-shard-major: request q's rows are contiguous."""
    rng = np.random.default_rng(5)
    payloads = [
        b"GET /search?q=1' UNION SELECT password FROM users--",
        b"<script>alert(1)</script>",
        b"; cat /etc/passwd",
        b"plain benign text about shoes and prices",
    ]
    rows, row_req = [], []
    for q in range(n_req):
        for r in range(rows_per_req):
            rows.append(payloads[(q + r) % len(payloads)])
            row_req.append(q)
    tokens, lengths = pad_rows(rows, round_to=64)
    from ingress_plus_tpu.compiler.ruleset import N_SV, VARIANTS
    from ingress_plus_tpu.compiler.seclang import STREAM_INDEX

    sv = np.zeros((len(rows), N_SV), np.int8)
    a = STREAM_INDEX["args"] * len(VARIANTS)
    sv[:, a:a + len(VARIANTS)] = 1  # args stream, every variant
    return tokens, lengths, np.asarray(row_req, np.int32), sv


def test_tp_sharded_equals_single_device(ruleset):
    mesh = make_mesh(n_data=1, n_model=8)
    eng = ShardedEngine(ruleset, mesh)
    tokens, lengths, row_req, row_sv = _mk_batch(ruleset)
    tenants = np.zeros((8,), np.int32)
    rh, ch, sc = eng.detect(tokens, lengths, row_req, row_sv, tenants, 8)

    single = DetectionEngine(ruleset)
    rh1, ch1, sc1 = single.detect(tokens, lengths, row_req, row_sv, 8)
    assert (rh == rh1).all(), "TP sharded rule hits differ"
    assert (ch == ch1).all()
    assert (sc == sc1).all()


def test_dp_tp_mesh(ruleset):
    mesh = make_mesh(n_data=2, n_model=4)
    eng = ShardedEngine(ruleset, mesh)
    tokens, lengths, row_req, row_sv = _mk_batch(ruleset)
    # shard-local request ids: each data shard owns 4 consecutive requests
    local_req = row_req % 4
    tenants = np.zeros((8,), np.int32)
    rh, ch, sc = eng.detect(tokens, lengths, local_req, row_sv, tenants, 8)

    single = DetectionEngine(ruleset)
    rh1, ch1, sc1 = single.detect(tokens, lengths, row_req, row_sv, 8)
    assert (rh == rh1).all()
    assert (sc == sc1).all()


def test_ep_tenant_masking(ruleset):
    """Tenant 0 sees only sqli rules; tenant 1 sees everything."""
    R = ruleset.n_rules
    sqli_only = np.zeros((2, R), bool)
    sqli_only[0] = np.asarray(
        [m.rule.attack_class == "sqli" for m in ruleset.rules])
    sqli_only[1] = True
    mesh = make_mesh(n_data=1, n_model=8)
    eng = ShardedEngine(ruleset, mesh, tenant_rule_mask=sqli_only)
    tokens, lengths, row_req, row_sv = _mk_batch(ruleset)

    t0 = np.zeros((8,), np.int32)      # all requests tenant 0
    rh0, _, _ = eng.detect(tokens, lengths, row_req, row_sv, t0, 8)
    t1 = np.ones((8,), np.int32)
    rh1, _, _ = eng.detect(tokens, lengths, row_req, row_sv, t1, 8)

    non_sqli_hits0 = rh0[:, ~sqli_only[0]].sum()
    assert non_sqli_hits0 == 0, "tenant mask leaked non-sqli rules"
    assert rh1.sum() >= rh0.sum()
    # xss request must still hit for tenant 1 but not tenant 0
    xss_rules = np.asarray(
        [m.rule.attack_class == "xss" for m in ruleset.rules])
    assert rh1[:, xss_rules].any()
    assert not rh0[:, xss_rules].any()


def test_sp_ring_scan_equals_contiguous(ruleset):
    mesh = make_mesh(n_data=1, n_model=8)
    tables = ScanTables.from_bitap(ruleset.tables)
    rng = np.random.default_rng(11)
    B, L = 4, 1024  # 8 shards × 128 bytes
    tokens = rng.integers(32, 127, size=(B, L), dtype=np.int32)
    # plant an attack SPANNING the shard boundary at L/8 (byte 128)
    atk = b"1' UNION SELECT password FROM users--"
    tokens[0, 120:120 + len(atk)] = np.frombuffer(atk, np.uint8)
    tokens[1, 1024 - len(atk):] = np.frombuffer(atk, np.uint8)

    merged = np.asarray(ring_scan(tables, mesh, tokens))
    for i in range(B):
        want = reference_scan(
            ruleset.tables, tokens[i].astype(np.uint8).tobytes())
        got = merged[i][: want.shape[0]]
        assert (got == want).all(), "ring scan row %d differs" % i


def test_sp_boundary_attack_detected(ruleset):
    """The boundary-spanning attack must appear in the merged mask."""
    mesh = make_mesh(n_data=1, n_model=8)
    tables = ScanTables.from_bitap(ruleset.tables)
    B, L = 1, 256  # 8 shards × 32 bytes — aggressive splitting
    tokens = np.full((B, L), ord("x"), np.int32)
    atk = b"/etc/passwd"
    tokens[0, 30:30 + len(atk)] = np.frombuffer(atk, np.uint8)  # spans 32
    merged = np.asarray(ring_scan(tables, mesh, tokens))
    want = reference_scan(ruleset.tables, tokens[0].astype(np.uint8).tobytes())
    assert want.any()
    assert (merged[0][: want.shape[0]] == want).all()


def test_sp_ring_scan_ragged_rows(ruleset):
    """VERDICT r04 item #6: per-row lengths in the ring scan.  Rows
    shorter than the padded width must scan exactly their own bytes —
    a planted attack INSIDE the padding region must NOT match, and the
    merged mask must equal the single-device engine's on the same
    (tokens, lengths)."""
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    mesh = make_mesh(n_data=1, n_model=8)
    tables = ScanTables.from_bitap(ruleset.tables)
    rng = np.random.default_rng(23)
    B, L = 4, 1024  # 8 shards x 128 bytes
    tokens = rng.integers(97, 122, size=(B, L), dtype=np.int32)
    lengths = np.asarray([1024, 300, 130, 64], np.int32)
    atk = b"1' UNION SELECT password FROM users--"
    # row 0: attack spanning the shard-3 boundary (byte 384)
    tokens[0, 380:380 + len(atk)] = np.frombuffer(atk, np.uint8)
    # row 1: attack inside its 300 valid bytes, spanning shard boundary
    tokens[1, 120:120 + len(atk)] = np.frombuffer(atk, np.uint8)
    # row 2: attack ENTIRELY in padding (beyond byte 130) — dead bytes
    tokens[2, 200:200 + len(atk)] = np.frombuffer(atk, np.uint8)
    # row 3: 64 valid bytes, all within shard 0

    merged = np.asarray(ring_scan(tables, mesh, tokens, lengths=lengths))
    want, _ = scan_bytes_jit(tables, tokens, lengths, gather="take")
    want = np.asarray(want)
    assert (merged == want).all()
    # absolute grounding: the padding attack really is invisible, the
    # in-bounds attacks really are found
    ref1 = reference_scan(
        ruleset.tables, tokens[1, :300].astype(np.uint8).tobytes())
    assert ref1.any() and (merged[1][: ref1.shape[0]] == ref1).all()
    ref2 = reference_scan(
        ruleset.tables, tokens[2, :130].astype(np.uint8).tobytes())
    assert (merged[2][: ref2.shape[0]] == ref2).all()


def test_sp_ring_scan_config5_mixed_1mb_batch(ruleset):
    """VERDICT r04 weak-item #5: the ring at the REAL config-#5 geometry
    — an actual 1MB body and a mixed 100KB/1MB ragged batch across the
    8-device mesh, with a boundary-spanning attack — not just the toy
    L=64*n shapes."""
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    mesh = make_mesh(n_data=1, n_model=8)
    tables = ScanTables.from_bitap(ruleset.tables)
    rng = np.random.default_rng(29)
    B, L = 2, 1 << 20                   # 1 MiB, 8 shards x 128 KiB
    shard = L // 8
    tokens = rng.integers(97, 122, size=(B, L), dtype=np.int32)
    lengths = np.asarray([L, 100 * 1024], np.int32)
    atk = b"1' UNION SELECT password FROM users--"
    # row 0 (full 1MB): attack spans the shard-1 boundary
    tokens[0, shard - 16:shard - 16 + len(atk)] = np.frombuffer(atk, np.uint8)
    # row 1 (100KB): attack inside the valid prefix...
    tokens[1, 50_000:50_000 + len(atk)] = np.frombuffer(atk, np.uint8)
    # ...and one planted far beyond its length — must stay invisible
    tokens[1, 500_000:500_000 + len(atk)] = np.frombuffer(atk, np.uint8)

    merged = np.asarray(ring_scan(tables, mesh, tokens, lengths=lengths))
    want, _ = scan_bytes_jit(tables, tokens, lengths, gather="take")
    assert (merged == np.asarray(want)).all()
    # the boundary-spanning and in-prefix attacks are present
    assert merged[0].any() and merged[1].any()


def test_sharded_pair_odd_length_padded():
    """ShardedEngine(pair) must accept odd-L host batches (one dead-class
    padding column, the pre-pair contract)."""
    from ingress_plus_tpu.compiler.ruleset import N_SV

    cr = compile_ruleset(parse_seclang(
        'SecRule ARGS "@rx (?i)union\\s+select" "id:1,phase:2,block,'
        "severity:CRITICAL,tag:'attack-sqli'\"\n"))
    mesh = make_mesh(n_data=2, n_model=4)
    eng = ShardedEngine(cr, mesh, scan_impl="pair")
    row = b"q=1 union  select password from users"
    tokens, lengths = pad_rows([row], round_to=64)
    tokens = np.asarray(tokens)[:, :63]          # force odd L
    lengths = np.minimum(np.asarray(lengths), 63)
    tokens = np.repeat(tokens, 2, axis=0)        # one row per data shard
    lengths = np.repeat(lengths, 2)
    sv = np.ones((2, N_SV), np.int8)
    rh, ch, sc = eng.detect(tokens, lengths,
                            np.zeros((2,), np.int32), sv,
                            np.zeros((2,), np.int32), 2)
    assert rh[0].any()


def test_tp_scan_impl_parity_and_autoselect(ruleset):
    """Round-4 (VERDICT item #7): the sharded step must produce identical
    verdicts under the pair-stride and gather scans, and autoselect must
    measure both and install a valid winner."""
    mesh = make_mesh(n_data=2, n_model=4)
    eng = ShardedEngine(ruleset, mesh, scan_impl="take")
    tokens, lengths, row_req, row_sv = _mk_batch(ruleset)
    local_req = row_req % 4   # detect() takes SHARD-LOCAL request ids
    tenants = np.zeros((8,), np.int32)
    out_take = eng.detect(tokens, lengths, local_req, row_sv, tenants, 8)
    assert np.asarray(out_take[2]).max() > 0   # parity must be non-vacuous
    eng.set_scan_impl("pair")
    out_pair = eng.detect(tokens, lengths, local_req, row_sv, tenants, 8)
    for a, b in zip(out_take, out_pair):
        assert (np.asarray(a) == np.asarray(b)).all()
    best = eng.autoselect_scan_impl(B=32, L=128, iters=3)
    assert best in ("pair", "take")
    assert eng.scan_impl == best
    out_best = eng.detect(tokens, lengths, local_req, row_sv, tenants, 8)
    for a, b in zip(out_take, out_best):
        assert (np.asarray(a) == np.asarray(b)).all()
