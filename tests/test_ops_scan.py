"""Differential tests: jnp scan engine vs the numpy bitap oracle.

The fake-backend analog from SURVEY.md §4: identical recurrence on CPU
(JAX_PLATFORMS=cpu via conftest) so CI needs no TPU.
"""

import random

import numpy as np
import pytest

from ingress_plus_tpu.compiler.bitap import reference_scan
from ingress_plus_tpu.compiler.factors import best_factor_group
from ingress_plus_tpu.compiler.regex_ast import parse_regex
from ingress_plus_tpu.compiler.bitap import pack_factors
from ingress_plus_tpu.ops.scan import ScanTables, pad_rows, scan_bytes


PATTERNS = [
    r"union\s+select",
    r"(?i)<script[^>]*>",
    r"\.\./(?:\.\./)*etc/passwd",
    r"eval\s*\(",
    r"onerror\s*=",
    r"/etc/(?:passwd|shadow|group)",
    r"(?i)x(?:p_cmdshell|p_dirtree)",
    r"document\.(?:cookie|location)",
]


@pytest.fixture(scope="module")
def tables():
    groups = [best_factor_group(parse_regex(p)) for p in PATTERNS]
    return pack_factors(groups)


def corpus(rng, n=60):
    snippets = [
        b"1 union select 2", b"<SCRIPT src=x>", b"../../etc/passwd",
        b"eval (x)", b"<img onerror =a>", b"/etc/shadow", b"XP_CMDSHELL",
        b"document.cookie",
    ]
    out = list(snippets)
    for _ in range(n):
        base = bytes(rng.randrange(32, 127) for _ in range(rng.randrange(0, 90)))
        if rng.random() < 0.5:
            s = rng.choice(snippets)
            k = rng.randrange(0, len(base) + 1)
            base = base[:k] + s + base[k:]
        out.append(base)
    return out


def test_batch_matches_oracle(tables):
    st = ScanTables.from_bitap(tables)
    rng = random.Random(3)
    rows = corpus(rng)
    tokens, lengths = pad_rows(rows)
    match, state = scan_bytes(st, tokens, lengths)
    match = np.asarray(match)
    for i, row in enumerate(rows):
        want = reference_scan(tables, row)
        assert (match[i] == want).all(), "row %d %r" % (i, row)


def test_empty_and_full_padding(tables):
    st = ScanTables.from_bitap(tables)
    tokens, lengths = pad_rows([b"", b"/etc/passwd"])
    match, _ = scan_bytes(st, tokens, lengths)
    match = np.asarray(match)
    assert (match[0] == 0).all()
    assert (match[1] == reference_scan(tables, b"/etc/passwd")).all()


def test_streaming_chunks_equal_contiguous(tables):
    """Chunked scan with state carry == one contiguous scan (config #5)."""
    st = ScanTables.from_bitap(tables)
    rng = random.Random(9)
    rows = corpus(rng, n=20)
    # contiguous
    tokens, lengths = pad_rows(rows)
    want, _ = scan_bytes(st, tokens, lengths)
    want = np.asarray(want)
    # chunked: split each row at arbitrary points, carry (state, match)
    state = match = None
    n_chunks = 4
    maxlen = max(len(r) for r in rows)
    chunk = (maxlen + n_chunks - 1) // n_chunks
    for c in range(n_chunks):
        part = [r[c * chunk : (c + 1) * chunk] for r in rows]
        tokens_c, lengths_c = pad_rows(part, max_len=chunk)
        got_m, state = scan_bytes(st, tokens_c, lengths_c, state=state, match=match)
        match = got_m
    got = np.asarray(match)
    assert (got == want).all(), "streaming mismatch"


def test_match_spanning_chunk_boundary(tables):
    """An attack split across a chunk boundary must still match."""
    st = ScanTables.from_bitap(tables)
    a, b = b"GET /etc/pas", b"swd HTTP/1.1"
    t1, l1 = pad_rows([a])
    m, s = scan_bytes(st, t1, l1)
    t2, l2 = pad_rows([b])
    m, s = scan_bytes(st, t2, l2, state=s, match=m)
    want = reference_scan(tables, a + b)
    assert (np.asarray(m)[0] == want).all()
    assert np.asarray(m)[0].any(), "boundary-spanning match lost"


def test_jit_cache_stable_shapes(tables):
    import jax

    st = ScanTables.from_bitap(tables)
    f = jax.jit(scan_bytes)
    tokens, lengths = pad_rows([b"abc", b"defg"])
    m1, _ = f(st, tokens, lengths)
    tokens2, lengths2 = pad_rows([b"/etc/passwd", b"zz"])
    m2, _ = f(st, tokens2, lengths2)  # same shapes → cached executable
    assert np.asarray(m2)[0].any()


def test_scan_pairs_match_parity(tables):
    """scan_pairs is the default request hot path (detect_rows auto-selects
    it when state is None): pin its match output to scan_bytes on random
    tokens/lengths — zero/short/odd lengths and a seeded sticky match
    accumulator included.  (state parity is NOT in the contract for short
    rows; see the scan_pairs docstring.)"""
    from ingress_plus_tpu.ops.scan import scan_pairs

    st = ScanTables.from_bitap(tables)
    rng = random.Random(11)
    rows = corpus(rng, n=40)
    # force the interesting length classes: empty, single byte, odd tails
    rows += [b"", b"u", b"union select"[:11], b"../../etc/passwd"[:7]]
    tokens, lengths = pad_rows(rows)
    B, W = tokens.shape[0], st.n_words

    m_bytes, _ = scan_bytes(st, tokens, lengths)
    m_pairs, _ = scan_pairs(st, tokens, lengths)
    assert (np.asarray(m_bytes) == np.asarray(m_pairs)).all()

    # seeded sticky accumulator must be OR-preserved identically
    seed = np.asarray(
        [[rng.getrandbits(32) for _ in range(W)] for _ in range(B)],
        dtype=np.uint32)
    import jax.numpy as jnp
    m_b2, _ = scan_bytes(st, tokens, lengths, match=jnp.asarray(seed))
    m_p2, _ = scan_pairs(st, tokens, lengths, match=jnp.asarray(seed))
    assert (np.asarray(m_b2) == np.asarray(m_p2)).all()
    assert (np.asarray(m_b2) & seed == seed).all()  # sticky


def test_scan_pairs_sticky_match_chaining(tables):
    """Chained calls accumulate the sticky match: a second scan_pairs
    call seeded with the first call's match over OTHER rows' bytes ends
    at the OR of both scans, exactly as scan_bytes chains."""
    import jax.numpy as jnp

    from ingress_plus_tpu.ops.scan import scan_pairs

    st = ScanTables.from_bitap(tables)
    rows = corpus(random.Random(3), n=9)
    tokens, lengths = pad_rows(rows, round_to=64)
    tokens, lengths = np.asarray(tokens), np.asarray(lengths)
    # second call: the same batch rolled by one row, so every row's
    # accumulator meets different bytes
    tokens2, lengths2 = np.roll(tokens, 1, axis=0), np.roll(lengths, 1)
    w1, _ = scan_bytes(st, tokens, lengths)
    want, _ = scan_bytes(st, tokens2, lengths2, match=w1)
    m1, _ = scan_pairs(st, tokens, lengths)
    m2, _ = scan_pairs(st, tokens2, lengths2, match=m1)
    m1, m2, want = np.asarray(m1), np.asarray(m2), np.asarray(want)
    assert (m2 == want).all()
    assert (m2 & m1 == m1).all()          # sticky
    assert (m2 != m1).any()               # and the second call added bits
    # one call over the same bytes twice changes nothing
    m3, _ = scan_pairs(st, tokens2, lengths2, match=jnp.asarray(m2))
    assert (np.asarray(m3) == m2).all()


def test_scan_pairs_odd_length_remainder(tables):
    """A row of odd length ends on the FIRST byte of its last pair: the
    match completing there must be collected, and the pair's second
    byte (padding, here deliberately live bytes that would complete
    another match) must stay invisible."""
    from ingress_plus_tpu.ops.scan import scan_pairs

    st = ScanTables.from_bitap(tables)
    row = bytearray(b"a" * 64)
    row[38:49] = b"/etc/passwd"          # ends at byte 48: length 49 is odd
    row[49:58] = b"/../etc/p"            # beyond the length
    rows = np.frombuffer(bytes(row), np.uint8)[None, :].repeat(4, axis=0)
    lengths = np.asarray([49, 48, 47, 1], np.int32)
    want, _ = scan_bytes(st, rows, lengths)
    got, _ = scan_pairs(st, rows, lengths)
    want, got = np.asarray(want), np.asarray(got)
    assert (got == want).all()
    assert want[0].any()
    # the row cut before the match's last byte matches less
    assert (want[0] != want[2]).any()
    # nothing after the length leaks in: the same prefix over dead
    # padding gives the same words
    clean = rows.copy()
    clean[:, 49:] = 0
    assert (np.asarray(scan_pairs(st, clean, lengths)[0]) == got).all()
