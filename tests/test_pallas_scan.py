"""Pallas byte kernel — bit-for-bit equivalence vs ops/scan.py
scan_bytes, state included.

On a backend other than a TPU the kernel runs through the Pallas
interpreter, so CI needs no TPU (the fake-backend analog of the
reference's kind-cluster e2e tier, SURVEY.md §4).
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
    got_m, got_s = pallas_scan_bytes(tables, tokens, lengths)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_odd_shapes_and_empty_rows(tables):
    rows = [b"", b"x", b"1 union select 2", b"a" * 700]
    tokens, lengths = pad_rows(rows, round_to=64)
    want_m, want_s = scan_bytes(tables, tokens, lengths)
    got_m, got_s = pallas_scan_bytes(tables, tokens, lengths,
                                     TB=8, CL=64)
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
    m1, s1 = pallas_scan_bytes(tables, ta, la)
    m2, _ = pallas_scan_bytes(tables, tb, lb, state=s1, match=m1)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(want_m))
