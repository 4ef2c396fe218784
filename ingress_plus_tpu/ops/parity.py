"""Scan-lowering parity against ops/scan.py ``scan_bytes``.

The ONE comparison behind the tier parity test
(tests/test_engine_impls.py, on the CPU) and ``chip_smoke.py``'s
compiled-parity phase (on the chip), so the two cannot drift apart: a
lowering is right when its sticky match words equal ``scan_bytes``' bit
for bit on the same ragged batch.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Tuple

import numpy as np

from ingress_plus_tpu.ops.scan import ScanTables, scan_bytes_jit

#: planted so a comparison can never pass on all-zero match words
ATTACKS = (b"1' union  select password from users -- ",
           b"<script>alert(1)</script>", b"../../etc/passwd",
           b"; cat /etc/hosts", b"sleep(5) or benchmark(9,1)")


def ragged_batch(B: int, L: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded (tokens uint8 (B, L), lengths int32 (B,)): printable
    noise, ragged lengths with an empty row, a full row and an odd
    length, and attack payloads planted at varying offsets — inside the
    scanned prefix of some rows, inside the padding of others (which
    must stay invisible)."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(32, 127, (B, L)).astype(np.uint8)
    lengths = rng.integers(0, L + 1, (B,)).astype(np.int32)
    lengths[0] = L
    if B > 2:
        lengths[1], lengths[2] = 0, max(1, L - 1) | 1
    for i in range(0, B, max(1, B // 16)):
        a = np.frombuffer(ATTACKS[(seed + i) % len(ATTACKS)], np.uint8)[:L]
        pos = int(rng.integers(0, L - len(a) + 1))
        tokens[i, pos:pos + len(a)] = a
    return tokens, lengths


def compare_scan(scan: Callable, tables: ScanTables, tokens: np.ndarray,
                 lengths: np.ndarray) -> dict:
    """Run ``scan(tokens, lengths) -> (match, state)`` and the
    ``scan_bytes`` reference on the default device; report bit equality
    of the match words (the pair stride does not keep ``scan_bytes``'
    state contract for rows shorter than L, and only the match is
    consumed)."""
    want_m = np.asarray(scan_bytes_jit(tables, tokens, lengths)[0])
    got_m = scan(tokens, lengths)[0]
    return {
        "B": int(tokens.shape[0]), "L": int(tokens.shape[1]),
        "match_equal": bool(np.array_equal(np.asarray(got_m), want_m)),
        "non_vacuous": bool(want_m.any()),
    }


def failed(cases: Iterable[dict]) -> List[dict]:
    """The cases that diverged from the reference."""
    return [c for c in cases if not c["match_equal"]]


def engine_parity(engine, shapes: Iterable[Tuple[int, int]],
                  seed: int = 7,
                  workers: int = 1) -> Dict[str, List[dict]]:
    """Every member of ``engine.SCAN_IMPLS`` through the engine's own
    ``scan_words`` at each ``(B, L)`` in ``shapes``, against
    ``scan_bytes`` on the same device.  A scan that fails to compile or
    run raises.  Returns {impl: [case, ...]}."""
    from concurrent.futures import ThreadPoolExecutor

    shapes = sorted(set(shapes))
    tables = engine.tables.scan
    out: Dict[str, List[dict]] = {}
    saved = engine.scan_impl
    try:
        for impl in engine.SCAN_IMPLS:
            engine.scan_impl = impl

            def one(shape, impl=impl):
                tokens, lengths = ragged_batch(*shape, seed=seed)
                case = compare_scan(
                    lambda t, ln: (engine.scan_words(engine.tables, t, ln),
                                   None),
                    tables, tokens, lengths)
                case["impl"] = impl
                return case

            # the shapes overlap their compiles on the pool
            with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
                out[impl] = list(pool.map(one, shapes))
    finally:
        engine.scan_impl = saved
    return out
