"""Sequence-parallel streaming scan — the ring-attention analog.

Benchmark config #5: chunked 1MB POST bodies.  Two cooperating modes:

1. **Chunk chaining (single device)** — ops/scan.py already carries
   (state, match) across chunk calls; serve/streaming.py drives it.  The
   carried state is O(words) bits, the moral equivalent of ring
   attention's KV-block handoff but constant-size (SURVEY.md §5).

2. **Sequence sharding (this module)** — a giant body is split along the
   byte axis across the ``model`` mesh axis; every device scans its slice
   *plus a halo of the last H-1 bytes of the previous slice*, where
   H = max factor length ≤ 32.  Because bitap state only ever depends on
   the last (factor_len - 1) bytes, the halo makes each local scan exact:
   matches ending in slice s are found by shard s.  Matches ending inside
   the halo are double-found by the previous shard — harmless, the match
   mask is a sticky OR.  The halo travels over ICI with one ``ppermute``
   (the ring); match masks merge with an all_gather + OR (both tiny).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ingress_plus_tpu.ops.scan import ScanTables, scan_bytes

HALO = 32  # ≥ max factor length (bitap.WORD_BITS); exactness bound


def ring_scan(tables: ScanTables, mesh: Mesh, tokens, lengths=None,
              axis: str = "model"):
    """Scan (B, L_total) byte rows sequence-sharded along ``axis``.

    tokens must be (B, L_total) with L_total divisible by the axis size.
    ``lengths`` (B,) gives each row's true byte count — rows may be
    RAGGED (a mixed 100KB/1MB batch pads to the widest row without
    scanning the padding, VERDICT r04 item #6): shard ``s`` clips its
    slice to ``clip(len - s*L_local, 0, L_local)`` bytes, so a shard
    past a row's end scans nothing and padding garbage can't match.
    The halo a shard receives is valid whenever it scans at all: a
    positive clipped length means every predecessor slice was full.
    ``lengths=None`` keeps the old full-width contract.
    Returns the merged sticky match mask (B, W), replicated.
    """
    n = mesh.shape[axis]
    B, L_total = tokens.shape
    assert L_total % n == 0, (L_total, n)
    assert L_total // n >= HALO, (
        "per-shard slice %d < HALO %d: the halo would be short and "
        "boundary-spanning matches silently lost — use fewer shards or a "
        "longer body" % (L_total // n, HALO))
    if lengths is None:
        lengths = np.full((B,), L_total, np.int32)

    def block(byte_table, init, final, tok, total_lens):
        # tok: (B, L_local) slice of the body; total_lens: (B,) replicated
        idx = jax.lax.axis_index(axis)
        # ring: receive the last HALO bytes of the previous shard
        halo_src = tok[:, -HALO:]
        perm = [(i, (i + 1) % n) for i in range(n)]
        halo = jax.lax.ppermute(halo_src, axis, perm)

        L_local = tok.shape[1]
        # this shard's share of each row: 0 when the row ended earlier
        eff = jnp.clip(total_lens - idx * L_local, 0, L_local)
        eff = eff.astype(jnp.int32)
        # shard 0 has no predecessor; zero bytes would FALSELY match rules
        # with \x00 in their classes, so instead shard 0 scans its chunk
        # left-aligned with masked suffix padding (same static shape).
        ext_mid = jnp.concatenate([halo, tok], axis=1)
        ext_zero = jnp.concatenate([tok, jnp.zeros_like(halo)], axis=1)
        ext = jnp.where(idx == 0, ext_zero, ext_mid)
        lens = jnp.where(
            idx == 0, eff,
            jnp.where(eff > 0, eff + HALO, 0),
        )

        class _T:
            n_words = byte_table.shape[1]
        t = _T()
        t.byte_table, t.init_mask, t.final_mask = byte_table, init, final
        t.byte_planes = None
        match, _ = scan_bytes(t, ext, lens, gather="take")

        # merge sticky masks: all_gather along the ring + OR-reduce
        all_m = jax.lax.all_gather(match, axis)          # (n, B, W)
        merged = all_m[0]
        for i in range(1, n):
            merged = merged | all_m[i]
        return merged

    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(None, None), P(None), P(None), P(None, axis), P(None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(tables.byte_table, tables.init_mask, tables.final_mask,
              jnp.asarray(tokens, jnp.int32),
              jnp.asarray(lengths, jnp.int32))
