"""Batched bitap scan — jnp/XLA implementation.

The recurrence per byte (uint32, element-wise over words — see
compiler/bitap.py for why no cross-word carries exist):

    S' = ((S << 1) | INIT) & B[byte]
    M' = M | (S' & FINAL)

Shapes: tokens (B, L) int32 in [0, 255] (padded with any value), lengths
(B,) int32, state/match (B, W) uint32.  Padded steps are identity on both S
and M (masked select), so a row's final state is exactly the state after its
``length`` real bytes — the property the streaming chunk chain relies on.

Design notes (TPU-first):
- `lax.scan` over the time axis with the batch×words update vectorized on
  the VPU; `unroll` amortizes loop overhead.
- The 256×W byte table is gathered per step with `jnp.take` — on TPU this
  compiles to a dynamic-gather from VMEM (the table is ~256×258×4B ≈ 264KB).
- Everything is static-shaped; jit caches one executable per (B, L, W).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ingress_plus_tpu.compiler.bitap import BitapTables


@jax.tree_util.register_pytree_node_class
@dataclass
class ScanTables:
    """Device-resident scan tables (a pytree, so it jits as an argument —
    ruleset hot-swap is just passing new arrays, no recompilation).

    ``byte_planes`` is the byte table split into 4 uint8 planes stored as
    bf16 (values 0..255 are exact in bf16): the TPU path fetches B[byte]
    for a whole batch as ``onehot(bytes) @ byte_planes`` — one MXU matmul —
    because per-lane dynamic gather is slow on TPU."""

    byte_table: jax.Array   # (256, W) uint32
    byte_planes: jax.Array  # (256, 4W) bfloat16 — plane-major [b0|b1|b2|b3]
    init_mask: jax.Array    # (W,) uint32
    final_mask: jax.Array   # (W,) uint32
    # ---- byte-class compression (Hyperscan-style): the 256 byte rows
    # collapse to k distinct classes (k≈75 on the CRS corpus).  Class
    # index k is the reserved DEAD class (all-zero reach) used as padding,
    # which makes per-step validity masks unnecessary: once a row runs
    # into padding its state dies and its match mask is stable.
    byte_class: Optional[jax.Array] = None   # (257,) int32: byte→class,
                                             #   [256] = dead class k
    class_table: Optional[jax.Array] = None  # (k+1, W) uint32
    # ---- class-pair stride (one W-word gather per TWO bytes):
    #   S2 = ((S<<2) | (I<<1) | I) & R'[c1,c2]
    #   R'[c1,c2] = ((T[c1]<<1) | I) & T[c2]
    # (exact: expanding ((S<<2)|(I<<1)|I) & ((T1<<1)|I) & T2 reproduces
    # the two-step shift-and because every cross term is absorbed by the
    # unconditional I coverage of initial states).  Odd-position match
    # ends are collected via FA[c1] = T[c1] & final.
    pair_reach: Optional[jax.Array] = None   # ((k+1)^2, W) uint32
    pair_final: Optional[jax.Array] = None   # (k+1, W) uint32: T[c] & F

    @classmethod
    def from_bitap(cls, t: BitapTables, classes: bool = True
                   ) -> "ScanTables":
        bt = t.byte_table.astype(np.uint32)
        planes = np.concatenate(
            [((bt >> (8 * k)) & 0xFF).astype(np.float32) for k in range(4)],
            axis=1,
        )
        fields = dict(
            byte_table=jnp.asarray(bt),
            byte_planes=jnp.asarray(planes, dtype=jnp.bfloat16),
            init_mask=jnp.asarray(t.init_mask, dtype=jnp.uint32),
            final_mask=jnp.asarray(t.final_mask, dtype=jnp.uint32),
        )
        if classes:
            byte_class, T, pair_reach, pair_final, k = \
                build_class_pair_tables(bt, t.init_mask, t.final_mask)
            fields.update(
                byte_class=jnp.asarray(byte_class),
                class_table=jnp.asarray(T),
                pair_reach=jnp.asarray(pair_reach),
                pair_final=jnp.asarray(pair_final),
            )
        return cls(**fields)

    @property
    def n_words(self) -> int:
        return self.byte_table.shape[1]

    @property
    def n_classes(self) -> int:
        """Real classes (excluding the dead padding class)."""
        return self.class_table.shape[0] - 1

    def tree_flatten(self):
        return (self.byte_table, self.byte_planes, self.init_mask,
                self.final_mask, self.byte_class, self.class_table,
                self.pair_reach, self.pair_final), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def build_class_pair_tables(byte_table: np.ndarray, init_mask: np.ndarray,
                            final_mask: np.ndarray,
                            k_pad: Optional[int] = None,
                            uniq_inv=None):
    """Byte-class compression + folded pair recurrence tables — the ONE
    construction shared by the single-chip tables (ScanTables.from_bitap)
    and the per-shard sharded tables (parallel/shard.py), so the
    recurrence can never diverge between paths (round-4 review).

    Returns (byte_class (257,), class_table (K+1, W), pair_reach
    ((K+1)^2, W), pair_final (K+1, W), k) as numpy; the DEAD class (zero
    reach) sits at index K = ``k_pad or k`` and byte_class[256] maps to
    it.  ``k_pad`` ≥ k pads the class axis (sharded paths need a uniform
    K across shards); padding rows keep all-zero reach.  ``uniq_inv``
    lets a caller that already ran the axis-0 unique (the sharded k_max
    pre-pass) hand the (uniq, inv) pair in instead of paying it twice."""
    bt = byte_table.astype(np.uint32)
    if uniq_inv is None:
        uniq, inv = np.unique(bt, axis=0, return_inverse=True)
    else:
        uniq, inv = uniq_inv
    inv = np.asarray(inv).ravel()  # numpy <2.0 returns (256, 1), axis=0
    k = int(uniq.shape[0])
    K = k_pad if k_pad is not None else k
    if K < k:
        raise ValueError("k_pad=%d < actual class count %d" % (K, k))
    T = np.zeros((K + 1, bt.shape[1]), np.uint32)
    T[:k] = uniq
    byte_class = np.concatenate(
        [inv.astype(np.int32), np.asarray([K], np.int32)])
    init = init_mask.astype(np.uint32)[None, None, :]
    pair = ((T[:, None, :] << np.uint32(1)) | init) & T[None, :, :]
    pair_reach = pair.reshape((K + 1) * (K + 1), -1)
    pair_final = T & final_mask.astype(np.uint32)[None, :]
    return byte_class, T, pair_reach, pair_final, k


def classes_for(byte_class: jax.Array, tokens: jax.Array,
                lengths: jax.Array) -> jax.Array:
    """(B, L) byte rows → (B, L) class ids with padding (pos ≥ length)
    mapped to the DEAD class via the 256 sentinel."""
    L = tokens.shape[1]
    toks = jnp.where(
        jnp.arange(L, dtype=jnp.int32)[None, :]
        < lengths.astype(jnp.int32)[:, None],
        jnp.asarray(tokens).astype(jnp.int32), jnp.int32(256))
    return jnp.take(byte_class, toks, axis=0).astype(jnp.int32)


def _reach_take(tables: ScanTables, bytes_t: jax.Array) -> jax.Array:
    """B[byte] via dynamic gather — fast on CPU, slow on TPU."""
    return jnp.take(tables.byte_table, bytes_t, axis=0)


def _reach_onehot(tables: ScanTables, bytes_t: jax.Array) -> jax.Array:
    """B[byte] via one-hot × byte-plane matmul — rides the MXU.

    onehot (B, 256) bf16 @ planes (256, 4W) bf16 → f32, exact for values
    ≤255; the four uint8 planes are recombined with shifts/ors."""
    B = bytes_t.shape[0]
    W = tables.n_words
    onehot = (bytes_t[:, None] == jnp.arange(256, dtype=jnp.int32)[None, :])
    planes = jnp.dot(onehot.astype(jnp.bfloat16), tables.byte_planes,
                     preferred_element_type=jnp.float32)
    p = planes.astype(jnp.uint32).reshape(B, 4, W)
    return (p[:, 0] | (p[:, 1] << jnp.uint32(8))
            | (p[:, 2] << jnp.uint32(16)) | (p[:, 3] << jnp.uint32(24)))


def scan_bytes(
    tables: ScanTables,
    tokens: jax.Array,   # (B, L) int32/uint8
    lengths: jax.Array,  # (B,) int32
    state: Optional[jax.Array] = None,  # (B, W) uint32 — streaming carry
    match: Optional[jax.Array] = None,  # (B, W) uint32 — sticky accumulator
    unroll: int = 8,
    gather: str = "auto",  # "take" | "onehot" | "auto"
) -> Tuple[jax.Array, jax.Array]:
    """Scan a batch of byte rows; returns (match, state) after each row's
    ``length`` bytes.  Pass the returned ``state``/``match`` back in for the
    next chunk of the same streams (benchmark config #5)."""
    B, L = tokens.shape
    W = tables.n_words
    if state is None:
        state = jnp.zeros((B, W), dtype=jnp.uint32)
    if match is None:
        match = jnp.zeros((B, W), dtype=jnp.uint32)
    # Benchmarked on TPU v5e (full 1.4k-rule corpus, W=291, B=1024, L=1024,
    # K=65 in-dispatch amortized — see utils/microbench.py for why naive
    # timing lies here): take ≈ 200 MB/s, onehot ≈ 100 MB/s.  XLA lowers
    # the (256, W) row gather acceptably, so "take" is the default.
    if gather == "auto":
        gather = "take"
    reach_fn = _reach_take if gather == "take" else _reach_onehot

    tokens_t = jnp.transpose(tokens.astype(jnp.int32))  # (L, B): scan axis first
    steps = jnp.arange(L, dtype=jnp.int32)
    lengths = lengths.astype(jnp.int32)

    init = tables.init_mask[None, :]
    final = tables.final_mask[None, :]

    def step(carry, xs):
        S, M = carry
        bytes_t, t = xs
        reach = reach_fn(tables, bytes_t)  # (B, W)
        S_new = ((S << jnp.uint32(1)) | init) & reach
        valid = (t < lengths)[:, None]  # (B, 1)
        S = jnp.where(valid, S_new, S)
        M = jnp.where(valid, M | (S_new & final), M)
        return (S, M), None

    (state, match), _ = jax.lax.scan(
        step, (state, match), (tokens_t, steps), unroll=unroll
    )
    return match, state


@functools.partial(jax.jit, static_argnames=("unroll", "gather"))
def scan_bytes_jit(tables, tokens, lengths, state=None, match=None,
                   unroll: int = 8, gather: str = "auto"):
    return scan_bytes(tables, tokens, lengths, state, match, unroll, gather)


def scan_pairs(
    tables: ScanTables,
    tokens: jax.Array,   # (B, L) int32/uint8, L even
    lengths: jax.Array,  # (B,) int32
    state: Optional[jax.Array] = None,
    match: Optional[jax.Array] = None,
    unroll: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Class-pair-stride scan: L/2 steps, ONE (B, W) reach gather per TWO
    bytes (see ScanTables.pair_reach for the folded recurrence) plus one
    small (B, W) gather for odd-position match ends.  Returns the same
    (match, state) as ``scan_bytes``, with one contract difference: rows
    shorter than L are padded with the DEAD class, so their returned
    ``state`` is zero, not the state after ``length`` bytes — use this
    path for request scans (only ``match`` is consumed) and equal-length
    chunk waves, NOT for carrying state across ragged streaming chunks.
    """
    B, L = tokens.shape
    if L % 2:
        raise ValueError("scan_pairs needs even L (pad_rows rounds to 128)")
    W = tables.n_words
    if state is None:
        state = jnp.zeros((B, W), dtype=jnp.uint32)
    if match is None:
        match = jnp.zeros((B, W), dtype=jnp.uint32)
    k1 = tables.class_table.shape[0]  # k + 1 (dead class last)

    # byte → class, with padding mapped to the dead class (reach 0): the
    # scan needs no per-step validity selects at all
    cls = classes_for(tables.byte_class, tokens, lengths)  # (B, L)
    c1 = jnp.transpose(cls[:, 0::2])                      # (L/2, B)
    c2 = jnp.transpose(cls[:, 1::2])
    pair_idx = c1 * jnp.int32(k1) + c2

    I = tables.init_mask[None, :]
    IOR = (I << jnp.uint32(1)) | I
    final = tables.final_mask[None, :]

    def step(carry, xs):
        S, M = carry
        pidx, cc1 = xs
        R = jnp.take(tables.pair_reach, pidx, axis=0)     # (B, W)
        FA1 = jnp.take(tables.pair_final, cc1, axis=0)    # (B, W)
        M = M | (((S << jnp.uint32(1)) | I) & FA1)        # ends at byte 1
        S = ((S << jnp.uint32(2)) | IOR) & R
        M = M | (S & final)                               # ends at byte 2
        return (S, M), None

    (state, match), _ = jax.lax.scan(
        step, (state, match), (pair_idx, c1), unroll=unroll)
    return match, state


@functools.partial(jax.jit, static_argnames=("unroll",))
def scan_pairs_jit(tables, tokens, lengths, state=None, match=None,
                   unroll: int = 8):
    return scan_pairs(tables, tokens, lengths, state, match, unroll)


def scan_bytes_reference(tables: ScanTables, data: bytes) -> np.ndarray:
    """Single-row convenience wrapper (numpy in/out) for tests/debugging."""
    if len(data) == 0:
        return np.zeros((tables.n_words,), dtype=np.uint32)
    tokens = jnp.asarray(np.frombuffer(data, dtype=np.uint8)[None, :])
    lengths = jnp.asarray([len(data)], dtype=jnp.int32)
    match, _ = scan_bytes(tables, tokens, lengths)
    return np.asarray(match[0])


def pad_rows(rows: list, max_len: Optional[int] = None, round_to: int = 128
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side helper: pack variable-length byte strings into a padded
    (B, L) uint8 matrix + lengths.  L is rounded up to ``round_to`` so jit
    sees few distinct shapes (length-bucketing happens in serve/batcher)."""
    if not rows:
        return np.zeros((0, round_to), np.uint8), np.zeros((0,), np.int32)
    L = max_len or max(1, max(len(r) for r in rows))
    L = ((L + round_to - 1) // round_to) * round_to
    out = np.zeros((len(rows), L), dtype=np.uint8)
    lengths = np.zeros((len(rows),), dtype=np.int32)
    for i, r in enumerate(rows):
        r = r[:L]
        out[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
        lengths[i] = len(r)
    return out, lengths
