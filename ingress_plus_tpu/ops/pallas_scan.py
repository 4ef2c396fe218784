"""Pallas TPU kernel for the batched bitap scan.

Same contract as ops/scan.py::scan_bytes — this is the hand-scheduled
version of the hot loop (the reference's per-byte libproton automaton scan,
SURVEY.md §3.3 hot loop #2).  What the kernel does that the XLA lax.scan
lowering can't:

- **Decoupled gather.** The serial dependency (S' depends on S) forces one
  step per input byte, and XLA re-gathers B[byte] from the (256, W) table
  inside every step.  Here the reach masks for a whole CL-byte chunk are
  computed up front on the MXU — one-hot(bytes) @ byte-planes in bf16
  (values ≤255 are exact) — and the serial chain then runs as pure VPU
  element-wise ops against VMEM scratch.
- **Early exit on ragged batches.** The serial loop bound is the *tile's*
  max row length (read on-chip), so a tile of short rows skips its padded
  tail entirely; XLA's scan always walks the full padded length.
- **State residency.** (state, match) live in the output VMEM blocks across
  the whole length axis (grid dim 1 is sequential), so HBM sees each token
  byte once and each state word twice.

Token layout: the kernels read tokens position-major and LANE-DENSE — a
(CL·TB/MR, MR) int32 block per (batch tile, chunk), element t·TB + r = byte
t of tile row r, one matmul chunk per block row.  XLA builds it outside the
kernel (one fused transpose; in-kernel (TB, CL)→(CL·TB, 1) reshapes are
unsupported shape casts in Mosaic).  A (CL·TB, 1) column — the layout this
file started with — pads its lane dimension 1→128 in VMEM *and* in HBM, a
128x blow-up that cannot hold the long-row tiers.  The one-hot is therefore
built TRANSPOSED, (K, MR) = iota over sublanes == the token row broadcast
along sublanes, and the MXU contracts dimension 0 of both operands.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ingress_plus_tpu.ops.scan import ScanTables, classes_for, scan_pairs_jit
from ingress_plus_tpu.utils.platform import on_tpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class _JitArgument:
    """A scanner goes into a jit as an ARGUMENT (the engine's per-bucket
    program, models/engine.py ``scan_fold_bucket``): the device arrays
    named in ``ARRAYS`` are its pytree leaves and everything else — the
    tiling ints, the device a replica is placed on — is static
    structure.  A hot swap that keeps the geometry then keeps the
    executables, and no table becomes a constant of the program."""

    ARRAYS: Tuple[str, ...] = ()

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        jax.tree_util.register_pytree_node_class(cls)

    def tree_flatten(self):
        static = tuple(sorted(
            kv for kv in vars(self).items() if kv[0] not in self.ARRAYS))
        return tuple(getattr(self, k) for k in self.ARRAYS), static

    @classmethod
    def tree_unflatten(cls, static, arrays):
        new = object.__new__(cls)
        vars(new).update(static)
        vars(new).update(zip(cls.ARRAYS, arrays))
        return new


#: scoped-VMEM ceiling a kernel may ask for — half of a v5e core's
#: 128 MiB, so a pack that outgrows it fails in the constructor with its
#: numbers instead of inside Mosaic
_VMEM_CEILING = 64 << 20


def _vmem_limit(K: int, Wp: int, TB: int, CL: int, MR: int,
                n_reach: int, n_tok: int) -> int:
    """``vmem_limit_bytes`` for one grid step, computed from the block
    shapes: blocked operands are double-buffered by the pipeline, the
    lengths column pads its lane dimension to 128, and stage 1 holds one
    (MR, 4*Wp) f32 matmul result plus its int32 cast and the one-hot."""
    blk = CL * TB
    need = (2 * K * 4 * Wp * 2            # bf16 byte planes
            + 2 * n_tok * blk * 4         # lane-dense token blocks
            + n_reach * blk * Wp * 4      # reach scratch
            + 2 * 4 * TB * Wp * 4         # state/match carry in + out
            + 2 * TB * 128 * 4            # lengths column
            + 2 * 2 * 8 * Wp * 4          # init/final rows
            + 2 * MR * 4 * Wp * 4         # matmul result + int32 cast
            + K * MR * 6)                 # one-hot, f32 then bf16
    if need > _VMEM_CEILING:
        raise ValueError(
            "scan kernel needs %.1f MiB of VMEM per grid step at K=%d "
            "Wp=%d TB=%d CL=%d MR=%d (%d reach buffer(s)), over the "
            "%d MiB ceiling — shrink CL or TB"
            % (need / 2**20, K, Wp, TB, CL, MR, n_reach,
               _VMEM_CEILING >> 20))
    # 2x: Mosaic's own temporaries (relayouts, spills of the unrolled
    # stage-1 blocks) are not in the shape arithmetic above
    return min(max(2 * need, 16 << 20), _VMEM_CEILING)


def check_compiled_tiling(TB: int, CL: int, MR: int) -> None:
    """What Mosaic (not the interpreter) needs of a token block
    (CL*TB/MR, MR): lane-dense rows and whole (8, 128) int32 tiles."""
    if MR % 128 or (CL * TB // MR) % 8:
        raise ValueError(
            "tiling cannot compile: need MR %% 128 == 0 and "
            "(CL*TB/MR) %% 8 == 0; got TB=%d CL=%d MR=%d" % (TB, CL, MR))


def _pos_major(tokens, TB: int, CL: int, MR: int):
    """(B, L) int32 → (B*L/MR, MR) position-major token rows: rows
    [(i*nk + k) * CL*TB/MR, +CL*TB/MR) hold chunk k of batch tile i,
    flat element t*TB + r = byte t of tile row r.  One fused XLA
    transpose (see the module docstring)."""
    B, L = tokens.shape
    nb, nk = B // TB, L // CL
    return (tokens.reshape(nb, TB, nk, CL).transpose(0, 2, 3, 1)
            .reshape(nb * nk * CL * TB // MR, MR))


def _reach_rows(tok_ref, j: int, planes_ref, Wp: int):
    """Stage 1 for matmul chunk ``j`` of a token block: the (MR, Wp)
    int32 reach rows of its MR (position, row) pairs, gathered on the
    MXU as one-hot(tokens)^T @ byte-planes (bf16, values <= 255 exact)."""
    K = planes_ref.shape[0]
    sub = tok_ref[j:j + 1, :]                                 # (1, MR)
    ids = jax.lax.broadcasted_iota(jnp.int32, (K, sub.shape[1]), 0)
    onehot_t = (ids == sub).astype(jnp.float32).astype(jnp.bfloat16)
    planes = jax.lax.dot_general(
        onehot_t, planes_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (MR, 4*Wp)
    p = planes.astype(jnp.int32)
    return (p[:, 0 * Wp:1 * Wp]
            | (p[:, 1 * Wp:2 * Wp] << 8)
            | (p[:, 2 * Wp:3 * Wp] << 16)
            | (p[:, 3 * Wp:4 * Wp] << 24))


def _scan_kernel(tok_ref, lens_ref, planes_ref, init_ref, final_ref,
                 state_in_ref, match_in_ref, match_ref, state_ref,
                 reach_ref, *, CL: int, TB: int, MR: int, Wp: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        state_ref[:] = state_in_ref[:]
        match_ref[:] = match_in_ref[:]

    t_max = jnp.max(lens_ref[:])      # tile's longest row
    t_rem = t_max - k * CL            # bytes of real work left in this chunk

    @pl.when(t_rem > 0)
    def _():
        # ---- stage 1: reach masks for every (position, row) via MXU ------
        for j in range(CL * TB // MR):
            @pl.when(j * (MR // TB) < t_rem)
            def _():
                reach_ref[pl.ds(j * MR, MR), :] = _reach_rows(
                    tok_ref, j, planes_ref, Wp)

        # ---- stage 2: serial shift-AND chain on the VPU ------------------
        init = init_ref[:]                                    # (1, Wp)
        final = final_ref[:]
        # lane broadcast hoisted out of the chain: the per-step validity
        # compare then yields a full (TB, Wp) mask directly
        lens = jnp.broadcast_to(lens_ref[:], (TB, Wp))

        def step(t, carry):
            S, M = carry
            reach = reach_ref[pl.ds(pl.multiple_of(t * TB, TB), TB), :]
            S_new = ((S << 1) | init) & reach
            valid = (k * CL + t) < lens
            S = jnp.where(valid, S_new, S)
            M = jnp.where(valid, M | (S_new & final), M)
            return (S, M)

        S, M = jax.lax.fori_loop(0, jnp.minimum(CL, t_rem), step,
                                 (state_ref[:], match_ref[:]))
        state_ref[:] = S
        match_ref[:] = M


@functools.partial(
    jax.jit, static_argnames=("TB", "CL", "MR", "interpret"))
def _pallas_scan(tokens, lengths, planes, init, final, state, match,
                 TB: int, CL: int, MR: int, interpret: bool):
    """tokens (B, L) int32 padded to tile multiples; lengths (B, 1) int32;
    state/match (B, Wp) int32.  Returns (match, state), (B, Wp) int32."""
    B, L = tokens.shape
    Wp = init.shape[1]
    K = planes.shape[0]
    nb, nk = B // TB, L // CL
    if not interpret:
        check_compiled_tiling(TB, CL, MR)
    rows = CL * TB // MR

    kernel = functools.partial(_scan_kernel, CL=CL, TB=TB, MR=MR, Wp=Wp)
    out_m, out_s = pl.pallas_call(
        kernel,
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((rows, MR), lambda i, k, nk=nk: (i * nk + k, 0),
                         memory_space=pltpu.VMEM),       # tokens (pos-major)
            pl.BlockSpec((TB, 1), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),       # lengths
            pl.BlockSpec((K, 4 * Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),       # byte planes
            pl.BlockSpec((1, Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),       # init
            pl.BlockSpec((1, Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),       # final
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),       # state carry in
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),       # match carry in
        ],
        out_specs=[
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Wp), jnp.int32),    # match
            jax.ShapeDtypeStruct((B, Wp), jnp.int32),    # state
        ],
        scratch_shapes=[pltpu.VMEM((CL * TB, Wp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(K, Wp, TB, CL, MR,
                                         n_reach=1, n_tok=1)),
        interpret=interpret,
    )(_pos_major(tokens, TB, CL, MR), lengths, planes, init, final,
      state, match)
    return out_m, out_s


class PallasScanner(_JitArgument):
    """Caches the padded/packed device tables for repeated kernel calls
    (serving + bench reuse one instance; hot-swap = build a new one)."""

    ARRAYS = ("planes", "init", "final")

    def __init__(self, tables: ScanTables, TB: int = 64, CL: int = 32,
                 MR: int = 128):
        W = tables.n_words
        Wp = _round_up(max(W, 128), 128)
        self.W, self.Wp, self.TB, self.CL = W, Wp, TB, CL
        self.MR = min(MR, CL * TB)
        # stage 1 writes reach rows in MR-row blocks and gates each block
        # by position — misaligned tilings would leave scratch rows stale
        # and silently corrupt the NFA state, so reject them loudly
        if TB % 8 or (CL * TB) % self.MR or self.MR % TB:
            raise ValueError(
                "invalid tiling: need TB %% 8 == 0, MR %% TB == 0 and "
                "(CL*TB) %% MR == 0; got TB=%d CL=%d MR=%d"
                % (TB, CL, self.MR))
        bt = np.zeros((256, Wp), np.uint32)
        bt[:, :W] = np.asarray(tables.byte_table)
        self.planes = jnp.asarray(np.concatenate(
            [((bt >> (8 * k)) & 0xFF).astype(np.float32) for k in range(4)],
            axis=1), jnp.bfloat16)
        init = np.zeros((1, Wp), np.int32)
        init[0, :W] = np.asarray(tables.init_mask).view(np.int32)
        final = np.zeros((1, Wp), np.int32)
        final[0, :W] = np.asarray(tables.final_mask).view(np.int32)
        self.init, self.final = jnp.asarray(init), jnp.asarray(final)

    def __call__(self, tokens, lengths, state=None, match=None,
                 interpret: bool = False):
        """scan_bytes contract: returns (match, state) as (B, W) uint32."""
        B, L = tokens.shape
        TB, CL, W, Wp = self.TB, self.CL, self.W, self.Wp
        Bp = _round_up(max(B, TB), TB)
        Lp = _round_up(max(L, CL), CL)

        def as_i32(x):
            x = jnp.asarray(x)
            return (jax.lax.bitcast_convert_type(x, jnp.int32)
                    if x.dtype == jnp.uint32 else x.astype(jnp.int32))

        tok_p = jnp.zeros((Bp, Lp), jnp.int32).at[:B, :L].set(
            jnp.asarray(tokens).astype(jnp.int32))
        len_p = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(
            jnp.asarray(lengths).astype(jnp.int32))
        sin = jnp.zeros((Bp, Wp), jnp.int32)
        if state is not None:
            sin = sin.at[:B, :W].set(as_i32(state))
        min_ = jnp.zeros((Bp, Wp), jnp.int32)
        if match is not None:
            min_ = min_.at[:B, :W].set(as_i32(match))

        out_m, out_s = _pallas_scan(
            tok_p, len_p, self.planes, self.init, self.final, sin, min_,
            TB=TB, CL=CL, MR=self.MR, interpret=interpret)
        to_u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
        return to_u32(out_m[:B, :W]), to_u32(out_s[:B, :W])


def pallas_scan_bytes(
    tables: ScanTables,
    tokens: jax.Array,
    lengths: jax.Array,
    state: Optional[jax.Array] = None,
    match: Optional[jax.Array] = None,
    TB: int = 64,
    CL: int = 32,
    MR: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One-shot convenience wrapper (table packing not cached); equivalence
    with scan_bytes is asserted bit-for-bit in tests/test_pallas_scan.py."""
    return PallasScanner(tables, TB=TB, CL=CL, MR=MR)(
        tokens, lengths, state, match, interpret=interpret)


# ---------------------------------------------------------------------------
# Class-pair Pallas kernel (round 4, VERDICT item #8)
# ---------------------------------------------------------------------------
#
# Why the byte kernel lost its own bake-off (pallas ≈ 254k vs pair ≈ 357k
# req/s on v5e): its serial VPU chain runs one shift-AND step per BYTE,
# while the XLA pair impl runs one per BYTE PAIR.  At W≈500+ (Wp 640
# lanes) the chain dominates, so the hand kernel's better gather couldn't
# make up a 2× step-count handicap.  This kernel takes BOTH wins:
#
# - **Pair chain.**  The serial loop consumes two bytes per step using the
#   same folded recurrence as ops/scan.py::scan_pairs —
#       pairR = ((R1 << 1) | I) & R2
#       M    |= ((S << 1) | I) & (R1 & final)      (ends at odd byte)
#       S     = ((S << 2) | (I<<1) | I) & pairR
#       M    |= S & final                          (ends at even byte)
#   where R1/R2 are the two bytes' single-byte reach rows.  Expanding the
#   fold reproduces two shift-AND steps exactly (see ScanTables notes).
# - **Class-compressed MXU gather.**  Bytes are mapped to Hyperscan-style
#   byte classes OUTSIDE the kernel (tiny 257-entry XLA gather); stage 1
#   one-hots over K1 ≤ 256 classes instead of 256 raw bytes, so the MXU
#   matmul contracts over the (usually much smaller) class count.
# - **Cross-chunk overlap.**  reach scratch is DOUBLE-BUFFERED: iteration
#   k first issues the MXU stage for chunk k+1 into buffer (k+1)%2 (its
#   tokens come from a second, shifted BlockSpec view of the same array),
#   then runs the serial chain of chunk k from buffer k%2.  The two
#   stages touch disjoint buffers, so Mosaic is free to run chunk k+1's
#   matmuls under chunk k's VPU chain instead of serializing them.
#
# Dead-class padding (index K-1 has all-zero reach) replaces per-step
# validity masks, exactly like scan_pairs: a padded row's state dies and
# its match is stable, so the chain needs no lens compares at all.  The
# state contract therefore matches scan_pairs, NOT scan_bytes: rows
# shorter than L return state 0 — use for request scans and equal-length
# chunk waves (match is what serving consumes).


def _pair_kernel(cls_ref, cls_nx_ref, lens_ref, planes_ref, init_ref,
                 final_ref, state_in_ref, match_in_ref, match_ref,
                 state_ref, reach0_ref, reach1_ref, *, CL: int, TB: int,
                 MR: int, Wp: int, NK: int):
    k = pl.program_id(1)
    even = (k % 2) == 0     # chunk k's reach lives in buf (k%2); the two
                            # buffers are separate scratch refs so all
                            # ref indexing stays static under Mosaic

    @pl.when(k == 0)
    def _():
        state_ref[:] = state_in_ref[:]
        match_ref[:] = match_in_ref[:]

    t_max = jnp.max(lens_ref[:])

    def stage1(tok_ref, buf_ref, rem):
        """Reach rows for one whole chunk into ``buf_ref`` (MXU).

        The guard rounds ``rem`` UP TO EVEN: the chain's last pair reads
        position rem itself when rem is odd (its R2 — a dead-class
        padding byte whose computed reach is all-zero), so that row MUST
        be freshly computed; guarding on bare ``rem`` left it stale from
        two chunks earlier and fabricated matches (round-4 review repro:
        TB=8/MR=8, 49-byte row)."""
        rem_even = ((rem + 1) // 2) * 2
        for j in range(CL * TB // MR):
            @pl.when(j * (MR // TB) < rem_even)
            def _():
                buf_ref[pl.ds(j * MR, MR), :] = _reach_rows(
                    tok_ref, j, planes_ref, Wp)

    # prime buffer 0 with chunk 0's reach on the first grid step
    @pl.when(k == 0)
    def _():
        stage1(cls_ref, reach0_ref, t_max)

    # issue chunk k+1's MXU work FIRST (into the other buffer) — program
    # order ahead of the chain, disjoint buffer, so Mosaic may overlap it
    # under the serial VPU chain of chunk k
    nx_rem = t_max - (k + 1) * CL

    @pl.when((k + 1 < NK) & (nx_rem > 0) & even)
    def _():
        stage1(cls_nx_ref, reach1_ref, nx_rem)

    @pl.when((k + 1 < NK) & (nx_rem > 0) & jnp.logical_not(even))
    def _():
        stage1(cls_nx_ref, reach0_ref, nx_rem)

    # ... then run chunk k's serial pair chain from its own buffer
    t_rem = t_max - k * CL

    def chain(buf_ref):
        init = init_ref[:]                                    # (1, Wp)
        final = final_ref[:]
        ior = (init << 1) | init

        def step(t, carry):
            S, M = carry
            at = pl.multiple_of(2 * t * TB, TB)
            R1 = buf_ref[pl.ds(at, TB), :]
            R2 = buf_ref[pl.ds(at + TB, TB), :]
            pairR = ((R1 << 1) | init) & R2
            M = M | (((S << 1) | init) & (R1 & final))
            S = ((S << 2) | ior) & pairR
            M = M | (S & final)
            return (S, M)

        n_pairs = (jnp.minimum(CL, t_rem) + 1) // 2
        S, M = jax.lax.fori_loop(0, n_pairs, step,
                                 (state_ref[:], match_ref[:]))
        state_ref[:] = S
        match_ref[:] = M

    @pl.when((t_rem > 0) & even)
    def _():
        chain(reach0_ref)

    @pl.when((t_rem > 0) & jnp.logical_not(even))
    def _():
        chain(reach1_ref)


@functools.partial(
    jax.jit, static_argnames=("TB", "CL", "MR", "interpret"))
def _pallas_pair_scan(cls_tokens, lengths, planes, init, final, state,
                      match, TB: int, CL: int, MR: int, interpret: bool):
    """cls_tokens (B, L) int32 CLASS indices (dead class = K1-1) padded to
    tile multiples; otherwise the _pallas_scan contract."""
    B, L = cls_tokens.shape
    Wp = init.shape[1]
    K1p = planes.shape[0]
    nb, nk = B // TB, L // CL
    if not interpret:
        check_compiled_tiling(TB, CL, MR)
    rows = CL * TB // MR
    toks_pm = _pos_major(cls_tokens, TB, CL, MR)

    kernel = functools.partial(_pair_kernel, CL=CL, TB=TB, MR=MR, Wp=Wp,
                               NK=nk)
    blk = CL * TB
    out_m, out_s = pl.pallas_call(
        kernel,
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((rows, MR), lambda i, k, nk=nk: (i * nk + k, 0),
                         memory_space=pltpu.VMEM),   # chunk k classes
            # chunk k+1's classes (clamped at the last chunk): feeds the
            # double-buffered prefetch stage
            pl.BlockSpec((rows, MR),
                         lambda i, k, nk=nk: (
                             i * nk + jnp.minimum(k + 1, nk - 1), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TB, 1), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),   # lengths
            pl.BlockSpec((K1p, 4 * Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),   # class planes
            pl.BlockSpec((1, Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Wp), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),   # state carry in
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),   # match carry in
        ],
        out_specs=[
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TB, Wp), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Wp), jnp.int32),    # match
            jax.ShapeDtypeStruct((B, Wp), jnp.int32),    # state
        ],
        scratch_shapes=[pltpu.VMEM((blk, Wp), jnp.int32),
                        pltpu.VMEM((blk, Wp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(K1p, Wp, TB, CL, MR,
                                         n_reach=2, n_tok=2)),
        interpret=interpret,
    )(toks_pm, toks_pm, lengths, planes, init, final, state, match)
    return out_m, out_s


def check_pair_tiling(TB: int, CL: int, MR: int) -> int:
    """Validate the (TB, CL, MR) tile config; returns the clamped MR."""
    MR = min(MR, CL * TB)
    if TB % 8 or CL % 2 or (CL * TB) % MR or MR % TB:
        raise ValueError(
            "invalid tiling: need TB %% 8 == 0, CL even, MR %% TB == 0 "
            "and (CL*TB) %% MR == 0; got TB=%d CL=%d MR=%d"
            % (TB, CL, MR))
    return MR


def pack_pair_tables(class_table: np.ndarray, init_mask: np.ndarray,
                     final_mask: np.ndarray):
    """Pad + plane-split class tables into the _pallas_pair_scan input
    layout — the ONE packing shared by PallasPairScanner (single chip)
    and ShardedEngine's per-shard pallas2 path.

    class_table (K1, W) uint32 with the DEAD class (all-zero reach)
    LAST; init/final (W,) uint32.  Returns (planes (K1p, 4*Wp) float32
    — byte planes of the uint32 words, exact in bf16 since every value
    <= 255; init (1, Wp) int32; final (1, Wp) int32; K1p; Wp), padded to
    the kernel's 128-lane tiles with all-zero (dead) rows."""
    K1, W = class_table.shape
    Wp = _round_up(max(W, 128), 128)
    K1p = _round_up(max(K1, 128), 128)
    ct = np.zeros((K1p, Wp), np.uint32)
    ct[:K1, :W] = np.asarray(class_table)
    planes = np.concatenate(
        [((ct >> (8 * j)) & 0xFF).astype(np.float32) for j in range(4)],
        axis=1)
    init = np.zeros((1, Wp), np.int32)
    init[0, :W] = np.asarray(init_mask).view(np.int32)
    final = np.zeros((1, Wp), np.int32)
    final[0, :W] = np.asarray(final_mask).view(np.int32)
    return planes, init, final, K1p, Wp


class PallasPairScanner(_JitArgument):
    """Class-pair Pallas kernel with cached packed tables.

    Same call contract as PallasScanner, with scan_pairs' state caveat
    (dead-class padding: short rows return state 0)."""

    ARRAYS = ("planes", "init", "final", "byte_class")

    def __init__(self, tables: ScanTables, TB: int = 64, CL: int = 16,
                 MR: int = 128):
        if tables.byte_class is None:
            raise ValueError("tables built without byte classes")
        W = tables.n_words
        planes, init, final, K1p, Wp = pack_pair_tables(
            np.asarray(tables.class_table), np.asarray(tables.init_mask),
            np.asarray(tables.final_mask))
        self.W, self.Wp, self.TB, self.CL, self.K1p = W, Wp, TB, CL, K1p
        self.MR = check_pair_tiling(TB, CL, MR)
        self.planes = jnp.asarray(planes, jnp.bfloat16)
        self.init, self.final = jnp.asarray(init), jnp.asarray(final)
        self.byte_class = tables.byte_class        # (257,) int32
        self.dead = int(tables.class_table.shape[0]) - 1

    def __call__(self, tokens, lengths, state=None, match=None,
                 interpret: bool = False):
        B, L = tokens.shape
        TB, CL, W, Wp = self.TB, self.CL, self.W, self.Wp
        Bp = _round_up(max(B, TB), TB)
        Lp = _round_up(max(L, CL), CL)

        def as_i32(x):
            x = jnp.asarray(x)
            return (jax.lax.bitcast_convert_type(x, jnp.int32)
                    if x.dtype == jnp.uint32 else x.astype(jnp.int32))

        lengths = jnp.asarray(lengths).astype(jnp.int32)
        # byte → class with padding mapped to the dead class (tiny XLA
        # gather; the kernel then one-hots over classes, not bytes) —
        # the SAME mapping scan_pairs uses (ops/scan.py classes_for)
        cls = classes_for(self.byte_class, tokens, lengths)
        cls_p = jnp.full((Bp, Lp), self.dead, jnp.int32).at[:B, :L].set(cls)
        len_p = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(lengths)
        sin = jnp.zeros((Bp, Wp), jnp.int32)
        if state is not None:
            sin = sin.at[:B, :W].set(as_i32(state))
        min_ = jnp.zeros((Bp, Wp), jnp.int32)
        if match is not None:
            min_ = min_.at[:B, :W].set(as_i32(match))

        out_m, out_s = _pallas_pair_scan(
            cls_p, len_p, self.planes, self.init, self.final, sin, min_,
            TB=TB, CL=CL, MR=self.MR, interpret=interpret)
        to_u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
        return to_u32(out_m[:B, :W]), to_u32(out_s[:B, :W])


# ---------------------------------------------------------------------------
# Raw-byte fused kernel (ISSUE 13: "make the device path real")
# ---------------------------------------------------------------------------
#
# The pallas2 host contract still made the caller prep CLASS arrays: an
# eager (257,)-LUT gather (classes_for), eager padding ops, and an int32
# upcast — per dispatch, on the host/default-device boundary.  The
# Hyperflex observation (arXiv:2512.07123) is that for a shift-and NFA
# packed across vector lanes, any byte-level pre-mapping composes into
# the per-byte reach fetch: planes_byte[b] == planes_class[byte_class[b]]
# by construction, so a kernel that one-hots RAW byte values over 257
# rows (256 bytes + one dead padding index) computes bit-identical reach
# rows with NO host-side class mapping at all.  The host ships the uint8
# request bytes and the lengths — a memcpy — and everything else
# (dead-index padding select, position-major transpose, the MXU reach
# matmuls, the lane-packed pair chain) lives in ONE device program.
#
# The MXU price: the one-hot contraction runs over K1p = 384 padded rows
# instead of the pack's K1p (128 on the bundled pack) — 3x the stage-1
# matmul flops.  That stage overlaps the serial VPU chain (the pair
# kernel's double-buffered prefetch), so the trade buys host-prep and
# transfer volume with idle MXU cycles.  Measured truth lives in
# `utils/microbench --scan`; parity is CI-gated (tools/lint.py
# devicegate) in interpret mode.

#: the reserved dead padding index of the raw-byte planes (row 256 has
#: all-zero reach — a padded position kills its lane's state and leaves
#: the sticky match stable, exactly the scan_pairs dead-class contract)
DEAD_BYTE = 256


def pack_byte_pair_tables(byte_table: np.ndarray, init_mask: np.ndarray,
                          final_mask: np.ndarray):
    """pack_pair_tables on the RAW byte axis: 257 rows (byte values +
    the dead padding index LAST), padded to the kernel's 128-lane tiles
    (K1p = 384).  The byte→class LUT is gone — it composes into the
    planes (planes[b] = class_planes[byte_class[b]])."""
    W = byte_table.shape[1]
    bt = np.zeros((DEAD_BYTE + 1, W), np.uint32)
    bt[:256] = np.asarray(byte_table)
    return pack_pair_tables(bt, init_mask, final_mask)


@functools.partial(
    jax.jit, static_argnames=("TB", "CL", "MR", "interpret"))
def _fused_byte_scan(tokens, lengths, planes, init, final, state, match,
                     TB: int, CL: int, MR: int, interpret: bool):
    """Raw-byte fused device program: tokens (B, L) uint8 RAW request
    bytes, lengths (B,) int32, state/match (B, W) uint32.  The
    ragged/padding handling is one elementwise select (position >=
    length → DEAD_BYTE) that XLA fuses into the position-major
    transpose; the Mosaic pair kernel then needs no validity compares
    at all.  Returns (match, state) as (B, W) uint32."""
    B, L = tokens.shape
    W = state.shape[1]
    Wp = init.shape[1]
    Bp = _round_up(max(B, TB), TB)
    Lp = _round_up(max(L, CL), CL)
    lengths = lengths.reshape(B)
    toks = jnp.where(
        jnp.arange(L, dtype=jnp.int32)[None, :] < lengths[:, None],
        tokens.astype(jnp.int32), jnp.int32(DEAD_BYTE))
    cls_p = jnp.full((Bp, Lp), DEAD_BYTE, jnp.int32).at[:B, :L].set(toks)
    len_p = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(lengths)

    def as_i32p(x):
        x = jax.lax.bitcast_convert_type(x, jnp.int32)
        return jnp.zeros((Bp, Wp), jnp.int32).at[:B, :W].set(x)

    out_m, out_s = _pallas_pair_scan(
        cls_p, len_p, planes, init, final, as_i32p(state), as_i32p(match),
        TB=TB, CL=CL, MR=MR, interpret=interpret)
    to_u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    return to_u32(out_m[:B, :W]), to_u32(out_s[:B, :W])


class PallasByteScanner(_JitArgument):
    """Raw-byte fused scanner — serving name ``pallas3`` (ISSUE 13,
    docs/SCAN_KERNEL.md "Device path").

    Contract: uint8 request bytes + lengths IN, (match, state) uint32
    OUT; byte→reach mapping, ragged/padding handling and the
    lane-packed pair chain all execute inside one device program, so
    the host path per dispatch approaches a memcpy (see the module
    comment above for the design and its MXU trade).

    Backend dispatch: on a TPU the Mosaic kernel compiles and serves;
    on CPU (or ``mode="reference"``) the SAME math runs as the XLA
    class-pair lowering (``scan_pairs`` — bit-identical by the plane
    composition identity, pinned by tests/test_pallas_scan.py and the
    ``devicegate`` CI gate), so CPU tests can serve ``--scan-impl
    pallas3``.  ``interpret=True`` forces the Mosaic interpreter (the
    parity-test path).

    State contract = scan_pairs (dead padding): rows shorter than L
    return state 0 — request scans and equal-length chunk waves, NOT
    ragged streaming carries (streams keep the byte path)."""

    ARRAYS = ("planes", "init", "final", "tables")

    def __init__(self, tables: ScanTables, TB: int = 64, CL: int = 16,
                 MR: int = 128):
        if tables.pair_reach is None:
            raise ValueError(
                "tables built without byte classes (the reference "
                "lowering needs the pair tables)")
        W = tables.n_words
        planes, init, final, K1p, Wp = pack_byte_pair_tables(
            np.asarray(tables.byte_table), np.asarray(tables.init_mask),
            np.asarray(tables.final_mask))
        self.W, self.Wp, self.TB, self.CL, self.K1p = W, Wp, TB, CL, K1p
        self.MR = check_pair_tiling(TB, CL, MR)
        self.planes = jnp.asarray(planes, jnp.bfloat16)
        self.init, self.final = jnp.asarray(init), jnp.asarray(final)
        #: reference-lowering twin (a pytree — passed as a jit ARGUMENT
        #: so nothing constant-folds, the BENCH_r02 lesson)
        self.tables = tables
        self.device = None   # for_device() replicas record their chip

    # ------------------------------------------------------- placement

    def for_device(self, device):
        """Replica with the packed tables placed on ``device`` via the
        NamedSharding idiom (SNIPPETS.md [3]): a one-device mesh with a
        replicated PartitionSpec pins this lane's copy to its own chip,
        so N serve lanes dispatch the kernel concurrently — the
        ``tables_for`` sigpack-replication story (docs/MESH_SERVING.md)
        now covers the Pallas path too."""
        import copy

        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        sh = NamedSharding(Mesh(np.asarray([device]), ("lane",)),
                           PartitionSpec())
        new = copy.copy(self)
        new.planes = jax.device_put(self.planes, sh)
        new.init = jax.device_put(self.init, sh)
        new.final = jax.device_put(self.final, sh)
        new.tables = jax.device_put(self.tables, sh)
        new.device = device
        return new

    def _use_kernel(self) -> bool:
        """On a TPU the Mosaic kernel serves, always; the reference
        lowering exists for CPU tests (pallas_call without interpret
        raises there)."""
        return on_tpu()

    # --------------------------------------------------------- dispatch

    def __call__(self, tokens, lengths, state=None, match=None,
                 interpret: bool = False, mode: str = "auto"):
        """scan_bytes-shaped call: returns (match, state) (B, W) uint32.

        ``mode``: "auto" = Mosaic kernel on TPU backends, reference XLA
        lowering elsewhere; "kernel" forces the pallas_call (compiled,
        or Mosaic-interpreted with interpret=True); "reference" forces
        the XLA lowering."""
        tokens = jnp.asarray(tokens)
        B, L = tokens.shape
        W = self.W
        lengths = jnp.asarray(lengths).astype(jnp.int32).reshape(B)
        if mode == "auto":
            mode = "kernel" if (interpret or self._use_kernel()) \
                else "reference"

        def as_u32(x):
            if x is None:
                return jnp.zeros((B, W), jnp.uint32)
            x = jnp.asarray(x)
            return (x if x.dtype == jnp.uint32
                    else jax.lax.bitcast_convert_type(x, jnp.uint32))

        state, match = as_u32(state), as_u32(match)
        if mode == "reference":
            if L % 2:
                # the pair fold consumes two bytes per step; one extra
                # column is past every row's length, so classes_for
                # maps it to the dead class — math unchanged
                tokens = jnp.pad(tokens, ((0, 0), (0, 1)))
            return scan_pairs_jit(self.tables, tokens, lengths,
                                  state, match)
        return _fused_byte_scan(
            tokens, lengths, self.planes, self.init, self.final,
            state, match, TB=self.TB, CL=self.CL, MR=self.MR,
            interpret=interpret)
