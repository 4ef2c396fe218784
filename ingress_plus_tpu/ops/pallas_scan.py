"""Pallas TPU kernel for the batched bitap scan.

Same contract as ops/scan.py::scan_bytes — this is the hand-scheduled
version of the hot loop (the reference's per-byte libproton automaton scan,
SURVEY.md §3.3 hot loop #2).  Nothing serves it: the engine runs it only
under ``scan_impl="pallas"``.  It stays because on the v5e it is the
fastest scan of (64, 2048) rows on both packs (0.69-0.70 ms against
``scan_pairs``' 0.88-0.89) and behind ``scan_pairs`` at (512, 256) and
(8, 16384) (PERF.md §6, PR 31): the lead for a selection by row length.
What the kernel does that the XLA lax.scan lowering can't:

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

Token layout: the kernel reads tokens position-major and LANE-DENSE — a
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ingress_plus_tpu.ops.scan import ScanTables
from ingress_plus_tpu.utils.platform import on_tpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


#: scoped-VMEM ceiling a kernel may ask for — half of a v5e core's
#: 128 MiB, so a pack that outgrows it fails in the constructor with its
#: numbers instead of inside Mosaic
_VMEM_CEILING = 64 << 20


def _vmem_limit(K: int, Wp: int, TB: int, CL: int, MR: int) -> int:
    """``vmem_limit_bytes`` for one grid step, computed from the block
    shapes: blocked operands are double-buffered by the pipeline, the
    lengths column pads its lane dimension to 128, and stage 1 holds one
    (MR, 4*Wp) f32 matmul result plus its int32 cast and the one-hot."""
    blk = CL * TB
    need = (2 * K * 4 * Wp * 2            # bf16 byte planes
            + 2 * blk * 4                 # lane-dense token block
            + blk * Wp * 4                # reach scratch
            + 2 * 4 * TB * Wp * 4         # state/match carry in + out
            + 2 * TB * 128 * 4            # lengths column
            + 2 * 2 * 8 * Wp * 4          # init/final rows
            + 2 * MR * 4 * Wp * 4         # matmul result + int32 cast
            + K * MR * 6)                 # one-hot, f32 then bf16
    if need > _VMEM_CEILING:
        raise ValueError(
            "scan kernel needs %.1f MiB of VMEM per grid step at K=%d "
            "Wp=%d TB=%d CL=%d MR=%d, over the %d MiB ceiling — shrink "
            "CL or TB"
            % (need / 2**20, K, Wp, TB, CL, MR, _VMEM_CEILING >> 20))
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
            vmem_limit_bytes=_vmem_limit(K, Wp, TB, CL, MR)),
        interpret=interpret,
    )(_pos_major(tokens, TB, CL, MR), lengths, planes, init, final,
      state, match)
    return out_m, out_s


def pallas_scan_bytes(
    tables: ScanTables,
    tokens: jax.Array,
    lengths: jax.Array,
    state: Optional[jax.Array] = None,
    match: Optional[jax.Array] = None,
    TB: int = 64,
    CL: int = 32,
    MR: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """scan_bytes contract: returns (match, state) as (B, W) uint32.
    Traceable: the kernel's operands are padded out of ``tables`` here
    (its ``byte_planes`` are the kernel's planes at width W), so a
    caller's jit takes the tables as an argument like the XLA lowerings
    do.  Compiled by Mosaic on a TPU; any other backend runs the same
    kernel through the Pallas interpreter (the tests)."""
    B, L = tokens.shape
    W = tables.n_words
    Wp = _round_up(max(W, 128), 128)
    MR = min(MR, CL * TB)
    # stage 1 writes reach rows in MR-row blocks and gates each block
    # by position — misaligned tilings would leave scratch rows stale
    # and silently corrupt the NFA state, so reject them loudly
    if TB % 8 or (CL * TB) % MR or MR % TB:
        raise ValueError(
            "invalid tiling: need TB %% 8 == 0, MR %% TB == 0 and "
            "(CL*TB) %% MR == 0; got TB=%d CL=%d MR=%d" % (TB, CL, MR))
    Bp = _round_up(max(B, TB), TB)
    Lp = _round_up(max(L, CL), CL)

    def as_i32(x):
        x = jnp.asarray(x)
        return (jax.lax.bitcast_convert_type(x, jnp.int32)
                if x.dtype == jnp.uint32 else x.astype(jnp.int32))

    def words(x, rows):
        """(rows', W) words → (rows, Wp) int32, zero-padded."""
        return jnp.zeros((rows, Wp), jnp.int32).at[
            :x.shape[0], :W].set(as_i32(x))

    planes = jnp.pad(tables.byte_planes.reshape(256, 4, W),
                     ((0, 0), (0, 0), (0, Wp - W))).reshape(256, 4 * Wp)
    tok_p = jnp.zeros((Bp, Lp), jnp.int32).at[:B, :L].set(
        jnp.asarray(tokens).astype(jnp.int32))
    len_p = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(
        jnp.asarray(lengths).astype(jnp.int32))
    zeros = jnp.zeros((Bp, Wp), jnp.int32)
    out_m, out_s = _pallas_scan(
        tok_p, len_p, planes,
        words(tables.init_mask[None, :], 1),
        words(tables.final_mask[None, :], 1),
        zeros if state is None else words(state, Bp),
        zeros if match is None else words(match, Bp),
        TB=TB, CL=CL, MR=MR, interpret=not on_tpu())

    def to_u32(x):
        return jax.lax.bitcast_convert_type(x[:B, :W], jnp.uint32)

    return to_u32(out_m), to_u32(out_s)
