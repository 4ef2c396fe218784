"""Tensor-parallel (ruleset-sharded) detection over a device mesh.

The bitap scan is *word-local*: no cross-word carries exist (bitap.py), so
sharding the word axis across the ``model`` mesh axis costs zero
communication in the hot loop.  Each shard scans the same bytes against its
slice of the byte table, extracts its own factors' hits, and votes partial
rule hits; one ``psum`` over ICI merges the votes — the verdict OR-reduce
named in SURVEY.md §2.4.  Batch rows ride the ``data`` axis (DP); tenant
(EP) masks apply to the merged votes.

Offline, ``shard_ruleset_tables`` re-packs a CompiledRuleset into
shard-major arrays (padded to uniform per-shard factor counts so shapes are
static under shard_map).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ingress_plus_tpu.compiler.ruleset import CompiledRuleset, N_SV
from ingress_plus_tpu.compiler.seclang import CLASSES
from ingress_plus_tpu.ops.scan import (
    build_class_pair_tables,
    scan_bytes,
    scan_pairs,
)


@dataclass
class ShardedTables:
    """Numpy arrays laid out shard-major for an n_model-way TP split."""

    n_model: int
    w_shard: int              # words per shard (padded)
    byte_table: np.ndarray    # (256, n_model * w_shard) uint32
    init_mask: np.ndarray     # (n_model * w_shard,) uint32
    final_mask: np.ndarray    # (n_model * w_shard,) uint32
    factor_word: np.ndarray   # (n_model, f_max) int32 — shard-relative
    factor_bit: np.ndarray    # (n_model, f_max) uint32
    factor_rule: np.ndarray   # (n_model, f_max, R) float32 (0-padded)
    rule_sv: np.ndarray       # (R, N_SV) float32 (replicated)
    rule_score: np.ndarray    # (R,) float32
    rule_class: np.ndarray    # (R, C) float32
    rule_no_prefilter: np.ndarray  # (R,) bool
    # ---- per-shard class-pair stride (round-4, VERDICT item #7): the
    # lowering the single chip serves (scan_pairs), sharded along words.
    # Byte classes are computed PER SHARD from that shard's byte-table slice —
    # a shard sees fewer distinct reach rows than the full table, so its
    # class count k_s is smaller; all shards pad to k_max with the dead
    # class LAST at index k_max (uniform shapes under shard_map).
    k_max: int = 0
    byte_class: np.ndarray = None    # (n_model, 257) int32; [256]=k_max
    class_table: np.ndarray = None   # (n_model, k_max+1, w_shard) uint32
    pair_reach: np.ndarray = None    # (n_model, (k_max+1)^2, w_shard)
    pair_final: np.ndarray = None    # (n_model, k_max+1, w_shard)


def shard_ruleset_tables(cr: CompiledRuleset, n_model: int,
                         lane_multiple: int = 8) -> ShardedTables:
    t = cr.tables
    W, F, R = t.n_words, t.n_factors, cr.n_rules
    w_shard = -(-W // n_model)
    w_shard = -(-w_shard // lane_multiple) * lane_multiple
    W_pad = w_shard * n_model

    bt = np.zeros((256, W_pad), np.uint32)
    bt[:, :W] = t.byte_table
    init = np.zeros((W_pad,), np.uint32)
    init[:W] = t.init_mask
    final = np.zeros((W_pad,), np.uint32)
    final[:W] = t.final_mask

    # factor → owning shard
    shard_of = t.factor_word // w_shard
    f_max = max(1, int(np.bincount(shard_of, minlength=n_model).max()))
    factor_word = np.zeros((n_model, f_max), np.int32)
    factor_bit = np.zeros((n_model, f_max), np.uint32)
    factor_rule = np.zeros((n_model, f_max, max(R, 1)), np.float32)
    fill = np.zeros((n_model,), np.int64)
    for f in range(F):
        s = int(shard_of[f])
        j = int(fill[s])
        factor_word[s, j] = t.factor_word[f] - s * w_shard
        factor_bit[s, j] = t.factor_bit[f]
        lo, hi = t.factor_rule_indptr[f], t.factor_rule_indptr[f + 1]
        factor_rule[s, j, t.factor_rule_ids[lo:hi]] = 1.0
        fill[s] += 1
    # padded factor slots keep word 0 / bit 0 but an all-zero rule map, so
    # whatever bit they read contributes nothing to the vote.

    onehot = np.zeros((max(R, 1), len(CLASSES)), np.float32)
    if R:
        onehot[np.arange(R), cr.rule_class] = 1.0

    # per-shard pair-stride tables via the SHARED construction
    # (ops/scan.py build_class_pair_tables — one recurrence, two paths),
    # padded to a uniform k_max so shapes are static under shard_map
    shard_uniq = []
    k_max = 1
    for s in range(n_model):
        bt_s = bt[:, s * w_shard:(s + 1) * w_shard]
        uniq, inv = np.unique(bt_s.astype(np.uint32), axis=0,
                              return_inverse=True)
        shard_uniq.append((uniq, inv))
        k_max = max(k_max, int(uniq.shape[0]))
    byte_class = np.zeros((n_model, 257), np.int32)
    class_table = np.zeros((n_model, k_max + 1, w_shard), np.uint32)
    pair_reach = np.zeros((n_model, (k_max + 1) ** 2, w_shard), np.uint32)
    pair_final = np.zeros((n_model, k_max + 1, w_shard), np.uint32)
    for s in range(n_model):
        sl = slice(s * w_shard, (s + 1) * w_shard)
        bc, T, pr, pf, _k = build_class_pair_tables(
            bt[:, sl], init[sl], final[sl], k_pad=k_max,
            uniq_inv=shard_uniq[s])
        byte_class[s] = bc
        class_table[s] = T
        pair_reach[s] = pr
        pair_final[s] = pf

    return ShardedTables(
        n_model=n_model, w_shard=w_shard, byte_table=bt, init_mask=init,
        final_mask=final, factor_word=factor_word, factor_bit=factor_bit,
        factor_rule=factor_rule,
        rule_sv=cr.rule_sv_mask.astype(np.float32),
        rule_score=cr.rule_score.astype(np.float32),
        rule_class=onehot,
        rule_no_prefilter=(t.rule_nfactors == 0),
        k_max=k_max, byte_class=byte_class, class_table=class_table,
        pair_reach=pair_reach, pair_final=pair_final,
    )


class ShardedEngine:
    """DP×TP detection step over a Mesh (the multi-chip flagship program).

    EP: ``tenant_rule_mask`` (T, R) bool — per-tenant rule subsets over the
    shared superset NFA (benchmark config #4: 256 Ingress tenants).
    """

    #: "pair" = class-pair stride, "take" = one gather per byte
    SCAN_IMPLS = ("pair", "take")

    def __init__(self, cr: CompiledRuleset, mesh: Mesh,
                 tenant_rule_mask: np.ndarray | None = None,
                 scan_impl: str = "pair"):
        self.mesh = mesh
        n_model = mesh.shape["model"]
        st = shard_ruleset_tables(cr, n_model)
        self.st = st
        if tenant_rule_mask is None:
            tenant_rule_mask = np.ones((1, max(cr.n_rules, 1)), bool)
        self.tenant_mask = tenant_rule_mask.astype(np.float32)
        if scan_impl not in self.SCAN_IMPLS:
            raise ValueError("sharded scan_impl must be one of %s"
                             % (self.SCAN_IMPLS,))
        self.scan_impl = scan_impl

        def put(arr, spec):
            return jax.device_put(arr, NamedSharding(mesh, spec))

        W_pad = st.w_shard * n_model
        self.d_byte = put(st.byte_table, P(None, "model"))
        self.d_init = put(st.init_mask, P("model"))
        self.d_final = put(st.final_mask, P("model"))
        self.d_fw = put(st.factor_word, P("model", None))
        self.d_fb = put(st.factor_bit, P("model", None))
        self.d_fr = put(st.factor_rule, P("model", None, None))
        self.d_rule_sv = put(st.rule_sv, P(None, None))
        self.d_score = put(st.rule_score, P(None))
        self.d_class = put(st.rule_class, P(None, None))
        self.d_nopf = put(st.rule_no_prefilter, P(None))
        self.d_tenant = put(self.tenant_mask, P(None, None))
        # pair-stride tables, one slice per model shard
        self.d_bcls = put(st.byte_class, P("model", None))
        self.d_ctab = put(st.class_table, P("model", None, None))
        self.d_preach = put(st.pair_reach, P("model", None, None))
        self.d_pfinal = put(st.pair_final, P("model", None, None))
        self._steps = {}
        self._step = self._build_step(self.scan_impl)

    def set_scan_impl(self, scan_impl: str) -> None:
        """Switch the sharded scan implementation (compiled steps are
        cached per impl)."""
        if scan_impl not in self.SCAN_IMPLS:
            raise ValueError("sharded scan_impl must be one of %s"
                             % (self.SCAN_IMPLS,))
        self.scan_impl = scan_impl
        self._step = self._build_step(scan_impl)

    def _build_step(self, scan_impl: str, global_rows: bool = False):
        """``global_rows=False`` (the detect() contract): row_req holds
        SHARD-LOCAL request ids, each data shard reduces its own rows,
        and the (Q, R) output is the concatenation of per-shard
        verdicts.  ``global_rows=True`` (the serving adapter,
        parallel/serve_mesh): row_req holds GLOBAL request ids, rows may
        sit on ANY data shard, and per-request verdicts are merged with
        one extra psum over the data axis — placement-free, so batch
        shapes depend only on (B, L, Q) and the batcher's warm_shape
        replay compiles exactly the executables live traffic hits."""
        key = (scan_impl, global_rows)
        if key in self._steps:
            return self._steps[key]
        mesh = self.mesh

        def block(byte_table, init, final, bcls, ctab, preach, pfinal,
                  fw, fb, fr, rule_sv, score,
                  cls_map, nopf, tenant_mask, tokens, lengths, row_req,
                  row_sv, tenants, num_requests):
            # shapes inside the block are per-device slices:
            # byte_table (256, w_shard); fw/fb (1, f_max); fr (1, f_max, R)
            fw, fb, fr = fw[0], fb[0], fr[0]
            w_shard = byte_table.shape[1]

            # word-local scan — ZERO communication.  "pair" runs the
            # class-pair stride (one reach gather per TWO bytes) on this
            # shard's own class tables; "take" is one gather per byte.
            class _T:  # minimal ScanTables duck-type for the scan kernels
                n_words = byte_table.shape[1]
            t = _T()
            t.byte_table, t.init_mask, t.final_mask = byte_table, init, final
            t.byte_planes = None
            if scan_impl == "pair":
                t.byte_class = bcls[0]
                t.class_table = ctab[0]
                t.pair_reach = preach[0]
                t.pair_final = pfinal[0]
                match, _ = scan_pairs(t, tokens, lengths)
            else:
                match, _ = scan_bytes(t, tokens, lengths, gather="take")

            # local factor hits → partial rule votes
            mw = jnp.take(match, fw, axis=1)
            fh = ((mw >> fb) & jnp.uint32(1)).astype(jnp.float32)
            vote = jnp.dot(fh, fr, preferred_element_type=jnp.float32)

            # ICI: merge votes across ruleset shards (the one collective)
            vote = jax.lax.psum(vote, axis_name="model")
            row_rule = vote > 0

            applies = jnp.dot(row_sv.astype(jnp.float32), rule_sv.T,
                              preferred_element_type=jnp.float32) > 0
            row_rule = jnp.logical_and(row_rule, applies)

            rh_i = jax.ops.segment_max(
                row_rule.astype(jnp.int32), row_req,
                num_segments=num_requests)
            ap_i = jax.ops.segment_max(
                applies.astype(jnp.int32), row_req,
                num_segments=num_requests)
            if global_rows:
                # rows for one request may live on several data shards:
                # OR the per-shard partials via psum.  segment_max fills
                # segments with NO rows on a shard with INT32_MIN, which
                # would poison the sum (INT_MIN + 1 stays negative and
                # erases a real hit) — clamp the partials to 0/1 first
                rh_i = jax.lax.psum(jnp.maximum(rh_i, 0),
                                    axis_name="data")
                ap_i = jax.lax.psum(jnp.maximum(ap_i, 0),
                                    axis_name="data")
            rule_hits = rh_i > 0
            req_has_rows = ap_i > 0
            rule_hits = jnp.logical_or(
                rule_hits, jnp.logical_and(req_has_rows, nopf[None, :]))

            # EP: tenant rule-subset masking
            tmask = jnp.take(tenant_mask, tenants % tenant_mask.shape[0],
                             axis=0) > 0
            rule_hits = jnp.logical_and(rule_hits, tmask)

            hits_f = rule_hits.astype(jnp.float32)
            class_hits = jnp.dot(hits_f, cls_map,
                                 preferred_element_type=jnp.float32) > 0
            scores = jnp.dot(hits_f, score,
                             preferred_element_type=jnp.float32)
            return rule_hits, class_hits, scores.astype(jnp.int32)

        @functools.partial(jax.jit, static_argnames=("num_requests",))
        def step(tokens, lengths, row_req, row_sv, tenants, num_requests):
            seg = (num_requests if global_rows
                   else num_requests // mesh.shape["data"])
            # global mode: tenants are per-request and replicated (the
            # verdict tensors are too, post-psum); local mode splits
            # both along the data axis
            out_axis = None if global_rows else "data"
            ten_spec = P(out_axis)
            fn = shard_map(
                functools.partial(block, num_requests=seg),
                mesh=mesh,
                in_specs=(
                    P(None, "model"), P("model"), P("model"),      # tables
                    P("model", None), P("model", None, None),      # pair
                    P("model", None, None), P("model", None, None),
                    P("model", None), P("model", None),
                    P("model", None, None),
                    P(None, None), P(None), P(None, None), P(None),
                    P(None, None),                                  # tenant
                    P("data", None), P("data"), P("data"),
                    P("data", None), ten_spec,
                ),
                out_specs=(P(out_axis, None), P(out_axis, None),
                           P(out_axis)),
                check_vma=False,
            )
            return fn(self.d_byte, self.d_init, self.d_final,
                      self.d_bcls, self.d_ctab, self.d_preach,
                      self.d_pfinal, self.d_fw,
                      self.d_fb, self.d_fr, self.d_rule_sv, self.d_score,
                      self.d_class, self.d_nopf, self.d_tenant,
                      tokens, lengths, row_req, row_sv, tenants)

        self._steps[key] = step
        return step

    def autoselect_scan_impl(self, B: int = 256, L: int = 256,
                             iters: int = 17,
                             global_rows: bool = False) -> str:
        """Measure the two sharded lowerings on THIS mesh and keep the
        winner: per impl, run the jitted step iters times back-to-back,
        so dispatch overhead mostly cancels."""
        import time as _time

        if jax.process_count() > 1:
            # multi-process meshes need make_global-built inputs (see
            # detect()); a measurement pass is not worth coordinating
            # across hosts — keep the configured impl
            return self.scan_impl
        n_data = self.mesh.shape["data"]
        B = -(-B // n_data) * n_data
        rng = np.random.default_rng(7)
        tokens = rng.integers(0, 256, (B, L), dtype=np.int32)
        lengths = np.full((B,), L, np.int32)
        # one request per row; local mode wants SHARD-LOCAL ids, global
        # mode GLOBAL ids (matching each step variant's contract)
        row_req = (np.arange(B, dtype=np.int32) if global_rows
                   else np.tile(np.arange(B // n_data, dtype=np.int32),
                                n_data))
        row_sv = np.ones((B, self.st.rule_sv.shape[1]), np.int8)
        tenants = np.zeros((B,), np.int32)

        timings = {}
        for impl in ("take", "pair"):
            # measure the step VARIANT the caller serves with (the mesh
            # adapter runs global_rows=True; timing the local-rows
            # program would rank a program live traffic never executes
            # and pay its compiles for nothing)
            step = self._build_step(impl, global_rows=global_rows)
            args = (jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(row_req), jnp.asarray(row_sv),
                    jnp.asarray(tenants))
            out = step(*args, num_requests=B)   # compile + warm
            jax.block_until_ready(out)
            t0 = _time.perf_counter()
            for _ in range(iters):
                out = step(*args, num_requests=B)
            jax.block_until_ready(out)
            timings[impl] = _time.perf_counter() - t0
        best = min(timings, key=timings.get)
        self.last_timings = timings   # consumed by MeshEngine/diagnostics
        self.set_scan_impl(best)
        return best

    def detect(self, tokens, lengths, row_req, row_sv, tenants,
               num_requests: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """row_req must hold SHARD-LOCAL request indices (each data shard
        owns Q/n_data consecutive requests; the serve batcher lays batches
        out this way).  num_requests is the global request count.

        Multi-process (DCN) meshes: pass GLOBAL arrays built with
        ``parallel.dcn.make_global`` (each host contributes its
        local_batch_bounds slice); outputs come back as full numpy on
        every process via ``gather_global`` — tests/test_dcn.py drives
        this with two real jax.distributed processes."""
        n_data = self.mesh.shape["data"]
        if num_requests % n_data != 0:
            raise ValueError(
                "num_requests=%d not divisible by data-axis size %d — pad "
                "the batch with empty requests" % (num_requests, n_data))
        if self.scan_impl == "pair" and tokens.shape[1] % 2:
            # scan_pairs needs even L; one padding column costs nothing
            # (padding maps to the dead class) and keeps detect()'s
            # any-length contract from before the pair default.  Host
            # arrays only — a multi-process global array (make_global)
            # cannot be re-padded here, and its producer pads to 64 (the
            # pad_rows contract) anyway.
            if isinstance(tokens, jax.Array) and len(tokens.devices()) > 1:
                raise ValueError(
                    "pair scan needs even L for device-global inputs")
            tokens = np.pad(np.asarray(tokens), ((0, 0), (0, 1)))
        rh, ch, sc = self._step(
            jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(row_req), jnp.asarray(row_sv), jnp.asarray(tenants),
            num_requests)
        from ingress_plus_tpu.parallel.dcn import gather_global

        return gather_global(rh), gather_global(ch), gather_global(sc)
