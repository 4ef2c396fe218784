"""DetectionEngine — the flagship model: batched scan + verdict heads.

One jit-compiled program takes a padded batch of normalized scan rows and
produces per-request rule prefilter hits, per-class verdicts and anomaly
scores.  This is the TPU re-design of the reference's per-request hot loop
(libproton signature match, SURVEY.md §3.3 hot loop #2): the per-byte
automaton runs as the bitap recurrence on the VPU, and the factor→rule→class
mapping runs as small MXU matmuls.

Shapes (per length-bucket, all static under jit):
    tokens   (B, L)     uint8/int32  — normalized row bytes
    lengths  (B,)       int32
    row_req  (B,)       int32        — owning request index in [0, Q)
    row_sv   (B, N_SV)  int8         — multi-hot stream-variant ids of row
    tenants  (Q,)       int32        — per-request tenant (EP routing)
Returns:
    rule_hits  (Q, R) bool — prefilter hits per request (pre-confirm)
    class_hits (Q, C) bool — any hit rule of that attack class
    scores     (Q,)  int32 — anomaly score (sum of hit rules' severities)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ingress_plus_tpu.compiler.ruleset import CompiledRuleset, N_SV
from ingress_plus_tpu.compiler.seclang import CLASSES
from ingress_plus_tpu.ops.scan import (
    ScanTables,
    scan_bytes,
    scan_bytes_jit,
    scan_pairs,
    scan_pairs_jit,
)
from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.trace import EV_SCAN_LAUNCH, flight, named_lock


@jax.tree_util.register_pytree_node_class
@dataclass
class EngineTables:
    """All device arrays (a pytree → hot-swappable without recompilation)."""

    scan: ScanTables
    factor_word: jax.Array     # (F,) int32
    factor_bit: jax.Array      # (F,) uint32
    #: PREFILTER GROUP axis (docs/SCAN_KERNEL.md "rule grouping"): rules
    #: with identical (factor set, stream-variant mask, no-prefilter
    #: flag) produce identical candidate columns, so the rule-count-
    #: scaling mapping matmul runs over G ≤ R equivalence classes and a
    #: cheap gather expands groups back to rules.  Clone-heavy pack
    #: growth (the dominant real-world growth mode) then costs the
    #: mapping nothing at all.
    factor_rule: jax.Array     # (F, G) float32 dense factor→group map
    rule_sv: jax.Array         # (G, N_SV) float32
    rule_score: jax.Array      # (R,) int32
    rule_class: jax.Array      # (R, C) float32 one-hot
    rule_no_prefilter: jax.Array  # (G,) bool — groups that always confirm
    rule_group: jax.Array      # (R,) int32 rule → prefilter group id

    def tree_flatten(self):
        return (
            (self.scan, self.factor_word, self.factor_bit, self.factor_rule,
             self.rule_sv, self.rule_score, self.rule_class,
             self.rule_no_prefilter, self.rule_group),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def from_ruleset(cls, cr: CompiledRuleset,
                     head_only: bool = False) -> "EngineTables":
        """Build device tables; ``head_only=True`` slices the word axis
        to ``BitapTables.n_head_words`` and keeps only the factors
        living there (docs/SCAN_KERNEL.md "per-bucket slicing").  Sound
        for dispatches whose rows all carry uri/args/headers
        stream-variants: every factor beyond the boundary is owned
        exclusively by body/response-only rules, which never apply to
        such rows — the sliced scan computes exactly the candidates the
        full scan would for them, at the head words' width."""
        t = cr.tables
        Wh = t.n_head_words
        if head_only and Wh < t.n_words:
            keep = np.nonzero(t.factor_word < Wh)[0]
            bt = type(t)(
                byte_table=t.byte_table[:, :Wh],
                init_mask=t.init_mask[:Wh],
                final_mask=t.final_mask[:Wh],
                factor_word=t.factor_word[keep],
                factor_bit=t.factor_bit[keep],
                factor_rule_indptr=t.factor_rule_indptr,  # re-derived below
                factor_rule_ids=t.factor_rule_ids,
                rule_nfactors=t.rule_nfactors,  # FULL-pack counts: a
                # body-only rule with factors is not "no prefilter"
                factor_len=t.factor_len[keep],
                n_head_words=Wh,
            )
            factor_sel = keep
        else:
            bt = t
            factor_sel = None
        F, R = bt.factor_word.shape[0], cr.n_rules
        # per-rule factor memberships (within THIS table's factor
        # subset), for the prefilter-group dedup
        rule_factors: list = [[] for _ in range(R)]
        for fi in range(F):
            f = int(factor_sel[fi]) if factor_sel is not None else fi
            lo, hi = t.factor_rule_indptr[f], t.factor_rule_indptr[f + 1]
            for r in t.factor_rule_ids[lo:hi]:
                rule_factors[int(r)].append(fi)
        nopf_rule = t.rule_nfactors == 0
        groups: dict = {}
        rule_group = np.zeros((max(R, 1),), np.int32)
        for r in range(R):
            key = (tuple(rule_factors[r]),
                   cr.rule_sv_mask[r].tobytes(), bool(nopf_rule[r]))
            g = groups.setdefault(key, len(groups))
            rule_group[r] = g
        G = max(len(groups), 1)
        fr = np.zeros((max(F, 1), G), dtype=np.float32)
        rule_sv_g = np.zeros((G, cr.rule_sv_mask.shape[1]), np.float32)
        nopf_g = np.zeros((G,), bool)
        for (fids, sv_bytes, nopf), g in groups.items():
            fr[list(fids), g] = 1.0
            rule_sv_g[g] = np.frombuffer(
                sv_bytes, dtype=bool).astype(np.float32)
            nopf_g[g] = nopf
        onehot = np.zeros((max(R, 1), len(CLASSES)), dtype=np.float32)
        if R:
            onehot[np.arange(R), cr.rule_class] = 1.0
        # F == 0 (every rule confirm-only, e.g. a pure 920-protocol pack):
        # factor_word/bit must pad like factor_rule's dummy row — the
        # dummy maps to no group (all-zero fr row), so it can never fire
        factor_word = bt.factor_word if F else np.zeros((1,), np.int32)
        factor_bit = (bt.factor_bit if F else np.zeros((1,), np.int32))
        return cls(
            scan=ScanTables.from_bitap(bt),
            factor_word=jnp.asarray(factor_word, jnp.int32),
            factor_bit=jnp.asarray(factor_bit.astype(np.uint32)),
            factor_rule=jnp.asarray(fr),
            rule_sv=jnp.asarray(rule_sv_g),
            rule_score=jnp.asarray(cr.rule_score, jnp.int32),
            rule_class=jnp.asarray(onehot),
            rule_no_prefilter=jnp.asarray(nopf_g),
            rule_group=jnp.asarray(rule_group),
        )


def fold_rows(
    tables: EngineTables,
    match_words: jax.Array,   # (B, W) uint32 — sticky match mask per row
    row_req: jax.Array,
    row_sv: jax.Array,
    req_fh: jax.Array,        # (Q, F) float32 accumulator
    req_sv: jax.Array,        # (Q, N_SV) float32 accumulator
) -> Tuple[jax.Array, jax.Array]:
    """Fold one bucket's rows into the per-REQUEST accumulators: factor
    hits (Q, F) and stream-variant coverage (Q, N_SV), each a running
    max.  The first half of :func:`map_match_words`, split out so a
    multi-bucket dispatch folds bucket by bucket
    (:func:`scan_fold_bucket`), never over the combination of buckets a
    cycle happens to contain (an eager concatenate of the buckets'
    match words compiled a fresh program per combination, in front of
    traffic)."""
    Q = req_fh.shape[0]
    # factor hits: gather each factor's word, test its bit     (B, F)
    mw = jnp.take(match_words, tables.factor_word, axis=1)
    fh = ((mw >> tables.factor_bit) & jnp.uint32(1)).astype(jnp.float32)
    return (
        jnp.maximum(req_fh, jax.ops.segment_max(
            fh, row_req, num_segments=Q)),
        jnp.maximum(req_sv, jax.ops.segment_max(
            row_sv.astype(jnp.float32), row_req, num_segments=Q)))


def expand_requests(
    tables: EngineTables,
    req_fh: jax.Array,        # (Q, F) float32 factor hits per request
    req_sv: jax.Array,        # (Q, N_SV) float32 stream-variant coverage
    num_requests: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-request factor hits → (rule_hits, class_hits, scores): the
    second half of :func:`map_match_words`, and the one stage whose
    cost scales with rule count — it runs once per dispatch.
    ``num_requests`` (static) takes the accumulators' first rows before
    anything else: a dispatch folds into accumulators of the engine's
    fixed request capacity and expands its own Q tier of them, so this
    program — and the (Q, R) result the host fetches — keys on Q."""
    if num_requests is not None:
        req_fh, req_sv = req_fh[:num_requests], req_sv[:num_requests]
    # factor → prefilter-GROUP hits (G ≤ R equivalence classes of rules
    # with identical candidate behavior — clone rules cost nothing here)
    req_group = jnp.dot(req_fh, tables.factor_rule,
                        preferred_element_type=jnp.float32) > 0  # (Q, G)

    # a group counts only for requests carrying one of its
    # stream-variant ids                                       (Q, G)
    applies = jnp.dot(req_sv, tables.rule_sv.T,
                      preferred_element_type=jnp.float32) > 0
    # groups with no prefilter always reach the confirm stage for any
    # request that has at least one applicable row
    group_hits = jnp.logical_and(
        jnp.logical_or(req_group, tables.rule_no_prefilter[None, :]),
        applies)

    # groups → rules (gather)                                  (Q, R)
    rule_hits = jnp.take(group_hits, tables.rule_group, axis=1)

    hits_f = rule_hits.astype(jnp.float32)
    class_hits = jnp.dot(hits_f, tables.rule_class,
                         preferred_element_type=jnp.float32) > 0
    scores = jnp.dot(hits_f, tables.rule_score.astype(jnp.float32),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    return rule_hits, class_hits, scores


def map_match_words(
    tables: EngineTables,
    match_words: jax.Array,   # (B, W) uint32 — sticky match mask per row
    row_req: jax.Array,
    row_sv: jax.Array,
    num_requests: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Match words → (rule_hits, class_hits, scores): the rule-mapping
    math of detect_rows on its own, for match words scanned elsewhere
    (the plain reference the tests compare against).

    Rows fold to REQUESTS before the factor→rule expansion
    (:func:`fold_rows` then :func:`expand_requests`): the (·, F) ×
    (F, R) dot — the one term here that scales with rule count — runs
    on Q request rows, not B scan rows (B ≈ 5x Q on the bench corpus;
    this was the dominant detect cost at the 2k-rule scale, BENCH_r05).
    The stream-variant gate is therefore applied per REQUEST, not per
    row: a factor firing on any of a request's rows counts for every
    rule of that request with a matching stream-variant.  That is a
    strict over-approximation of the old row-level gate (candidates
    only ever added — the exact confirm lane decides verdicts), the
    same trade the budgeted reduction makes in compiler/reduce.py, and
    in practice a factor that fires on one normalization variant of a
    text fires on its siblings too."""
    req_fh, req_sv = fold_rows(
        tables, match_words, row_req, row_sv,
        jnp.zeros((num_requests, tables.factor_word.shape[0]),
                  jnp.float32),
        jnp.zeros((num_requests, row_sv.shape[1]), jnp.float32))
    return expand_requests(tables, req_fh, req_sv)


map_match_words_jit = jax.jit(
    map_match_words, static_argnames=("num_requests",))
expand_requests_jit = jax.jit(
    expand_requests, static_argnames=("num_requests",))


#: bytes after the L token bytes of a packed bucket row: the row's
#: length and its owning request (little-endian int32 each), then its
#: N_SV stream-variant flags, padded to whole words
ROW_TAIL = -(-(8 + N_SV) // 4) * 4


def empty_bucket(B: int, L: int) -> np.ndarray:
    """One bucket as the host ships it: ``(B, L + ROW_TAIL)`` uint8, a
    shape that depends on (B, L) alone, so a bucket is ONE
    host-to-device transfer.  Fill it through :func:`bucket_views`."""
    return np.zeros((B, L + ROW_TAIL), np.uint8)


def bucket_views(packed: np.ndarray):
    """``(tokens (B, L) uint8, lengths (B,) int32, row_req (B,) int32,
    row_sv (B, N_SV) uint8)`` as writable views of a packed bucket."""
    L = packed.shape[1] - ROW_TAIL
    ints = packed[:, L:L + 8].view("<i4")
    return (packed[:, :L], ints[:, 0], ints[:, 1],
            packed[:, L + 8:L + 8 + N_SV])


def _split_bucket(packed: jax.Array):
    """:func:`bucket_views` on the device, inside the bucket's program."""
    L = packed.shape[1] - ROW_TAIL

    def int32_at(col):
        b = packed[:, col:col + 4].astype(jnp.int32)
        return b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)

    return (packed[:, :L], int32_at(L), int32_at(L + 4),
            packed[:, L + 8:L + 8 + N_SV])


#: the two XLA lowerings of ops/scan.py: "pair" = class-pair stride
#: (one reach gather per two bytes), "take" = one gather per byte — the
#: plain reference, what carries state across streamed chunks, and what
#: serves a pack whose tables hold no pair tables.  "pallas" = the byte
#: kernel of ops/pallas_scan.py, by name only: nothing selects it
#: (PERF.md §6 PR 31: fastest at the 2,048 tier, behind "pair" at the
#: others)
SCAN_IMPLS = ("pair", "take", "pallas")


def resolve_scan_impl(scan_impl: str, scan: ScanTables) -> str:
    """``"auto"`` → ``"pair"`` where the pack's tables carry
    ``pair_reach``, else ``"take"``: the one place that states the
    rule.  A named lowering is returned as it is."""
    if scan_impl == "auto":
        return "pair" if scan.pair_reach is not None else "take"
    if scan_impl not in SCAN_IMPLS:
        raise ValueError("scan_impl must be \"auto\" or one of %s, not %r"
                         % (SCAN_IMPLS, scan_impl))
    return scan_impl


def _byte_scan(impl: str):
    """The scan with ``scan_bytes``' whole contract (state carried)
    behind ``impl``: the kernel for "pallas", else ``scan_bytes``."""
    if impl == "pallas":
        from ingress_plus_tpu.ops.pallas_scan import pallas_scan_bytes

        return pallas_scan_bytes
    return scan_bytes


def _match_words(impl: str, tables: EngineTables, tokens,
                 lengths) -> jax.Array:
    """One bucket's sticky match words (B, W) uint32 under ``impl``."""
    if impl == "pair":
        scan = scan_pairs_jit
    elif impl == "take":
        scan = scan_bytes_jit
    else:
        scan = _byte_scan(impl)
    return scan(tables.scan, tokens, lengths)[0]


@functools.partial(jax.jit, static_argnames=("impl",))
def scan_fold_bucket(tables: EngineTables, packed: jax.Array,
                     req_fh: jax.Array, req_sv: jax.Array, impl: str):
    """One bucket's whole device work as ONE program: unpack the
    shipped buffer, scan its rows, fold them into the per-request
    accumulators (:func:`fold_rows`).  The accumulators have the
    engine's fixed request capacity, so the executable keys on the
    bucket's (B, L) alone — not on the dispatch's Q tier, and never on
    the set of buckets a cycle contains.  (``scan`` stays in the name:
    the profiler prints ``jit_<name>`` and the benchmark's roofline
    finds the scan programs by that word.)"""
    tokens, lengths, row_req, row_sv = _split_bucket(packed)
    m = _match_words(impl, tables, tokens, lengths)
    return fold_rows(tables, m, row_req, row_sv, req_fh, req_sv)


def detect_rows(
    tables: EngineTables,
    tokens: jax.Array,
    lengths: jax.Array,
    row_req: jax.Array,
    row_sv: jax.Array,
    num_requests: int,
    state: Optional[jax.Array] = None,
    match: Optional[jax.Array] = None,
    scan_impl: str = "auto",
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The full detection step (jit this with static num_requests and
    scan_impl; ``scan_impl`` as :func:`resolve_scan_impl` takes it)."""
    impl = resolve_scan_impl(scan_impl, tables.scan)
    if impl == "pair" and state is None:
        # class-pair stride: half the steps, one reach gather per two
        # bytes (ops/scan.py scan_pairs) — the request path only consumes
        # the match mask, so the pair path's zero-state-after-padding
        # contract is fine here; explicit carries use the byte path
        match_words, state = scan_pairs(
            tables.scan, tokens, lengths, None, match)
    else:
        match_words, state = _byte_scan(impl)(
            tables.scan, tokens, lengths, state, match)
    rule_hits, class_hits, scores = map_match_words(
        tables, match_words, row_req, row_sv, num_requests)
    return rule_hits, class_hits, scores, match_words, state


detect_rows_jit = jax.jit(
    detect_rows, static_argnames=("num_requests", "scan_impl"))


class DetectionEngine:
    """Host-facing wrapper: upload tables once, detect per batch.

    Hot-swap (the proton.db sync-node analog, SURVEY.md §3.4): call
    ``swap_ruleset`` with a new CompiledRuleset — same pytree structure, so
    the jit cache is reused; the old tables are dropped after the next
    dispatch completes (double-buffered by XLA's async dispatch)."""

    SCAN_IMPLS = SCAN_IMPLS

    def __init__(self, cr: CompiledRuleset, scan_impl: str = "auto"):
        self.ruleset = cr
        self.tables = EngineTables.from_ruleset(cr)
        # head-sliced twin (docs/SCAN_KERNEL.md): word prefix + the
        # factors living there, for dispatches with no body/response
        # rows; None when the pack has no word tiering — or when EVERY
        # factor is tail-tier (n_head_words == 0: a zero-word slice is
        # degenerate and its mapping gather would crash)
        self.head_tables = (
            EngineTables.from_ruleset(cr, head_only=True)
            if 0 < cr.tables.n_head_words < cr.tables.n_words else None)
        #: the lowering every launch runs, resolved here once
        self.scan_impl = resolve_scan_impl(scan_impl, self.tables.scan)
        # per-device replicated tables (docs/MESH_SERVING.md): the
        # sigpack rides to each serve lane's chip ONCE, at first use —
        # {device: (tables, head_tables|None)}
        self._device_tables: dict = {}
        #: device programs enqueued by detect_device_multi
        #: (ipt_device_launches_total); carried across hot swaps
        self.device_launches = 0
        self._launch_lock = named_lock("DetectionEngine._launch_lock")
        #: rows of the per-request accumulators every bucket program
        #: folds into.  The pipeline's warm-up sets it to its largest Q
        #: tier; a larger dispatch grows it, and its buckets then
        #: compile anew.  Carried across hot swaps.
        self.request_capacity = 0
        # the zero accumulators a dispatch starts from, made once:
        # {(capacity, F, device): (req_fh, req_sv)}; never donated
        self._zero_acc: dict = {}

    def rebuilt(self, cr: CompiledRuleset) -> "DetectionEngine":
        """Fresh engine of the SAME kind on a new ruleset — the batcher
        hot-swap uses this so a mesh-backed engine (parallel/serve_mesh
        MeshEngine) survives the swap instead of silently reverting to
        the single-chip engine."""
        eng = type(self)(cr, scan_impl=self.scan_impl)
        eng.device_launches = self.device_launches
        eng.request_capacity = self.request_capacity
        return eng

    def device_info(self) -> dict:
        """Geometry + impl of the live device tables (served by
        /rules/stats so an operator can see what the scan plane is
        actually running without opening the checkpoint artifact)."""
        t = self.ruleset.tables
        return {
            "scan_impl": self.scan_impl,
            "n_rules": int(self.ruleset.n_rules),
            "n_factors": int(t.n_factors),
            "n_words": int(t.n_words),
            "n_head_words": int(t.n_head_words),
            "n_prefix_shared": int(t.n_prefix_shared),
            "max_factor_len": int(t.max_factor_len),
            "reduction": getattr(self.ruleset, "reduction", None),
        }

    def head_slicing_active(self) -> bool:
        """True iff a head-only dispatch scans the sliced tables: the
        pack is word-tiered."""
        return self.head_tables is not None

    def swap_ruleset(self, cr: CompiledRuleset) -> None:
        # tables are a jit *argument* (pytree), so a geometry change just
        # keys a fresh executable on next call — never clear the cache
        # (that would dump pre-warmed shapes for the new tables too)
        self.ruleset = cr
        self.tables = EngineTables.from_ruleset(cr)
        self.head_tables = (
            EngineTables.from_ruleset(cr, head_only=True)
            if 0 < cr.tables.n_head_words < cr.tables.n_words else None)
        self._device_tables = {}
        self._zero_acc = {}

    def tables_for(self, device):
        """The (tables, head_tables) pair replicated to ``device`` —
        device_put once per chip per generation (docs/MESH_SERVING.md
        "sigpack replication"); ``device=None`` is the default-device
        pair.  The replica is a pytree copy, so the jit cache keys one
        executable set per device (XLA executables are device-bound;
        the lane warmup compiles them all in one overlapped pass)."""
        if device is None:
            return self.tables, self.head_tables
        key = device
        pair = self._device_tables.get(key)
        if pair is None:
            pair = (jax.device_put(self.tables, device),
                    (jax.device_put(self.head_tables, device)
                     if self.head_tables is not None else None))
            self._device_tables[key] = pair
        return pair

    def drop_compiled(self) -> None:
        """Forget every compiled executable (the recompile_storm fault
        site's hammer; also useful to measure cold-dispatch cost) —
        subsequent dispatches pay fresh XLA compiles."""
        jax.clear_caches()
        self._device_tables = {}
        self._zero_acc = {}

    def scan_words(self, tabs: EngineTables, tokens, lengths):
        """One bucket's sticky match words (B, W) uint32 under
        ``scan_impl``, as a program of its own, for the parity checks
        (ops/parity.py).  ``tabs`` is what is scanned (full or
        head-sliced, default or per-device)."""
        return _match_words(self.scan_impl, tabs, tokens, lengths)

    def _rule_hits_device(self, tokens, lengths, row_req, row_sv,
                          num_requests: int):
        # fault-injection sites (utils/faults.py): a wedged device is a
        # sleep here (the batcher's dispatch watchdog must catch it), a
        # crashed dispatch is a raise (the breaker must count it)
        faults.sleep_if("dispatch_hang")
        faults.raise_if("dispatch_raise")
        tokens = jnp.asarray(tokens)
        lengths = jnp.asarray(lengths)
        row_req = jnp.asarray(row_req)
        row_sv = jnp.asarray(row_sv)
        out = detect_rows_jit(self.tables, tokens, lengths, row_req,
                              row_sv, num_requests,
                              scan_impl=self.scan_impl)
        return out[:3]

    def detect(self, tokens, lengths, row_req, row_sv, num_requests: int):
        rule_hits, class_hits, scores = self._rule_hits_device(
            tokens, lengths, row_req, row_sv, num_requests)
        return (np.asarray(rule_hits), np.asarray(class_hits),
                np.asarray(scores))

    def detect_device(self, tokens, lengths, row_req, row_sv,
                      num_requests: int):
        """Async variant: returns the (Q, R) rule-hit device array without
        blocking, so callers can dispatch several buckets back-to-back and
        materialize afterwards (one sync per batch, not per bucket)."""
        rule_hits, _, _ = self._rule_hits_device(
            tokens, lengths, row_req, row_sv, num_requests)
        return rule_hits

    def detect_device_multi(self, buckets, num_requests: int,
                            head_only: bool = False, device=None):
        """Multi-bucket dispatch with ONE rule expansion (docs/
        SCAN_KERNEL.md): ``buckets`` are packed buffers
        (:func:`empty_bucket`), and each is one call into JAX — one
        host-to-device transfer and one program
        (:func:`scan_fold_bucket`) that scans the bucket's rows and
        folds them into per-request factor hits.  The rule-count-scaling
        factor→rule expansion (``expand_requests``) then runs once, on
        the dispatch's own Q tier of the accumulators: ``buckets + 1``
        launches.  Executable space stays ADDITIVE — (B, L) for a
        bucket, Q for the expansion — the serving-stability property
        the per-bucket path always had.  (Any program keyed on the
        bucket SET would multiply the executable space by every
        combination of tier sizes a traffic mix produces; the serve
        plane recompiled its way into brownout under exactly that — the
        bench's detect_k, one static batch shape repeated, is where
        full fusion pays.)

        ``head_only=True`` (caller asserts no row carries a
        body/response stream-variant) scans the sliced head tables —
        the word prefix — instead of the full pack width.  Returns the
        (Q, R) rule-hit device array without blocking.

        ``device`` pins the dispatch to one chip of the serve mesh
        (docs/MESH_SERVING.md): the programs run against that device's
        replicated tables (``tables_for``) and accumulators, and the
        buffers follow them there, so N lanes' dispatches execute
        concurrently on N chips."""
        faults.sleep_if("dispatch_hang")
        faults.raise_if("dispatch_raise")
        # scan_launch: the host→device transfers and the enqueue of
        # every program, to the return of the last one (nothing blocks)
        with flight.span(EV_SCAN_LAUNCH, arg=len(buckets)):
            return self._enqueue_multi(buckets, num_requests, head_only,
                                       device)

    def _enqueue_multi(self, buckets, num_requests: int, head_only: bool,
                       device):
        """The body of :meth:`detect_device_multi` (its ``scan_launch``
        span): one call per bucket and the expansion, counted as they
        are made."""
        if not buckets:
            return np.zeros((num_requests, max(self.ruleset.n_rules, 1)),
                            bool)
        full_tabs, head_tabs = self.tables_for(device)
        tabs = (head_tabs if head_only and head_tabs is not None
                else full_tabs)
        # committed arguments (a lane's tables and accumulators) place
        # the program, and the host buffer follows them: the numpy
        # array goes to the device inside the bucket's own call
        req_fh, req_sv = self._accumulators(tabs, num_requests, device)
        launched = 0
        for packed in buckets:
            req_fh, req_sv = scan_fold_bucket(
                tabs, packed, req_fh, req_sv, impl=self.scan_impl)
            launched += 1
        rule_hits = expand_requests_jit(tabs, req_fh, req_sv,
                                        num_requests=num_requests)[0]
        self._count_launches(launched + 1)
        return rule_hits

    def _accumulators(self, tabs: EngineTables, num_requests: int,
                      device):
        """The zero (capacity, F) / (capacity, N_SV) accumulators a
        dispatch of ``tabs`` starts from, on ``device``: made once per
        shape and passed in, never donated, so no dispatch launches a
        program to zero them."""
        cap = self.request_capacity = max(self.request_capacity,
                                          num_requests)
        key = (cap, tabs.factor_word.shape[0], device)
        acc = self._zero_acc.get(key)
        if acc is None:
            acc = self._zero_acc[key] = tuple(
                jnp.zeros((cap, n), jnp.float32, device=device)
                for n in (key[1], N_SV))
        return acc

    def _count_launches(self, n: int) -> None:
        """``ipt_device_launches_total``: device programs enqueued by
        :meth:`detect_device_multi` (lane workers call it concurrently
        in mesh serving, hence the lock)."""
        with self._launch_lock:
            self.device_launches += n
