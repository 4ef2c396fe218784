"""Serve the multi-chip detection step behind the single-chip engine API.

``MeshEngine`` adapts ShardedEngine (DP×TP over a Mesh, shard.py) to the
``DetectionEngine`` interface the serving stack consumes (pipeline
``detect_device`` bucket dispatch, batcher hot-swap, server ``--scan-impl
auto``), so ``serve --mesh data=2,model=4`` runs the SAME deadline
batcher / bucketing / confirm pipeline with the scan spread over a
device mesh.  Reference parity: wallarm scales the data plane by adding
nginx workers/replicas (SURVEY §2.4 DP row); here one serve process
scales across the chips it owns.

Row layout contract: the adapter uses the sharded step's GLOBAL-ROWS
variant (shard.py ``_build_step(global_rows=True)``) — rows ride in
caller order with GLOBAL request ids, the data shards each
segment-reduce their own row slice against all Q segments, and the
per-request partials merge with one psum over the "data" axis.  Row
placement is therefore free, and every jit shape is a pure function of
(B, L, Q) — which is exactly the batcher's seen_shapes/warm_shape
replay contract (a placement-dependent shape would make the hot-swap
pre-compile the wrong executables and stall post-swap traffic on XLA
compiles under the swap lock).

Tenant (EP) masking stays in the PIPELINE (mask_hits), exactly as for
the single-chip engine — the adapter always builds the sharded step with
the trivial all-tenants mask so the two paths cannot diverge on EP
semantics.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np

from ingress_plus_tpu.compiler.ruleset import CompiledRuleset
from ingress_plus_tpu.parallel.shard import ShardedEngine
from ingress_plus_tpu.utils.overlap import collect as overlap_collect

try:  # Mesh type only used for annotations / isinstance docs
    from jax.sharding import Mesh
except Exception:  # pragma: no cover
    Mesh = None


def batch_mesh(devices: Optional[Sequence] = None):
    """The data-parallel serve mesh: every local device on one
    ``("batch",)`` axis (docs/MESH_SERVING.md).  Scan rows shard across
    it at request granularity (serve/lanes.py LanePool) with the
    sigpack replicated once per device
    (models/engine.DetectionEngine.tables_for)."""
    from jax.sharding import Mesh as _Mesh

    devs = list(devices) if devices is not None else jax.devices()
    return _Mesh(np.asarray(devs), ("batch",))


def run_lane_measurement(cr: CompiledRuleset, n_lanes: int,
                         n_req: int = 1024, max_batch: int = 64,
                         mode: str = "block",
                         seed: int = 42,
                         tier_warmup: bool = True) -> dict:
    """Measure the LANE-SHARDED serve plane end to end: a real Batcher
    with ``n_lanes`` per-device lanes over the local jax devices, warmed
    then driven with a labeled corpus through the real admission queue.
    Returns ``req_per_s_mesh`` plus per-device utilization — the number
    MULTICHIP graduates to (a smoke test proves the mesh exists; this
    proves what it serves).  Shared by ``bench.py --mesh-point`` and
    ``__graft_entry__.dryrun_multichip`` so the two artifacts can never
    measure different programs."""
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.utils.corpus import generate_corpus

    devices = jax.devices()
    pipeline = DetectionPipeline(cr, mode=mode)
    # throughput harness: the whole corpus floods the queue at once, so
    # the SLO machinery must stand down — a huge deadline (no queue-math
    # shedding of the backlog) and a queue that fits the corpus.  The
    # serve default keeps its bounded admission; this measures capacity.
    batcher = Batcher(pipeline, max_batch=max_batch,
                      max_delay_s=0.0005, n_lanes=n_lanes,
                      lane_devices=devices,
                      hard_deadline_s=600.0,
                      queue_cap=max(8192, n_req + 16))
    try:
        corpus = generate_corpus(n=n_req, attack_fraction=0.2, seed=seed)
        requests = [lr.request for lr in corpus]
        t_w0 = time.perf_counter()
        # ``tier_warmup=False`` (the bench mesh-scale points on the
        # full CRS pack): skip the exhaustive Q-pad-tier pass — the
        # corpus warm pass below compiles exactly the shapes the
        # measured pass replays, at a fraction of the big pack's tier
        # compile bill
        if tier_warmup and n_lanes > 1:
            batcher.warm_lanes()
        elif tier_warmup:
            # same coverage for the 1-lane baseline point: every Q-pad
            # tier through the single-lane path
            from ingress_plus_tpu.models.pipeline import warm_sizes

            for size in warm_sizes(max_batch):
                pipeline.detect(requests[:size])
            pipeline.reset_detection_observations()
        # one unmeasured pass of the corpus itself: live traffic's
        # bucket mixes differ from the synthetic warm corpus, and a
        # first-pass jit compile inside the measured window would book
        # as mesh throughput (the r03-r05 lesson, per lane now)
        futs = [batcher.submit(r) for r in requests]
        for f in futs:
            f.result(timeout=600)
        warm_s = time.perf_counter() - t_w0
        batcher.reset_latency_observations()
        # measured pass: the full admission→split→scan→confirm→verdict
        # chain, wall-clocked from first submit to last resolved future
        ps = pipeline.stats
        c0, e0, p0 = ps.confirm_us, ps.engine_us, ps.prep_us
        t0 = time.perf_counter()
        futs = [batcher.submit(r) for r in requests]
        verdicts = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        # confirm-stage share of the measured window's pipeline time
        # (docs/CONFIRM_PLANE.md): the serialized-residue gauge the
        # mesh-scale leg warns on — when confirm bounds mesh
        # throughput, more chips cannot help
        d_confirm = ps.confirm_us - c0
        d_stages = d_confirm + (ps.engine_us - e0) + (ps.prep_us - p0)
        confirm_share = (round(d_confirm / d_stages, 4)
                         if d_stages > 0 else None)
        fail_open = sum(1 for v in verdicts if v.fail_open)
        attacks = sum(1 for v in verdicts if v.attack)
        lanes = batcher.lanes.snapshot()
        util = {str(ln["lane"]): (round(ln["busy_us"] / (wall * 1e6), 4)
                                  if wall > 0 else None)
                for ln in lanes}
        return {
            "n_devices": len(devices),
            "n_lanes": n_lanes,
            "requests": n_req,
            "req_per_s_mesh": round(n_req / wall, 1) if wall > 0 else None,
            "wall_s": round(wall, 3),
            "warmup_s": round(warm_s, 1),
            "verdicts": len(verdicts),
            "fail_open": fail_open,
            "attacks": attacks,
            "per_device_utilization": util,
            "per_lane": [{k: ln[k] for k in
                          ("lane", "device", "requests", "rows",
                           "dispatch_fill", "hangs", "errors", "busy_us")}
                         for ln in lanes],
            "serve_time_recompiles": pipeline.stats.engine_compiles,
            "confirm_share": confirm_share,
            "confirm_us": d_confirm,
            "confirm_workers": pipeline.confirm_pool.n_workers,
            # cycle flight recorder (ISSUE 12): the MEASURED overlap
            # structure of this point — scan↔confirm overlap fraction,
            # per-lane idle share, drain occupancy, critical path,
            # serialized-residue ranking (utils/overlap.py); the
            # recorder was reset with the latency observations, so the
            # report describes only the measured pass
            "pipeline_overlap": overlap_collect(batcher),
            "ruleset": {"rules": int(cr.n_rules),
                        "words": int(cr.tables.n_words)},
        }
    finally:
        batcher.close()


def parse_mesh_spec(spec: str, n_devices: Optional[int] = None):
    """'data=2,model=4' (or '2x4') → an actual jax Mesh over the local
    devices.  A total of 0 on either axis is rejected; the product must
    not exceed the device count."""
    spec = spec.strip()
    if "x" in spec and "=" not in spec:
        d, m = spec.split("x", 1)
        n_data, n_model = int(d), int(m)
    else:
        kv = dict(p.split("=", 1) for p in spec.split(","))
        n_data, n_model = int(kv["data"]), int(kv["model"])
    if n_data < 1 or n_model < 1:
        raise ValueError("mesh axes must be >= 1: %r" % spec)
    devs = jax.devices()
    need = n_data * n_model
    if n_devices is not None:
        devs = devs[:n_devices]
    if need > len(devs):
        raise ValueError("mesh %dx%d needs %d devices, have %d"
                         % (n_data, n_model, need, len(devs)))
    arr = np.asarray(devs[:need]).reshape(n_data, n_model)
    from jax.sharding import Mesh as _Mesh
    return _Mesh(arr, ("data", "model"))


class MeshEngine:
    """DetectionEngine-compatible facade over the sharded DP×TP step."""

    #: sharded impls only — the pipeline/server select from these
    SCAN_IMPLS = ShardedEngine.SCAN_IMPLS

    def __init__(self, cr: CompiledRuleset, mesh, scan_impl: str = "pair"):
        if jax.process_count() > 1:
            raise ValueError(
                "MeshEngine serves a SINGLE-host mesh (its dispatch "
                "builds host-local arrays); multi-host batches ride "
                "parallel/dcn.py make_global into ShardedEngine.detect "
                "instead — see tests/test_dcn.py")
        self.ruleset = cr
        self.mesh = mesh
        self._sharded = ShardedEngine(cr, mesh, scan_impl=scan_impl)
        self._tables = None        # lazy single-chip tables (stream path)

    # ------------------------------------------------ engine API surface

    @property
    def scan_impl(self) -> str:
        return self._sharded.scan_impl

    @scan_impl.setter
    def scan_impl(self, v: str) -> None:
        self._sharded.set_scan_impl(v)

    @property
    def tables(self):
        """Single-chip EngineTables for consumers that scan OUTSIDE the
        mesh step (the streaming-body carry path runs chunk scans
        locally; only whole-batch prefilter rides the mesh)."""
        if self._tables is None:
            from ingress_plus_tpu.models.engine import EngineTables
            self._tables = EngineTables.from_ruleset(self.ruleset)
        return self._tables

    def device_info(self) -> dict:
        """Engine-API twin of DetectionEngine.device_info (served by
        /rules/stats), plus the mesh shape the scan is sharded over."""
        t = self.ruleset.tables
        return {
            "scan_impl": self.scan_impl,
            "n_rules": int(self.ruleset.n_rules),
            "n_factors": int(t.n_factors),
            "n_words": int(t.n_words),
            "max_factor_len": int(t.max_factor_len),
            "mesh": {str(k): int(v)
                     for k, v in self.mesh.shape.items()},
        }

    def swap_ruleset(self, cr: CompiledRuleset) -> None:
        self.ruleset = cr
        self._tables = None
        self._sharded = ShardedEngine(cr, self.mesh,
                                      scan_impl=self.scan_impl)

    def drop_compiled(self) -> None:
        """Engine-API twin of DetectionEngine.drop_compiled (the
        recompile_storm fault site calls it on whatever engine serves):
        forget every compiled executable."""
        import jax

        jax.clear_caches()
        self._tables = None

    def rebuilt(self, cr: CompiledRuleset) -> "MeshEngine":
        """Fresh engine of the SAME kind on a new ruleset (batcher
        hot-swap contract — see DetectionEngine.rebuilt)."""
        return MeshEngine(cr, self.mesh, scan_impl=self.scan_impl)

    def autoselect_scan_impl(self, **kw) -> dict:
        """Measure the sharded impls on the live mesh, install the
        winner, and return {impl: seconds} (the server prints it).
        Measures the global-rows step — the variant _dispatch serves
        with — so the bake-off ranks and pre-warms the real program."""
        kw.setdefault("global_rows", True)
        self._sharded.autoselect_scan_impl(**kw)
        return dict(getattr(self._sharded, "last_timings", {}))

    # -------------------------------------------------------- dispatch

    def _dispatch(self, tokens, lengths, row_req, row_sv,
                  num_requests: int):
        """One global-rows sharded step; returns the device
        (num_requests, R) rule-hit array plus class/score legs.

        The global-rows step (shard.py _build_step(global_rows=True))
        reduces GLOBAL request ids and psums verdict partials across the
        data axis, so row placement is free: rows ride in caller order,
        the row axis pads to n_data * B_s with B_s a pure function of
        the row count — which makes every jit shape a function of
        (B, L, Q) alone, exactly what the batcher's warm_shape replay
        (seen_shapes contract) pre-compiles."""
        eng = self._sharded
        n_data = eng.mesh.shape["data"]
        tokens = np.asarray(tokens)
        lengths = np.asarray(lengths, np.int32)
        row_req = np.asarray(row_req, np.int32)
        row_sv = np.asarray(row_sv, np.int8)

        B = tokens.shape[0]
        B_s = max(8, 1 << int(np.ceil(np.log2(max(1, -(-B // n_data))))))
        L = tokens.shape[1]
        if L % 2:
            L += 1          # pair recurrence consumes byte PAIRS
        tok2 = np.zeros((n_data * B_s, L), tokens.dtype)
        len2 = np.zeros((n_data * B_s,), np.int32)
        # padding rows carry request id 0 — harmless ONLY because their
        # row_sv stays all-zero: `applies` is then false for every rule,
        # so they can never contribute a vote (do not give padding rows
        # a nonzero sv)
        req2 = np.zeros((n_data * B_s,), np.int32)
        sv2 = np.zeros((n_data * B_s, row_sv.shape[1]), np.int8)
        tok2[:B, :tokens.shape[1]] = tokens
        len2[:B] = lengths
        req2[:B] = row_req
        sv2[:B] = row_sv
        # per-REQUEST tenant ids (replicated in the global-rows step);
        # EP masking happens in the pipeline, so the trivial tenant 0
        # rides here
        ten2 = np.zeros((num_requests,), np.int32)
        step = eng._build_step(eng.scan_impl, global_rows=True)
        rh, ch, sc = step(
            jax.numpy.asarray(tok2), jax.numpy.asarray(len2),
            jax.numpy.asarray(req2), jax.numpy.asarray(sv2),
            jax.numpy.asarray(ten2), num_requests=num_requests)
        return rh, ch, sc

    def detect_device(self, tokens, lengths, row_req, row_sv,
                      num_requests: int):
        rh, _, _ = self._dispatch(tokens, lengths, row_req, row_sv,
                                  num_requests)
        return rh

    def detect(self, tokens, lengths, row_req, row_sv, num_requests: int):
        rh, ch, sc = self._dispatch(tokens, lengths, row_req, row_sv,
                                    num_requests)
        return np.asarray(rh), np.asarray(ch), np.asarray(sc)
