"""Parallel confirm plane (docs/CONFIRM_PLANE.md).

PR 6 made the device scan pack-size-invariant and PR 7 sharded it
across per-chip lanes — leaving the serial CPU confirm loop in
``Pipeline.finalize`` as the serialized residue that bounds mesh
throughput (ROADMAP item 2's follow-on).  This module removes confirm
from the critical path three ways, all verdict-preserving:

1. **Sharded confirm workers** — :func:`confirm_one` is the pure
   per-request candidate walk (no shared mutable state: candidates in,
   confirmed rules + detail points out), so a :class:`ConfirmPool` can
   walk request shares in N walker processes at once
   (models/confirm_walker.py: the walk's regexes, substring tests and
   bytecode all hold the interpreter lock, so threads would walk one at
   a time) while the single-threaded fold (telemetry, scoring, ACL,
   Verdict assembly) stays in ``Pipeline.finalize_join``.  Each worker
   is a waiter thread that blocks on its walker's answer to the share
   the dispatch thread put on its pipe.  A wedged worker or a dead walker fails only ITS request
   share open within the pool's hang budget — the walker is killed and
   the worker replaced like a wedged device lane (serve/lanes.py),
   siblings' verdicts are untouched.
2. **Mandatory-literal quick-reject** — lives in models/confirm.py
   (``ConfirmRule.qr_literals``): a C-level ``literal in value`` check
   in front of every ``re.search``, derived from the same
   mandatory-factor machinery the prefilter soundness audit uses.
3. **Flood memoization** — :class:`ConfirmMemo`, a bounded per-cycle
   memo keyed on ``(rule, stream-bytes digest)``: replayed floods and
   templated scanners send near-identical segments, so the confirm
   outcome for an identical (rule, streams) pair is reused across
   requests within one cycle.  Per-request ctl target exclusions
   (``extra_excl``) bypass the memo entirely — their outcome is not a
   pure function of (rule, streams).

The parallel-firewall literature (PAPERS.md: GPU parallel firewalls,
arXiv:1312.4188; the Hyperflex prefilter/verify split, 2512.07123) says
the same thing twice: keep the cheap vectorized stage wide AND make the
exact verification stage both parallel and rarely-invoked.
"""

from __future__ import annotations

import itertools
import os
import pickle
import socket
import struct
import subprocess
import sys
import time
from contextlib import ExitStack
from hashlib import blake2b
from multiprocessing.connection import Connection
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from ingress_plus_tpu.serve.lanes import DeviceHang, LaneWorker
from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.trace import (
    EV_CONFIRM,
    EV_CONFIRM_IPC,
    flight,
    named_lock,
)


class ConfirmResult:
    """One request's confirm outcome — everything the single-threaded
    fold needs, nothing shared: ``confirmed`` (rule indices, walk
    order), ``points`` (attack-export match details, capped at 8),
    ``excluded`` (the runtime-ctl exclusion mask applied, for the
    telemetry fold), ``detection_only`` (a matched
    ctl:ruleEngine=DetectionOnly), and the per-rule cost samples
    ``rule_idx``/``rule_ns`` (RuleStats confirm-cost telemetry)."""

    __slots__ = ("confirmed", "points", "excluded", "detection_only",
                 "rule_idx", "rule_ns")

    def __init__(self) -> None:
        self.confirmed: List[int] = []
        self.points: List[dict] = []
        self.excluded: Optional[np.ndarray] = None
        self.detection_only = False
        self.rule_idx: List[int] = []
        self.rule_ns: List[int] = []


class ConfirmMemo:
    """Bounded per-cycle confirm memo keyed ``(rule_index, digest)``.

    The digest is a 16-byte blake2b over the request's confirm streams
    (key, length, bytes — unambiguous framing), computed at most once
    per request: identical streams ⇒ identical parse, identical
    transform outputs, identical operator outcome, identical detail
    points.  Bounded by refusing inserts at capacity (``suppressed``
    counts) — eviction would thrash on exactly the high-cardinality
    traffic the bound exists for, and a flood's working set is small by
    definition.  One memo serves one walk: the inline walk's batch, or
    one share in its walker process (which reports the counts)."""

    __slots__ = ("cap", "hits", "misses", "suppressed", "_d", "_seen")

    def __init__(self, cap: int = 4096) -> None:
        self.cap = int(cap)
        self.hits = 0
        self.misses = 0
        self.suppressed = 0
        self._d: Dict[tuple, tuple] = {}
        self._seen: set = set()

    def __len__(self) -> int:
        return len(self._d)

    def see(self, digest: bytes) -> bool:
        """Record one request digest; True when it was already seen
        this cycle.  Per-rule entries engage only from a digest's
        SECOND occurrence on — unique traffic pays one digest + one
        set op per request and ZERO per-rule memo round-trips
        (measured at ~9% of confirm before this gate), while a flood
        of N identical requests walks twice and hits N-2 times."""
        if digest in self._seen:
            return True
        if len(self._seen) < self.cap:
            # concheck: ok GIL-atomic set.add; a lost add just costs one duplicate confirm walk
            self._seen.add(digest)
        return False

    def get(self, key: tuple) -> Optional[tuple]:
        v = self._d.get(key)
        if v is not None:
            self.hits += 1  # concheck: ok telemetry-grade counter race
        return v

    def put(self, key: tuple, value: tuple) -> None:
        if len(self._d) < self.cap:
            self.misses += 1  # concheck: ok telemetry-grade counter race
            # concheck: ok GIL-atomic dict store; racers store the identical value for the key
            self._d[key] = value
        else:
            self.suppressed += 1  # concheck: ok telemetry-grade counter race


class VerdictCache:
    """Bounded CROSS-cycle confirm cache keyed ``(generation,
    rule_index, digest)`` — the promotion of :class:`ConfirmMemo` from
    per-batch to per-process (ISSUE 15, docs/RETUNE.md).

    Soundness is the memo's second-occurrence argument with the
    generation folded into the key: within one generation the confirm
    closures, ctl resolution, and rule-row order are immutable (a swap
    installs a NEW generation tag), so the outcome for (generation,
    rule, streams-digest) is a pure function and may be replayed across
    batches.  Per-request ctl target exclusions still bypass the cache
    entirely (confirm_one's ``extra_excl`` gate — unchanged).  Swap /
    rollout boundaries call :meth:`invalidate`; that is HYGIENE (the
    old generation's entries are unreachable dead weight), never a
    soundness requirement.

    Unlike the memo, capacity EVICTS oldest-first instead of refusing
    inserts: a long-running cache must follow the traffic mix as it
    drifts.  All dict/counter races are GIL-atomic / telemetry-grade,
    same discipline as ConfirmMemo; ``invalidate`` REBINDS fresh dicts
    (atomic swap) so racing readers see either generation's view,
    both sound."""

    __slots__ = ("cap", "hits", "misses", "suppressed", "evicted",
                 "invalidations", "_d", "_seen")

    def __init__(self, cap: int = 65536) -> None:
        self.cap = max(1, int(cap))
        self.hits = 0
        self.misses = 0
        self.suppressed = 0
        self.evicted = 0
        self.invalidations = 0
        self._d: Dict[tuple, tuple] = {}
        # (generation, digest) → True, insertion-ordered: the cross-
        # cycle second-occurrence gate (a flood recurring every batch
        # digests once per request but walks confirm only once total)
        self._seen: Dict[tuple, bool] = {}

    def __len__(self) -> int:
        return len(self._d)

    def see(self, key: tuple) -> bool:
        if key in self._seen:
            return True
        if len(self._seen) >= self.cap:
            try:
                # concheck: ok oldest-first eviction; a racing del costs one retried insert
                del self._seen[next(iter(self._seen))]
            except (KeyError, StopIteration, RuntimeError):
                pass
        self._seen[key] = True  # concheck: ok GIL-atomic dict store
        return False

    def get(self, key: tuple) -> Optional[tuple]:
        v = self._d.get(key)
        if v is not None:
            self.hits += 1  # concheck: ok telemetry-grade counter race
        return v

    def put(self, key: tuple, value: tuple) -> None:
        if len(self._d) >= self.cap:
            try:
                # concheck: ok oldest-first eviction under the GIL
                del self._d[next(iter(self._d))]
                self.evicted += 1
            except (KeyError, StopIteration, RuntimeError):
                self.suppressed += 1
                return
        self.misses += 1  # concheck: ok telemetry-grade counter race
        # concheck: ok GIL-atomic dict store; racers store the identical value for the key
        self._d[key] = value

    def invalidate(self, reason: str = "") -> None:
        """Drop every entry (swap / promote / rollback hygiene).  The
        rebind is one GIL-atomic store per dict, so in-flight views
        keep reading a consistent (old or new) snapshot."""
        self._d = {}
        self._seen = {}
        self.invalidations += 1

    def view(self, generation: str) -> "_CycleView":
        """Per-finalize-batch adapter speaking ConfirmMemo's interface
        with this pipeline generation folded into every key — the
        confirm walk (confirm_one) and the stats fold (finalize_join's
        per-job hit/miss deltas) run unchanged."""
        return _CycleView(self, generation)

    def snapshot(self) -> dict:
        return {"entries": len(self._d), "cap": self.cap,
                "hits": self.hits, "misses": self.misses,
                "suppressed": self.suppressed, "evicted": self.evicted,
                "invalidations": self.invalidations}


class _CycleView(ConfirmMemo):
    """One batch's handle on the shared VerdictCache: delegates storage
    to the cache (generation-prefixed keys) while keeping its OWN
    hit/miss counters, which finalize_join folds as per-batch deltas —
    exactly what it did with a per-cycle ConfirmMemo."""

    __slots__ = ("cache", "gen")

    def __init__(self, cache: VerdictCache, generation: str) -> None:
        super().__init__(cap=cache.cap)
        self.cache = cache
        self.gen = generation

    def see(self, digest: bytes) -> bool:
        return self.cache.see((self.gen, digest))

    def get(self, key: tuple) -> Optional[tuple]:
        r, digest = key
        v = self.cache.get((self.gen, r, digest))
        if v is not None:
            self.hits += 1  # concheck: ok telemetry-grade counter race
        return v

    def put(self, key: tuple, value: tuple) -> None:
        r, digest = key
        self.misses += 1  # concheck: ok telemetry-grade counter race
        self.cache.put((self.gen, r, digest), value)


def streams_digest(streams: Dict[str, bytes]) -> bytes:
    """Content digest of one request's confirm streams (sorted keys,
    length-framed values — no concatenation ambiguity)."""
    h = blake2b(digest_size=16)
    for k in sorted(streams):
        v = streams[k]
        h.update(k.encode())
        h.update(b"\x00")
        h.update(len(v).to_bytes(4, "big"))
        h.update(v)
    return h.digest()


def confirm_one(pl, req, hit_row: np.ndarray,
                memo: Optional[ConfirmMemo] = None) -> ConfirmResult:
    """The pure per-request confirm walk — the loop body of the old
    serial ``finalize``, minus every piece of shared state.  ``pl`` is
    the owning DetectionPipeline, read-only here (confirms, ctl_rules,
    ruleset — all immutable between swaps, and in-flight cycles pin
    their generation).  Verdict-affecting inputs beyond ``hit_row`` are
    all inside ``req.confirm_streams()`` — which is exactly why the
    memo can key on its digest."""
    res = ConfirmResult()
    hit_rules = np.nonzero(hit_row)[0]
    streams = req.confirm_streams() if len(hit_rules) else {}
    cache: Dict = {}   # per-request transform/collection memo across rules
    # pass 1 — runtime ctl exclusions: a matched exclusion rule
    # (ctl:ruleRemoveById / ruleRemoveTargetById / ruleEngine=Off)
    # removes rules or target subfields for THIS request before
    # detection rules are confirmed (ModSecurity's request-scoped ctl
    # semantics, resolved statically — compiler/ruleset.py _resolve_ctls)
    excluded = None          # (R,) bool or None
    extra_excl: Dict = {}    # rule index → {kind: {selector}}
    for ci, remove_mask, target_excl, engine in pl.ctl_rules:
        if not hit_row[ci]:
            continue
        if not pl.confirms[ci].matches_streams(streams, cache):
            continue
        if engine == "off":
            excluded = np.ones(hit_row.shape[0], dtype=bool)
            break
        if engine == "detection_only":
            res.detection_only = True
        if remove_mask.any():
            excluded = (remove_mask if excluded is None
                        else excluded | remove_mask)
        for idx, excl_map in target_excl.items():
            merged = extra_excl.setdefault(idx, {})
            for kind, sels in excl_map.items():
                merged.setdefault(kind, set()).update(sels)
    res.excluded = excluded
    confirms = pl.confirms
    rule_ids = pl.ruleset.rule_ids
    points = res.points
    confirmed = res.confirmed
    ctl_pass = pl._ctl_pass_idx
    rule_idx = res.rule_idx
    rule_ns = res.rule_ns
    use_memo = False
    digest = b""
    if memo is not None and len(hit_rules):
        # one digest + one seen-set op per request; per-rule memo
        # round-trips engage only from a digest's second occurrence
        # (ConfirmMemo.see) — unique traffic skips them entirely
        digest = streams_digest(streams)
        use_memo = memo.see(digest)
    cache_get = cache.get
    for r in hit_rules.tolist():
        if r in ctl_pass:
            continue   # config machinery, never a detection hit
        if excluded is not None and excluded[r]:
            continue
        cr = confirms[r]
        if cr._qr_rule_ok and r not in extra_excl:
            # whole-rule literal quick-reject, inlined (this loop runs
            # per candidate — the method-call form measurably slowed the
            # hot path): no mandatory literal in the shared haystack ⇒
            # the exact walk would return False for every value.  No
            # memo traffic and no cost sample either — a rejected walk
            # costs ~nothing by construction, and the confirm-cost
            # telemetry exists to rank the EXPENSIVE rules.
            hay = cache_get(("#qrh", cr._plan_sig, cr._tkey))
            if hay is None:
                hay = cr._build_qr_hay(streams, cache)
            for lit in cr.qr_literals:
                if lit in hay:
                    break
            else:
                cr.qr_skips += 1
                continue
        det: tuple | list
        tr0 = time.perf_counter_ns()
        if use_memo and r not in extra_excl:
            # flood memo: the outcome for (rule, streams) is pure —
            # per-request ctl target exclusions (extra_excl) are the
            # one request-scoped input, so those rules bypass the memo
            key = (r, digest)
            cached = memo.get(key)
            if cached is not None:
                hit, det = cached
            else:
                dl: list = []
                # detail is ALWAYS collected on the memoized path (a
                # later request may still have point budget when this
                # one's is spent); the points cap is applied below, so
                # the exported matches are byte-identical either way
                hit = cr.matches_streams(streams, cache, None,
                                         detail_out=dl)
                det = tuple(dl)
                memo.put(key, (hit, det))
        else:
            dl = []
            hit = cr.matches_streams(
                streams, cache, extra_excl.get(r),
                detail_out=dl if len(points) < 8 else None)
            det = dl
        rule_idx.append(r)
        rule_ns.append(time.perf_counter_ns() - tr0)
        if hit:
            confirmed.append(r)
            if det and len(points) < 8:
                points.append({"rule_id": int(rule_ids[r]),
                               "var": det[0][0],
                               "value": det[0][1]})
    return res


#: generations a walker process keeps installed (the least recently
#: dealt goes first, at the next install): the live one, a rollout's
#: candidate beside its incumbent, and the one a cycle in flight across
#: a swap still pins
KEEP_GENERATIONS = 4

_generations = itertools.count(1)


def next_confirm_generation() -> int:
    """A process-unique id for one installed confirm state
    (``DetectionPipeline._install``): what a share is tagged with and
    what the walker processes key their installed rules by.  The
    generation *tag* (ruleset version + scorer) cannot serve: two
    pipelines may carry one version."""
    return next(_generations)


def auto_workers(n_lanes: int = 1, cores: Optional[int] = None) -> int:
    """``--confirm-workers auto``, the server's default: the cores this
    process may run on, less one each for the dispatch thread, the
    event loop and every lane worker, capped at 8 — and 1 (the inline
    walk) where that leaves fewer than two."""
    if cores is None:
        cores = len(os.sched_getaffinity(0))
    spare = cores - 2 - max(1, n_lanes)
    return min(8, spare) if spare >= 2 else 1


class WalkerDied(Exception):
    """A walker process ended, or its pipe broke, with a call in
    flight."""


def _start_walker(send_timeout_s: float) -> Tuple[subprocess.Popen,
                                                  Connection]:
    """A fresh interpreter running ``models/confirm_walker.py`` on one
    end of a socket pair.  Fork-and-exec, never a bare fork: this
    process holds the accelerator runtime's threads.  ``subprocess``
    rather than ``multiprocessing``'s spawn context because the latter
    re-imports the parent's ``__main__`` in the child — the serve entry
    point, which imports jax.  A send into a walker that has stopped
    reading gives up after ``send_timeout_s`` (the caller is the
    dispatch thread)."""
    ours, theirs = socket.socketpair()
    sec = int(send_timeout_s)
    ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDTIMEO, struct.pack(
        "ll", sec, int((send_timeout_s - sec) * 1e6)))
    env = dict(os.environ)
    root = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (root, env.get("PYTHONPATH")) if p)
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "ingress_plus_tpu.models.confirm_walker",
             str(theirs.fileno())],
            pass_fds=(theirs.fileno(),), stdin=subprocess.DEVNULL,
            stdout=subprocess.DEVNULL, env=env)
    finally:
        theirs.close()
    return proc, Connection(ours.detach())


def walker_state(pl) -> dict:
    """What a walker process installs for ``pl``'s generation: the
    compiled rule descriptors (the ``ConfirmRule`` closures are rebuilt
    from them there, as ``_install`` builds them here) and the resolved
    ctl rules — everything :func:`confirm_one` reads of a pipeline."""
    return {"descs": [c.desc for c in pl.confirms],
            "ctl_rules": pl.ctl_rules,
            "ctl_pass_idx": pl._ctl_pass_idx,
            "rule_ids": pl.ruleset.rule_ids}


def _decode(raw: bytes, worker_index: int):
    kind, payload = pickle.loads(raw)
    if kind != "ok":
        raise RuntimeError("confirm walker %d: %s" % (worker_index, payload))
    return payload


class _ConfirmWorker(LaneWorker):
    """One confirm worker: a waiter thread and the walker process
    behind it.  The thread is LaneWorker's bounded-call machinery
    (submit/wait/abandon) with confirm-plane fault attribution —
    ``slow_confirm:worker=K`` plans target exactly one of these.  A
    message goes down the pipe on the thread that posts it
    (:meth:`post`: no hand-off on the way out), and the closure queued
    with it blocks on the answer here, the interpreter lock released."""

    HANDOFF_SPANS = False    # lane_handoff is the device lanes' span

    def __init__(self, seq: int, worker_index: int,
                 send_timeout_s: float = 30.0):
        self.worker_index = worker_index
        self.proc, self.conn = _start_walker(send_timeout_s)
        #: generation -> when it was last dealt a share (the pool's
        #: clock): what the walker holds.  This side decides what goes
        #: (an install names the generations to drop)
        self.held: Dict[int, int] = {}
        #: generations whose install is on the pipe
        self.installing: set = set()
        #: when an install found the walker gone (the pool respawns it)
        self.failed_at: Optional[float] = None
        # a message's bytes on the pipe and its reply's closure in the
        # queue go in one order
        self._wire = named_lock("_ConfirmWorker._wire")
        super().__init__(seq=seq, lane_index=None, name="ipt-confirm")

    def _setup(self) -> None:
        faults.set_current_confirm_worker(self.worker_index)
        flight.register_thread("confirm_worker")

    def _run(self) -> None:
        try:
            super()._run()
        finally:
            # end-of-file on its pipe is the walker's order to exit
            self.conn.close()
            try:
                self.proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def _gone(self, e: BaseException) -> WalkerDied:
        return WalkerDied("confirm walker %d (pid %d) is gone: %r"
                          % (self.worker_index, self.proc.pid, e))

    def send(self, data: bytes) -> None:
        """One pickled message down the pipe, on the calling thread,
        which holds ``_wire`` until it has queued the closure that
        takes the answer (:meth:`post`)."""
        try:
            self.conn.send_bytes(data)
        except OSError as e:
            raise self._gone(e) from e

    def post(self, data: bytes, on_reply) -> LanePending:
        """Send one pickled message NOW, on the calling thread, and
        queue ``on_reply`` for the waiter thread, which receives the
        answer in it (:meth:`recv_reply`).  Every message goes this
        way, so the answers come back in the queue's order."""
        with self._wire:
            self.send(data)
            return self.submit(on_reply)

    def recv_reply(self) -> bytes:
        """On the waiter thread: block on the next answer."""
        try:
            return self.conn.recv_bytes()
        except (EOFError, OSError) as e:
            raise self._gone(e) from e

    def ask(self, msg: tuple, timeout: float):
        """One message and its decoded answer (tests, tools)."""
        return _decode(self.post(pickle.dumps(msg, 5),
                                 self.recv_reply).wait(timeout),
                       self.worker_index)

    def post_install(self, gen: int, data: bytes, drop: Tuple[int, ...],
                     stamp: int) -> LanePending:
        """Put ``gen``'s install on the pipe (``data``: the pickled
        message, which names ``drop``); the generation counts as held,
        as of ``stamp``, once the walker has answered."""
        def _installed():
            try:
                _decode(self.recv_reply(), self.worker_index)
            except WalkerDied:
                self.failed_at = time.monotonic()
                raise
            finally:
                # concheck: ok GIL-atomic set.discard after the poster's add
                self.installing.discard(gen)
            for old in drop:
                # concheck: ok GIL-atomic dict.pop; readers use `in`/get
                self.held.pop(old, None)
            # concheck: ok GIL-atomic dict store
            self.held[gen] = stamp

        # concheck: ok GIL-atomic set.add; the waiter's discard follows the answer
        self.installing.add(gen)
        try:
            return self.post(data, _installed)
        except WalkerDied:
            self.installing.discard(gen)
            self.failed_at = time.monotonic()
            raise

    def kill(self) -> None:
        """End the walker now: its pipe reads end-of-file, so a waiter
        blocked on it wakes (:class:`WalkerDied`), leaves its loop at
        the sentinel and reaps the process."""
        self._q.put(None)
        self.proc.kill()

    def close(self, timeout: float = 2.0) -> None:
        self._q.put(None)
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():   # a share in flight
            self.proc.kill()
            self._thread.join(timeout=timeout)


class ConfirmJob:
    """One finalize batch's confirm phase in flight: launched by
    ``Pipeline.finalize_launch``, joined (bounded) by
    ``Pipeline.finalize_join``.  ``results[i]`` is None until that
    request's share lands — and stays None when its worker wedged or
    its walker died (the fold fails exactly those requests open)."""

    __slots__ = ("requests", "rule_hits", "results", "pending", "memo",
                 "launch_us", "join_us", "share_workers", "memo_hits",
                 "memo_misses", "cycle", "t0")

    def __init__(self, requests, rule_hits) -> None:
        self.requests = requests
        self.rule_hits = rule_hits
        #: when the batch was launched (perf_counter): the shares' one
        #: hang budget runs from here, whenever the join comes
        self.t0 = time.perf_counter()
        self.results: List[Optional[ConfirmResult]] = [None] * len(requests)
        #: [(worker, request indices, when its share was sent (ns),
        #:   LanePending or None where the walker was gone already)]
        self.pending: List[tuple] = []
        #: the inline walk's memo; a walker process keeps its own and
        #: reports the share's counts (``memo_hits``/``memo_misses``)
        self.memo: Optional[ConfirmMemo] = None
        self.launch_us = 0
        #: µs spent in :func:`join_confirm` waiting for the shares
        self.join_us = 0
        #: the worker index of each share dealt to a walker process
        #: (request qi went to share ``qi % len``); empty = walked
        #: inline by the caller
        self.share_workers: List[int] = []
        self.memo_hits = 0
        self.memo_misses = 0
        self.cycle = 0


class ConfirmPool:
    """N confirm workers behind the pipeline's finalize
    (``--confirm-workers N|auto``).  ``n_workers == 1`` runs INLINE on
    the calling thread — zero threads, zero processes, zero handoff,
    byte-for-byte the pre-pool serial walk (the <3% clean-path budget
    is enforced against this mode).  With N > 1 every worker is a
    waiter thread in front of a walker process
    (models/confirm_walker.py) and a batch of more than one request is
    dealt into shares of ``ceil(n / N)`` requests, one share a worker;
    a batched dispatch of one is walked inline by the caller whatever N
    is, and so is a batch that finds fewer than two workers holding its
    generation (walkers still starting, a generation nobody installed
    ahead of its traffic: the install goes out and a later batch finds
    it).  The one caller that asks otherwise (``lone_to_walker``) is
    the oversized side lane's finish thread: its lone request is a body
    past 16 KiB unpacked, whose walk of tens of ms would hold the
    interpreter lock beside the side lane's scan thread, so it goes to
    one walker holding the generation and the caller blocks on the
    pipe; with none such it is walked inline as well.  So a share's
    hang budget covers a walk and nothing else.  The pool is
    ruleset-free — the batcher carries ONE pool across hot swaps like
    the stats object, installing each new generation in the walkers
    before it serves (:meth:`install`)."""

    #: a worker whose walker was gone at an install is respawned no
    #: sooner than this (a host that cannot start walkers serves
    #: inline, at one attempt a slot per interval)
    RESPAWN_S = 5.0

    def __init__(self, n_workers: int = 1, hang_budget_s: float = 30.0):
        self.n_workers = max(1, int(n_workers))
        self.hang_budget_s = float(hang_budget_s)
        self.workers_replaced = 0
        #: requests walked, by where (ipt_confirm_requests_total{where=})
        self.requests_inline = 0
        self.requests_process = 0
        self._seq = 0
        self._clock = itertools.count(1)   # orders the deals (the LRU's)
        self._lock = named_lock("ConfirmPool._lock")
        self._workers: List[_ConfirmWorker] = []
        if self.n_workers > 1:
            self._workers = [self._spawn(i) for i in range(self.n_workers)]

    @property
    def inline(self) -> bool:
        return not self._workers

    def _spawn(self, index: int) -> _ConfirmWorker:
        self._seq += 1
        return _ConfirmWorker(seq=self._seq, worker_index=index,
                              send_timeout_s=self.hang_budget_s)

    def submit(self, index: int, fn):
        return self._workers[index].submit(fn)

    def _snapshot_workers(self) -> List[_ConfirmWorker]:
        with self._lock:
            return list(self._workers)

    def _post_installs(self, pl) -> Tuple[List[_ConfirmWorker], List]:
        """The workers whose walker holds ``pl``'s generation and has no
        install on its pipe; to every other one that lacks it the
        install goes out (once), and the handles of those posted by
        this call."""
        gen = pl.confirm_gen
        ready, posted = [], []
        data: Dict[tuple, bytes] = {}
        for i, w in enumerate(self._snapshot_workers()):
            if gen in w.held:
                if not w.installing:
                    ready.append(w)
                continue
            if w.failed_at is not None:
                if time.monotonic() - w.failed_at < self.RESPAWN_S:
                    continue
                w = self.replace(i, w)
            if gen in w.installing:
                continue
            # what this walker may forget: its least recently dealt
            # generations, so that it keeps KEEP_GENERATIONS
            known = sorted(list(w.held.items()), key=lambda kv: kv[1])
            drop = tuple(g for g, _at in known[:max(
                len(known) + len(w.installing) + 1 - KEEP_GENERATIONS, 0)])
            if drop not in data:
                data[drop] = pickle.dumps(
                    ("install", gen, walker_state(pl), drop), 5)
            try:
                posted.append(w.post_install(gen, data[drop], drop,
                                             next(self._clock)))
            except WalkerDied:
                pass    # failed_at is stamped: respawned at a later call
        return ready, posted

    def deal(self, pl, n: int,
             lone_to_walker: bool = False) -> List[_ConfirmWorker]:
        """The workers a batch of ``n`` requests of ``pl`` goes out to,
        a share each (request ``i`` to share ``i % len``): no worker
        gets more than ``ceil(n / N)`` and no more workers are used
        than that takes.  Empty: the caller walks inline — an inline
        pool, a batch of one (nothing to spread, and the hop would cost
        as much as the walk of a batched request), or fewer than two
        walkers holding the generation yet.  ``lone_to_walker``: a
        batch of one goes to one walker that holds the generation, the
        last of them (batched shares are dealt from the first), so that
        a long walk leaves the caller's interpreter lock alone; the
        side lane's finish asks it, and nothing else does."""
        if not self._workers or n < (1 if lone_to_walker else 2):
            return []
        ready, _posted = self._post_installs(pl)
        if n == 1:
            dealt = ready[-1:]
        elif len(ready) < 2:
            return []
        else:
            per = -(-n // len(ready))
            dealt = ready[:-(-n // per)]
        now = next(self._clock)
        for w in dealt:
            # concheck: ok GIL-atomic dict store; the LRU stamp
            w.held[pl.confirm_gen] = now
        return dealt

    def count(self, n: int, process: bool) -> None:
        """Book ``n`` requests walked in a walker process or inline.
        Two threads deal (the dispatch thread and the side lane's
        finish thread), so the counts take the pool's lock."""
        with self._lock:
            if process:
                self.requests_process += n
            else:
                self.requests_inline += n

    def install(self, pl, wait_s: float = 0.0) -> None:
        """Install ``pl``'s generation in every walker ahead of its
        first share (a pipeline's own pool at its install, the
        batcher's swap path, a rollout's candidate at its admission).
        The installs go out from this thread and are answered in
        parallel; ``wait_s`` bounds how long the caller stays for the
        answers.  Batches are walked inline until two walkers hold the
        generation."""
        _ready, posted = self._post_installs(pl)
        deadline = time.perf_counter() + wait_s
        for p in posted:
            try:
                p.wait(max(deadline - time.perf_counter(), 0.0))
            except Exception:  # noqa: BLE001 — _post_installs respawns
                pass

    def replace(self, index: int,
                worker: Optional[_ConfirmWorker] = None) -> _ConfirmWorker:
        """Abandon a wedged or dead worker: its walker process is
        killed (a process, unlike a thread stuck in native code, can
        be), its waiter thread exits when its call returns, and a fresh
        worker takes the slot.  ``worker``: the one the failed share
        went to — a slot somebody already replaced is left alone.
        Returns the slot's worker."""
        with self._lock:
            old = self._workers[index]
            if worker is not None and worker is not old:
                return old
            old.kill()
            new = self._workers[index] = self._spawn(index)
            self.workers_replaced += 1
            return new

    def lost(self, pl, worker: _ConfirmWorker) -> None:
        """A share's worker wedged or its walker died: replace it and
        tell the fresh walker the generation at once — the slot takes
        shares again when it holds it."""
        self.replace(worker.worker_index, worker)
        self.install(pl)

    def snapshot(self) -> dict:
        return {"workers": self.n_workers,
                "inline": self.inline,
                "walker_pids": [w.proc.pid
                                for w in self._snapshot_workers()],
                "hang_budget_s": self.hang_budget_s,
                "workers_replaced": self.workers_replaced,
                "requests_inline": self.requests_inline,
                "requests_process": self.requests_process}

    def close(self, timeout: float = 2.0) -> None:
        for w in self._snapshot_workers():
            w.close(timeout=timeout)


def _walk_inline(pl, job: ConfirmJob, tt: bool, trace_cycle: int) -> None:
    """The serial walk on the calling thread — the classic path."""
    requests, rule_hits = job.requests, job.rule_hits
    cache = getattr(pl, "confirm_cache", None)
    if cache is not None and len(requests):
        # cross-cycle verdict cache: engages even for 1-request batches
        # (the reuse is across cycles) and takes precedence over the
        # per-cycle memo — it subsumes it
        job.memo = cache.view(pl.generation_tag)
    else:
        cap = getattr(pl, "confirm_memo_entries", 0)
        if cap and len(requests) > 1:
            job.memo = ConfirmMemo(cap)
    memo = job.memo
    # worker id 0 stamped around the inline walk so worker-targeted
    # fault plans behave identically at --confirm-workers 1
    faults.set_current_confirm_worker(0)
    try:
        with flight.span(EV_CONFIRM, cycle=trace_cycle, tag=0,
                         arg=len(requests)):
            faults.sleep_if("slow_confirm")
            for qi, req in enumerate(requests):
                if tt:
                    faults.set_current_tenant(req.tenant)
                    faults.sleep_if("slow_confirm")
                job.results[qi] = confirm_one(pl, req, rule_hits[qi],
                                              memo)
    finally:
        if tt:
            faults.set_current_tenant(None)
        faults.set_current_confirm_worker(None)
    # no hop: the dispatch's confirm_ipc reads 0, beside its walk
    now = time.monotonic_ns()
    flight.span_at(EV_CONFIRM_IPC, now, now, cycle=trace_cycle)


def launch_confirm(pl, requests, rule_hits: np.ndarray,
                   lone_to_walker: bool = False) -> ConfirmJob:
    """Start one finalize batch's confirm phase.  Inline (an inline
    pool, or a batch of one): the whole walk runs NOW on the calling
    thread (the classic serial path).  Pooled: each share is put on its
    worker's pipe from this thread, its walker process walks it, its
    waiter thread blocks on the answer, and the call returns
    immediately — the batcher's loop overlaps the in-flight
    confirm with the next cycle's scan dispatch, the same software-
    pipelining move PR 7 made for host→device transfer.
    ``lone_to_walker`` (:meth:`ConfirmPool.deal`): a batch of one goes
    to a walker too — the side lane's finish, whose one body's walk
    must not hold the interpreter lock its scan thread needs.  Its spans
    carry the caller's ambient cycle, which on that thread is never a
    batched cycle's, so ``confirm_walk`` / ``confirm_ipc`` stay the
    batched dispatches' alone."""
    job = ConfirmJob(requests, rule_hits)
    pool = pl.confirm_pool
    n = len(requests)
    t0 = time.perf_counter()
    # tenant-targeted slow_confirm (docs/ROBUSTNESS.md "Tenant
    # isolation"): the per-request arrival points below exist ONLY when
    # the active plan targets a tenant — untargeted plans never reach
    # them, so their site arrival counts (and replays) are unchanged;
    # the share-level sleep_if above/below is invisible to a
    # tenant-targeted rule (no tenant stamped there).
    tt = faults.tenant_targeted("slow_confirm")
    # flight recorder: the cycle id (and the lane the dispatch thread
    # works for) is read on the CALLING thread and travels into the
    # worker closures, so a confirm share overlapping the NEXT cycle's
    # scan still stitches to the cycle whose verdicts it computes
    trace_cycle = job.cycle = flight.cycle()
    trace_lane = flight.lane()
    workers = pool.deal(pl, n, lone_to_walker)
    k = len(workers)
    job.share_workers = [w.worker_index for w in workers]
    if k == 0:
        pool.count(n, process=False)
        _walk_inline(pl, job, tt, trace_cycle)
        job.launch_us = int((time.perf_counter() - t0) * 1e6)
        return job
    pool.count(n, process=True)
    memo_cap = getattr(pl, "confirm_memo_entries", 0)
    cache = getattr(pl, "confirm_cache", None)
    cache_cap = cache.cap if cache is not None else 0
    shares = []
    for si, worker in enumerate(workers):
        idxs = list(range(si, n, k))
        items = []
        for i in idxs:
            cand = np.flatnonzero(rule_hits[i])
            items.append((requests[i].confirm_streams() if len(cand)
                          else {}, requests[i].tenant,
                          cand.astype(np.int32).tobytes()))
        shares.append((worker, idxs, pickle.dumps(
            ("walk", pl.confirm_gen, items, memo_cap, cache_cap), 5)))

    def _answer_of(idxs, worker):
        def _answer():
            # on the waiter thread: the fault site, then the answer
            flight.set_cycle(trace_cycle)
            flight.set_lane(trace_lane)
            try:
                with flight.span(EV_CONFIRM, cycle=trace_cycle,
                                 tag=worker.worker_index, arg=len(idxs)):
                    faults.sleep_if("slow_confirm")
                    if tt:
                        for i in idxs:
                            faults.set_current_tenant(requests[i].tenant)
                            faults.sleep_if("slow_confirm")
                    raw = worker.recv_reply()
            finally:
                if tt:
                    faults.set_current_tenant(None)
            return raw, time.monotonic_ns()
        return _answer

    # every share goes down its pipe before any waiter is woken: a
    # hand-off to a waiter thread costs this thread as much as a send,
    # and the walkers are walking while it makes them
    with ExitStack() as wires:
        sent = []
        for worker, _idxs, data in shares:
            wires.enter_context(worker._wire)
            try:
                t_out = time.monotonic_ns()
                worker.send(data)
                sent.append(t_out)
            except WalkerDied:
                sent.append(None)   # gone already: join fails it open
        for t_out, (worker, idxs, _data) in zip(sent, shares):
            job.pending.append((worker, idxs, t_out, worker.submit(
                _answer_of(idxs, worker)) if t_out is not None else None))
    job.launch_us = int((time.perf_counter() - t0) * 1e6)
    return job


def join_confirm(pl, job: ConfirmJob) -> List[Optional[ConfirmResult]]:
    """Bounded-join the confirm shares.  ONE shared deadline for the
    whole batch, counted from its launch (the shares launched together
    — k wedged workers cost one hang budget, not k, the lane-collection
    lesson of PR 7; and a join that comes late, the confirm having been
    held open across the next scan, adds no budget of its own).  A
    share past the deadline: its worker is abandoned (the walker
    process killed) and replaced, its requests' results stay None (the
    fold fails exactly those open), ``stats.confirm_hangs`` counts it.
    A share whose walker died under it: the same, less the hang count.
    A share that RAISED re-raises after every other share is folded —
    the batch-level error contract of the serial path, with the healthy
    shares' work not discarded by ordering.  The shares are taken once:
    a caller that must wait outside a lock and fold inside it
    (``StreamEngine.finish``) joins first, and the fold's own join
    finds nothing left; the wait is kept in ``job.join_us``."""
    if not job.pending:
        return job.results
    t0 = time.perf_counter()
    pool = pl.confirm_pool
    deadline = job.t0 + pool.hang_budget_s
    err: Optional[BaseException] = None
    confirms = pl.confirms
    pending_shares, job.pending = job.pending, []
    for worker, idxs, t_out, pending in pending_shares:
        try:
            if pending is None:
                raise WalkerDied("confirm walker %d was gone at the send"
                                 % worker.worker_index)
            raw, t_back = pending.wait(
                max(deadline - time.perf_counter(), 0.001))
            results, qr, hits, misses, walk_ns = _decode(
                raw, worker.worker_index)
        except (DeviceHang, WalkerDied) as e:
            if isinstance(e, DeviceHang):
                pl.stats.confirm_hangs += 1
            pool.lost(pl, worker)
            continue
        except Exception as e:  # noqa: BLE001 — re-raised below
            if err is None:
                err = e
            continue
        for i, res in zip(idxs, results):
            job.results[i] = res
        job.memo_hits += hits
        job.memo_misses += misses
        # the hop: from the share's send to its answer in the waiter's
        # hands, less the walker's own walk time (the pipe both ways,
        # the walker's unpickling and pickling, the wake-ups)
        flight.span_at(EV_CONFIRM_IPC,
                       t_back - max(t_back - t_out - walk_ns, 0), t_back,
                       cycle=job.cycle, tag=worker.worker_index)
        # the share's quick-reject counts land on this generation's
        # closures, where RuleStats gathers them
        for r, skips, evals in qr:
            cr = confirms[r]
            cr.qr_skips += skips
            cr.qr_evals += evals
    job.join_us += int((time.perf_counter() - t0) * 1e6)
    if err is not None:
        raise err
    return job.results
