"""Per-device serve lanes — the mesh-scale dispatch plane
(docs/MESH_SERVING.md).

PR 4 gave the batcher ONE watchdogged device lane and ONE circuit
breaker: a wedged or erroring dispatch fails its batch open, the breaker
trips, and traffic rides the CPU confirm-only fallback.  That
generalizes here to N per-chip instances behind the same admission
queue: each :class:`Lane` owns one device, one single-worker dispatch
thread (so a hang on chip 3 cannot head-of-line-block chips 0-2 or the
dispatch thread), one :class:`CircuitBreaker`, and its own fill/hang
telemetry (``ipt_dispatch_fill{device=}`` and friends).

Degradation semantics (the capacity-not-service contract):

* a hung/erroring lane fails only ITS share of the cycle open and trips
  only ITS breaker — the other lanes' sub-batches resolve normally;
* while a lane's breaker is open the splitter simply stops assigning it
  rows (capacity degrades ~1/N, verdict quality does not);
* a half-open lane gets a small canary share; success closes it;
* the global CPU confirm-only fallback engages only when EVERY lane is
  down — the single-lane behavior of PR 4, now the last resort instead
  of the first.

Row placement: the splitter shards scan work at REQUEST granularity
(each request's rows travel together), weighted by scanned bytes, so no
cross-lane merge of per-request partials is ever needed and every lane's
executable shapes remain pure functions of its (B, L, Q) — the same
placement-free property the warm-shape replay contract depends on.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.trace import (
    EV_HANDOFF,
    EV_LANE_CALL,
    flight,
    named_lock,
)


class DeviceHang(Exception):
    """A device-lane call exceeded the hang budget."""


class LanePending:
    """Handle for one in-flight lane-worker call: ``wait(timeout)``
    returns the result, re-raises the worker's exception, or raises
    :class:`DeviceHang` — the caller decides what a hang means (the
    batcher fails that lane's share open and abandons the worker)."""

    __slots__ = ("_box", "_ev")

    def __init__(self, box: dict, ev: threading.Event):
        self._box = box
        self._ev = ev

    def done(self) -> bool:
        return self._ev.is_set()

    def wait_done(self, timeout: float) -> bool:
        """Block up to ``timeout`` for the call to end, without taking
        its result: True once it has (the dispatch loop's sliced waits
        on a stage; ``wait`` then returns at once)."""
        return self._ev.wait(timeout)

    def wait(self, timeout: Optional[float]):
        t_wait = time.monotonic_ns()
        if not self._ev.wait(timeout):
            raise DeviceHang("device dispatch exceeded %.3fs"
                             % (timeout if timeout is not None else -1.0))
        handoff = self._box.get("handoff")
        if handoff is not None:
            # the hand-off BACK (tag 1): from the closure's end — or
            # from when this thread began to wait, if that was later —
            # to this thread awake again
            cycle, t_end = handoff
            flight.span_at(EV_HANDOFF, max(t_end, t_wait),
                           time.monotonic_ns(), cycle=cycle, tag=1)
        if "error" in self._box:
            raise self._box["error"]
        return self._box.get("result")


class LaneWorker:
    """Single-worker executor for one device's dispatch, so callers can
    bound their wait: a wedged XLA dispatch times out instead of
    head-of-line-blocking every tenant.

    On timeout the worker is ABANDONED — Python cannot kill a thread
    stuck in native code, so the owner replaces the worker and the
    zombie (at most one per hang) exits when/if the stuck call returns.
    A zombie that un-sticks may still mutate pipeline telemetry
    counters concurrently with live traffic — bounded noise in
    observability, never in verdicts (its batch's futures were already
    resolved fail-open, and the batcher's ``_safe_set`` tolerates the
    late duplicate set)."""

    #: device lanes stamp the two thread hand-offs around a call as
    #: ``lane_handoff`` spans (put → closure start on the worker's ring,
    #: closure end → caller awake on the caller's): the caller's wait
    #: minus the closure's run time.  The confirm pool's workers reuse
    #: this machinery and turn the stamps off.
    HANDOFF_SPANS = True

    def __init__(self, seq: int = 0, lane_index: Optional[int] = None,
                 name: str = "ipt-device"):
        self.seq = seq
        self.lane_index = lane_index
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="%s-%d" % (name, seq))
        self._thread.start()

    def _setup(self) -> None:
        """Thread-local attribution stamped once at worker startup —
        lane-targeted fault injection (utils/faults.py ``lane=``): sites
        fired from this thread attribute to this lane.  Subclasses that
        reuse the bounded-call machinery for non-device work (the
        confirm plane's workers, models/confirm_plane.py) override this
        with their own attribution."""
        flight.register_thread("lane_worker")
        if self.lane_index is not None:
            faults.set_current_lane(self.lane_index)
            # spans this thread closes book to its lane too
            # (ipt_lane_stage_us, the annotations' lane=)
            flight.set_lane(self.lane_index)

    def _run(self) -> None:
        self._setup()
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, box, ev = item
            stamp = box.get("put")
            if stamp is not None:
                # the hand-off TO this worker (tag 0)
                flight.span_at(EV_HANDOFF, stamp[1], time.monotonic_ns(),
                               cycle=stamp[0], tag=0)
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed to the caller
                box["error"] = e
            if stamp is not None:
                box["handoff"] = (stamp[0], time.monotonic_ns())
            ev.set()

    def submit(self, fn: Callable) -> LanePending:
        box: dict = {}
        if self.HANDOFF_SPANS and flight.enabled:
            # (the caller's cycle id, the moment of the put)
            box["put"] = (flight.cycle(), time.monotonic_ns())
        ev = threading.Event()
        self._q.put((fn, box, ev))
        return LanePending(box, ev)

    def call(self, fn: Callable, timeout: float):
        pending = self.submit(fn)
        try:
            return pending.wait(timeout)
        except DeviceHang:
            self._q.put(None)   # the worker exits if it ever un-sticks
            raise

    def close(self, timeout: float = 2.0) -> None:
        self._q.put(None)
        self._thread.join(timeout=timeout)


class CircuitBreaker:
    """Device-path circuit breaker (docs/ROBUSTNESS.md).

    closed → open on a dispatch HANG (immediate: a wedged device does
    not get ``failure_threshold`` more batches to wedge) or on
    ``failure_threshold`` consecutive dispatch errors; open → half_open
    once ``cooldown_s`` has passed; half_open routes a SINGLE canary
    batch to the device — success closes the breaker, another
    failure/hang re-opens it and restarts the cooldown.  One instance
    per lane (docs/MESH_SERVING.md); the CPU confirm-only fallback
    engages only when every lane's breaker is open."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, failure_threshold: int = 3,
                 cooldown_s: float = 5.0):
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self.state = self.CLOSED
        self.failures = 0           # consecutive, reset on success
        self.trips = 0
        self.closes = 0
        self.probes = 0
        self.last_trip_reason: Optional[str] = None
        self._opened_at = 0.0
        self._lock = named_lock("CircuitBreaker._lock")

    def route(self) -> str:
        """Where this lane's share goes: "device" | "canary" |
        "fallback"."""
        with self._lock:
            if self.state == self.CLOSED:
                return "device"
            if self.state == self.OPEN:
                if time.monotonic() - self._opened_at < self.cooldown_s:
                    return "fallback"
                self.state = self.HALF_OPEN
                self.probes += 1
            return "canary"

    def trip(self, reason: str) -> None:
        with self._lock:
            self._trip_locked(reason)

    def _trip_locked(self, reason: str) -> None:
        self.state = self.OPEN
        self._opened_at = time.monotonic()
        self.trips += 1
        self.failures = 0
        self.last_trip_reason = reason

    def record_failure(self, reason: str = "dispatch_error") -> None:
        with self._lock:
            if self.state == self.HALF_OPEN:
                self._trip_locked("canary_" + reason)
                return
            self.failures += 1
            if self.state == self.CLOSED \
                    and self.failures >= self.failure_threshold:
                self._trip_locked(reason)

    def record_success(self) -> None:
        with self._lock:
            self.failures = 0
            if self.state == self.HALF_OPEN:
                self.state = self.CLOSED
                self.closes += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self.state,
                "consecutive_failures": self.failures,
                "failure_threshold": self.failure_threshold,
                "cooldown_s": self.cooldown_s,
                "trips": self.trips,
                "closes": self.closes,
                "probes": self.probes,
                "last_trip_reason": self.last_trip_reason,
                # the OPEN->HALF_OPEN transition only happens on the
                # next batch (route()); probe_due tells traffic-less
                # consumers (/readyz) that the cooldown has elapsed and
                # the breaker WANTS a canary — readiness must come back
                # so the canary can arrive, or an out-of-rotation pod
                # would stay unready forever
                "probe_due": (self.state == self.OPEN
                              and time.monotonic() - self._opened_at
                              >= self.cooldown_s),
            }


@dataclass
class LaneStats:
    """Per-lane dispatch telemetry (the ``device=`` label's backing
    store: ipt_dispatch_fill / ipt_watchdog_hangs_total /
    ipt_lane_* series)."""

    dispatches: int = 0
    requests: int = 0
    hangs: int = 0
    errors: int = 0
    rows: int = 0            # live scan rows dispatched to this device
    padded_rows: int = 0     # post-padding rows (fill denominator)
    busy_us: int = 0         # launch → materialized wall per dispatch
    stream_cycles: int = 0   # stream scan work pinned to this lane

    def fill(self) -> Optional[float]:
        if not self.padded_rows:
            return None
        return self.rows / self.padded_rows

    def snapshot(self) -> dict:
        d = dict(self.__dict__)
        d["dispatch_fill"] = (round(self.fill(), 4)
                              if self.padded_rows else None)
        return d


class Lane:
    """One device's serve lane: pinned device (or the default device on
    single-chip platforms), single-worker dispatch thread, breaker, and
    fill/hang telemetry."""

    def __init__(self, index: int, device: Any = None,
                 failure_threshold: int = 3, cooldown_s: float = 5.0):
        self.index = index
        self.device = device
        self.breaker = CircuitBreaker(failure_threshold=failure_threshold,
                                      cooldown_s=cooldown_s)
        self.stats = LaneStats()
        self._worker_seq = index * 1000
        self.worker = LaneWorker(self._worker_seq, lane_index=index)

    @property
    def label(self) -> str:
        return str(self.index)

    def submit(self, fn: Callable) -> LanePending:
        self.stats.dispatches += 1
        return self.worker.submit(fn)

    def call(self, fn: Callable, timeout: float):
        """Blocking bounded call; a hang abandons the worker (the PR 4
        single-lane semantics, now per chip)."""
        self.stats.dispatches += 1
        try:
            # lane_call: this thread blocked while the worker runs fn —
            # in a profiler trace, what of it no span of the worker's
            # covers is the hand-off (and the worker's unspanned code)
            with flight.span(EV_LANE_CALL, tag=self.index):
                return self.worker.call(fn, timeout)
        except DeviceHang:
            self.abandon_worker()
            raise

    def abandon_worker(self) -> None:
        """Replace a wedged worker thread.  The shutdown sentinel goes
        on the OLD worker's queue first, so the zombie exits when/if
        its stuck call returns instead of blocking on get() forever —
        without it every mesh-path hang would leak a thread for the
        process lifetime (reviewer catch; the call() path already
        queues its own sentinel, a duplicate is harmless)."""
        self.worker._q.put(None)
        self._worker_seq += 1
        self.worker = LaneWorker(self._worker_seq, lane_index=self.index)

    def snapshot(self) -> dict:
        return {
            "lane": self.index,
            "device": str(self.device) if self.device is not None else None,
            "breaker": self.breaker.snapshot(),
            **self.stats.snapshot(),
        }

    def close(self, timeout: float = 2.0) -> None:
        self.worker.close(timeout=timeout)


class LanePool:
    """N per-device lanes behind one admission queue
    (docs/MESH_SERVING.md).  ``devices`` are the jax devices of the
    ``("batch",)`` serve mesh — one lane each, sigpack tables replicated
    per device by the engine (``DetectionEngine.tables_for``).  A single
    lane may ride the default device (``devices=None``); more than one
    lane needs a distinct device each — lanes sharing a chip would
    serialize on it while reporting N-way capacity."""

    def __init__(self, n_lanes: int = 1,
                 devices: Optional[Sequence[Any]] = None,
                 failure_threshold: int = 3, cooldown_s: float = 5.0):
        if n_lanes < 1:
            raise ValueError("n_lanes must be >= 1, got %d" % n_lanes)
        devices = list(devices or ())
        if n_lanes > 1 and len(set(devices[:n_lanes])) < n_lanes:
            raise ValueError(
                "%d lanes need %d distinct devices, got %s"
                % (n_lanes, n_lanes, [str(d) for d in devices] or "none"))
        self.lanes: List[Lane] = [
            Lane(i, device=devices[i] if devices else None,
                 failure_threshold=failure_threshold,
                 cooldown_s=cooldown_s)
            for i in range(n_lanes)]

    @property
    def n(self) -> int:
        return len(self.lanes)

    @property
    def primary(self) -> Lane:
        return self.lanes[0]

    def lane(self, index: int) -> Lane:
        return self.lanes[index]

    def routes(self) -> List[Tuple[Lane, str]]:
        """One breaker decision per lane per cycle.  Returns the lanes
        willing to take device work this cycle with their route
        ("device" | "canary"); empty ⇒ every lane is down and the
        caller serves through the global CPU confirm-only fallback."""
        out = []
        for lane in self.lanes:
            r = lane.breaker.route()
            if r != "fallback":
                out.append((lane, r))
        return out

    def any_available(self) -> bool:
        """Readiness view: at least one lane can (or wants to) serve —
        closed, half-open, or open-with-cooldown-elapsed (probe_due:
        the canary that would close it needs traffic routed here)."""
        for lane in self.lanes:
            snap = lane.breaker.snapshot()
            if snap["state"] != CircuitBreaker.OPEN or snap["probe_due"]:
                return True
        return False

    @staticmethod
    def split(items: Sequence[Any],
              targets: Sequence[Tuple[Lane, str]],
              weight: Optional[Callable[[Any], int]] = None,
              canary_cap: int = 4) -> List[List[Any]]:
        """Deterministically shard one cycle's items across the serving
        lanes, balanced by ``weight`` (scanned bytes — padding waste
        concentrates when one lane draws all the long rows).  Half-open
        lanes get at most ``canary_cap`` items: a canary probes the
        device, it does not bet a full share of the cycle on it."""
        if not targets:
            return []
        loads = [0] * len(targets)
        counts = [0] * len(targets)
        out: List[List[Any]] = [[] for _ in targets]
        for item in items:
            w = weight(item) if weight is not None else 1
            best, best_load = None, None
            for i, (_lane, route) in enumerate(targets):
                if route == "canary" and counts[i] >= canary_cap:
                    continue
                if best is None or loads[i] < best_load:
                    best, best_load = i, loads[i]
            if best is None:       # every lane is a saturated canary
                best = loads.index(min(loads))
            out[best].append(item)
            loads[best] += w
            counts[best] += 1
        return out

    def snapshot(self) -> List[dict]:
        return [lane.snapshot() for lane in self.lanes]

    def close(self, timeout: float = 2.0) -> None:
        for lane in self.lanes:
            lane.close(timeout=timeout)
