"""The one dispatch loop (`Batcher._run`) and its admission estimate.

Every lane count runs the pipelined cycle: launch, scan on the lanes,
collect into a free confirm stage, confirm on the walkers, resolve;
one cycle scans while the one before confirms.  With a confirm held
open one more cycle stands between an arrival and its verdict, so the
queue math multiplies the service time by one more: the estimate of that time must not let ONE stalled cycle
shed a closed loop's whole in-flight set (what refused PR 32), and must
still shed when the plane is slow cycle after cycle.
"""

import threading
import time
from types import SimpleNamespace

import pytest

from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils import faults
from ingress_plus_tpu.utils.faults import FaultPlan
from ingress_plus_tpu.utils.trace import RecentMedian

HARD_DEADLINE_S = 0.25


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.clear()
    yield
    faults.clear()


def _batcher(n_lanes=1, confirm_workers=2, max_delay_s=0.0005,
             hard_deadline_s=HARD_DEADLINE_S):
    """The server's batcher geometry (256 / 0.5 ms / 250 ms) on the
    fault matrix's pack, with walkers to hold a confirm open on (the
    loop whose queue math has ``held`` 1) unless told otherwise; served
    a few waves so that the first cycles below compile nothing.  (The
    tests that widen the batch window to 200 ms, so that what they
    submit together is one cycle, move the deadline out with it: a
    wait of a quarter of the deadline is the brownout ladder's first
    rung, and a degraded verdict has no walk.)"""
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    pipeline = DetectionPipeline(faults._matrix_ruleset(), mode="block",
                                 confirm_workers=confirm_workers)
    b = Batcher(pipeline, n_lanes=n_lanes, max_batch=256,
                max_delay_s=max_delay_s, hard_deadline_s=hard_deadline_s)
    for size in (16, 16, 4, 1):
        futs = [b.submit(r) for r in
                faults._requests(size, attack_every=4, tag="warm")]
        faults._collect(futs, timeout_s=120)
    return b


class _ClosedLoop:
    """``n`` client threads, each: submit, wait for the verdict, hop
    2 ms, again — the benchmark's closed loops in small."""

    def __init__(self, batcher, n=32):
        self.b = batcher
        self.stop = threading.Event()
        self.verdicts = []
        self.unresolved = 0
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._client, args=(i,))
                        for i in range(n)]

    def _client(self, i):
        k = 0
        while not self.stop.is_set():
            k += 1
            fut = self.b.submit(Request(uri="/c%d?i=%d" % (i, k),
                                        request_id="c%d-%d" % (i, k)))
            try:
                v = fut.result(timeout=60)
            except Exception:
                with self._lock:
                    self.unresolved += 1
                continue
            with self._lock:
                self.verdicts.append(v)
            time.sleep(0.002)

    def __enter__(self):
        for t in self.threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        for t in self.threads:
            t.join(timeout=120)

    def wait_samples(self, n, timeout_s=120):
        """Until the estimator holds ``n`` more samples."""
        target = self.b._service.n + n
        deadline = time.monotonic() + timeout_s
        while self.b._service.n < target:
            assert time.monotonic() < deadline, "the loop stopped cycling"
            time.sleep(0.01)


# ------------------------------------------------------- stall tests

@pytest.mark.parametrize("stall_s", [0.6, 3.0])
@pytest.mark.parametrize("n_lanes", [1, 2])
def test_one_stalled_cycle_sheds_nothing(n_lanes, stall_s):
    """One device dispatch that takes ``stall_s`` (under the hang
    budget: a stall, not a hang) under a closed loop of 32: every
    request gets its real verdict, late.  The parent's pipelined loop
    shed 84 and 36 here through its estimator (ISSUE 33)."""
    b = _batcher(n_lanes)
    try:
        with _ClosedLoop(b) as loop:
            loop.wait_samples(20)
            plan = FaultPlan.from_spec(
                "dispatch_hang:times=1,delay_s=%g" % stall_s)
            faults.install(plan)
            deadline = time.monotonic() + 60
            while not plan.fired["dispatch_hang"]:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            time.sleep(stall_s)
            loop.wait_samples(12)
        assert not any(t.is_alive() for t in loop.threads)
        assert loop.unresolved == 0
        assert dict(b.pipeline.stats.shed) == {}
        assert not any(v.fail_open for v in loop.verdicts)
        assert b.stats.hangs == 0 and b.breaker.trips == 0
    finally:
        b.close()


@pytest.mark.parametrize("n_lanes", [1, 2])
def test_sustained_slowness_still_sheds(n_lanes):
    """Every cycle 0.2 s with a backlog behind it: (1 + 1 + held) x
    0.2 s passes the 250 ms deadline, and the queue math sheds within
    five such cycles (two more may have been in flight before them)."""
    b = _batcher(n_lanes)
    try:
        with _ClosedLoop(b) as loop:
            loop.wait_samples(20)
            n0 = b._service.n
            faults.install(FaultPlan.from_spec(
                "dispatch_hang:times=1000,delay_s=0.2"))
            deadline = time.monotonic() + 60
            while not b.pipeline.stats.shed.get("deadline"):
                assert time.monotonic() < deadline, "never shed"
                time.sleep(0.005)
            cycles = b._service.n - n0
            faults.clear()
        assert cycles <= 5 + 3, cycles
        assert loop.unresolved == 0
    finally:
        b.close()


def _est_wait(samples, depth, held):
    """`Batcher._est_wait_s` alone: the estimator fed by hand, a pool
    of one worker (``held`` 0) or two (1), the server's max_batch."""
    stub = SimpleNamespace(
        _service=RecentMedian(9), max_batch=256,
        pipeline=SimpleNamespace(
            confirm_pool=SimpleNamespace(n_workers=1 + held)))
    for x in samples:
        stub._service.update(x)
    return Batcher._est_wait_s(stub, depth)


@pytest.mark.parametrize("held", [0, 1])
@pytest.mark.parametrize("stall_s", [0.3, 0.5, 30.0])
@pytest.mark.parametrize("cycle_s", [0.005, 0.020, 0.045])
def test_one_sample_of_any_length_keeps_the_estimate_under_the_deadline(
        cycle_s, stall_s, held):
    samples = [cycle_s] * 20 + [stall_s]
    for depth in (1, 32, 128):
        assert _est_wait(samples, depth, held) <= HARD_DEADLINE_S
        # and it stands where it stood
        assert _est_wait(samples, depth, held) == \
            _est_wait(samples[:-1], depth, held)


@pytest.mark.parametrize("held", [0, 1])
@pytest.mark.parametrize("cycle_s", [0.005, 0.020, 0.045])
def test_five_slow_cycles_in_a_row_pass_the_deadline(cycle_s, held):
    steady = [cycle_s] * 20
    for depth in (1, 32, 128):
        assert _est_wait(steady + [0.2] * 4, depth, held) <= HARD_DEADLINE_S
        assert _est_wait(steady + [0.2] * 5, depth, held) > HARD_DEADLINE_S
    # a cold estimator never sheds, whatever it has seen
    assert _est_wait([0.2] * 5, 128, held) == 0.0


# -------------------------------------------------------- loop tests

def _two_cycles_back_to_back(b, stall_s):
    """Eight requests as two cycles, the second launched while the
    first one's confirm stage is open: the first four's walk is slowed
    to ``stall_s`` (both shares of a two-worker pool; inline, the
    dispatch thread itself sleeps there), and the second four are
    submitted once the first are launched.  (The batch window is 200 ms
    here, so that what is submitted together is one cycle.)  Returns
    the futures once both cycles are launched."""
    faults.install(FaultPlan.from_spec(
        "slow_confirm:times=2,delay_s=%g" % stall_s))
    futs = []
    for tag in ("x", "y"):
        launched = b.stats.batches
        # attacks: a request with no candidate has no walk to slow
        futs += [b.submit(r) for r in
                 faults._requests(4, attack_every=1, tag=tag)]
        deadline = time.monotonic() + 30
        while b.stats.batches == launched:
            assert time.monotonic() < deadline
            time.sleep(0.002)
    return futs


def test_idle_tail_resolves_at_once_at_one_lane_with_walkers():
    """Five requests, then nothing: the cycle resolves as soon as its
    walkers have answered (the loop looks a batch window at a time),
    direct: no later launch found its confirm open.  Not an idle tick
    (50 ms) later, and not when the next request happens to come."""
    b = _batcher(1, max_delay_s=0.2, hard_deadline_s=4.0)
    try:
        s = b.stats
        held0, direct0 = s.cycles_held, s.cycles_direct
        t0 = time.perf_counter()
        futs = [b.submit(r) for r in faults._requests(5, tag="tail")]
        verdicts = [f.result(timeout=30) for f in futs]
        took = time.perf_counter() - t0
        assert [v.request_id for v in verdicts] == \
            ["tail%d" % i for i in range(5)]
        assert not any(v.fail_open for v in verdicts)
        assert s.cycles_held == held0
        assert s.cycles_direct > direct0
        assert took < 5.0
    finally:
        b.close()


@pytest.mark.parametrize("n_lanes", [1, 2])
def test_close_with_cycles_in_flight_resolves_each_future_once(n_lanes):
    """`close()` while one cycle's confirm is open, the next one's scan
    is in flight and more is queued behind both: the loop's tail
    resolves the first, collects and resolves the second, and what was
    still queued drains fail-open (`shutdown`).  Every future resolves, and each is
    counted on exactly one of the two paths."""
    b = _batcher(n_lanes, max_delay_s=0.2, hard_deadline_s=4.0)
    try:
        completed0 = b.stats.completed
        futs = _two_cycles_back_to_back(b, stall_s=1.0)
        assert not any(f.done() for f in futs)
        futs += [b.submit(r) for r in faults._requests(4, tag="z")]
    finally:
        b.close()
    assert all(f.done() for f in futs)
    verdicts = [f.result(timeout=0) for f in futs]
    assert [v.request_id for v in verdicts] == \
        ["%s%d" % (tag, i) for tag in "xyz" for i in range(4)]
    served = b.stats.completed - completed0
    shed = b.pipeline.stats.shed.get("shutdown", 0)
    assert (served, shed) == (8, 4)
    assert [v.fail_open for v in verdicts] == [False] * 8 + [True] * 4
    assert not b._active_guards


# ----------------------------------------------------- counter tests

def _metrics(batcher) -> str:
    from ingress_plus_tpu.serve.server import ServeLoop

    return ServeLoop(batcher, "/tmp/unused.sock")._metrics_text()


@pytest.mark.parametrize("confirm_workers,held", [(2, 1), (1, 0)])
def test_cycles_counter_moves_as_the_hold_decision_does(
        confirm_workers, held):
    """Two cycles back to back (the first one's walk slowed, so that
    the second is launched while it lasts): with walkers the first
    one's confirm was held open across that launch and the second, a
    lone tail, resolves direct; an inline confirm is over when the
    collection returns, there is no stage to hold open, and both are
    direct."""
    b = _batcher(1, confirm_workers, max_delay_s=0.2,
                 hard_deadline_s=4.0)
    try:
        s = b.stats
        held0, direct0 = s.cycles_held, s.cycles_direct
        futs = _two_cycles_back_to_back(b, stall_s=1.0)
        verdicts = [f.result(timeout=30) for f in futs]
        assert not any(v.fail_open for v in verdicts)
        assert s.cycles_held - held0 == held
        assert s.cycles_direct - direct0 == 2 - held
        text = _metrics(b)
        assert 'ipt_cycles_total{confirm="held"} %d' % s.cycles_held in text
        assert ('ipt_cycles_total{confirm="direct"} %d' % s.cycles_direct
                in text)
    finally:
        b.close()
