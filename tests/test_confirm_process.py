"""Walker processes of the confirm plane (models/confirm_walker.py,
docs/CONFIRM_PLANE.md; ISSUE 30).

With more than one confirm worker every share of a dispatch is walked
in a process of its own.  Held here: the process pool's verdicts are the
inline walk's, verdict for verdict and result for result (the ctl
paths included); a walker never imports jax; a dead or hung walker
costs its share and nothing else, and is killed, replaced and counted;
a hot swap is served from the walkers while a share pinned to the old
generation is still walked against the old rules; no walker outlives
its pool or its server; a batch of one never crosses a pipe; `auto`
stays inline on a narrow host.  No wall time is asserted anywhere.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models import confirm_plane
from ingress_plus_tpu.models.confirm_plane import (
    ConfirmPool,
    WalkerDied,
    auto_workers,
    join_confirm,
    launch_confirm,
)
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils.trace import flight

RULES = """
SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" "id:942100,phase:2,block,t:urlDecodeUni,t:lowercase,severity:CRITICAL,tag:'attack-sqli'"
SecRule ARGS|REQUEST_BODY "@rx (?i)<script[^>]*>" "id:941100,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|ARGS "@rx /etc/(?:passwd|shadow)" "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
SecRule ARGS "@pm sleep( benchmark( xp_cmdshell" "id:942150,phase:2,block,severity:ERROR,tag:'attack-sqli'"
SecRule REQUEST_URI "@beginsWith /internal/" \\
    "id:10001,phase:1,pass,nolog,ctl:ruleRemoveById=942100"
SecRule REQUEST_URI "@beginsWith /profile" \\
    "id:10002,phase:1,pass,nolog,ctl:ruleRemoveTargetById=942100;ARGS:bio"
SecRule REQUEST_URI "@streq /healthz" \\
    "id:10003,phase:1,pass,nolog,ctl:ruleEngine=Off"
SecRule REQUEST_URI "@beginsWith /audit" \\
    "id:10004,phase:1,pass,nolog,ctl:ruleEngine=DetectionOnly"
"""

#: the same pack less its SQLi rule: a generation whose verdicts differ
RULES_B = "\n".join(ln for ln in RULES.splitlines() if "id:942100" not in ln)

SQLI = "1%27%20UNION%20SELECT%20x%20FROM%20t"
CTL_URIS = {
    "ruleRemoveById": "/internal/p?q=" + SQLI,
    "ruleRemoveTargetById": "/profile?bio=union select creds&q=" + SQLI,
    "ruleEngine=DetectionOnly": "/audit?q=" + SQLI,
    "ruleEngine=Off": "/healthz",
}


def _mixed(n=24, tag="m"):
    """Attacks, benign requests and every ctl path, interleaved."""
    uris = (["/p?q=" + SQLI, "/x?v=<script>alert(1)</script>",
             "/index.html?page=7", "/d?f=/etc/passwd"]
            + list(CTL_URIS.values()))
    return [Request(uri=uris[i % len(uris)] + ("&i=%d" % i if "?" in
                                               uris[i % len(uris)] else ""),
                    headers={}, body=b"", request_id="%s%d" % (tag, i))
            for i in range(n)]


def _vt(v):
    return (v.attack, v.blocked, tuple(v.rule_ids), v.score,
            tuple(v.classes), v.fail_open, v.degraded,
            tuple((m["rule_id"], m["var"], m["value"]) for m in v.matches))


def _rt(res):
    """A ConfirmResult as the fold reads it (the cost samples' times
    left out: they are clocks)."""
    return (res.confirmed, res.points,
            None if res.excluded is None
            else tuple(np.flatnonzero(res.excluded)),
            res.detection_only, res.rule_idx)


def _confirm(pl, reqs):
    rh = pl.mask_hits(reqs, pl.prefilter(reqs))
    job = launch_confirm(pl, reqs, rh)
    return job, join_confirm(pl, job)


def _gone(pid: int) -> bool:
    """No such process, or one that has exited and waits for its
    parent's wait()."""
    try:
        with open("/proc/%d/stat" % pid) as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except (FileNotFoundError, ProcessLookupError):
        return True


def _wait_for(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


@pytest.fixture(scope="module")
def small():
    return compile_ruleset(parse_seclang(RULES))


@pytest.fixture(scope="module")
def pair(small):
    """(inline, pooled): one pack, the serial walk beside three walker
    processes.  The constructor returns with the walkers holding it."""
    inline = DetectionPipeline(small, mode="block")
    pooled = DetectionPipeline(small, mode="block", confirm_workers=3)
    yield inline, pooled
    pooled.confirm_pool.close()


# ------------------------------------------------------------- parity

def test_corpus_parity_with_the_inline_walk_at_20_percent_attacks():
    """The served pack over `generate_corpus`: every verdict of the
    process pool is the inline walk's."""
    from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
    from ingress_plus_tpu.utils.corpus import generate_corpus

    cr = compile_ruleset(load_bundled_rules())
    reqs = [lr.request for lr in generate_corpus(
        n=96, attack_fraction=0.2, seed=30)]
    inline = DetectionPipeline(cr, mode="block")
    pooled = DetectionPipeline(cr, mode="block", confirm_workers=4)
    try:
        want, got = [], []
        for i in range(0, len(reqs), 16):
            want += [_vt(v) for v in inline.detect(reqs[i:i + 16])]
            got += [_vt(v) for v in pooled.detect(reqs[i:i + 16])]
        assert got == want
        assert sum(w[0] for w in want) >= 10      # attacks were found
        assert not all(w[0] for w in want)
        pool = pooled.confirm_pool
        assert pool.requests_process == len(reqs)
        assert pool.requests_inline == 0
        # nothing /rules/health renders went dark: the walkers'
        # quick-reject counts came home, and they are the inline walk's
        # (both walked every batch with a fresh memo over unique
        # requests, so the evaluations are the same)
        assert (pooled.rule_stats.quick_reject_summary()
                == inline.rule_stats.quick_reject_summary())
        assert pooled.rule_stats.quick_reject_summary()["skips"] > 0
    finally:
        pooled.confirm_pool.close()


@pytest.mark.parametrize("ctl", sorted(CTL_URIS))
def test_ctl_requests_walk_alike(pair, ctl):
    """A request that trips the ctl, among others: confirmed order,
    points, `excluded`, `detection_only` and the sampled rules are the
    inline walk's, request for request."""
    inline, pooled = pair
    reqs = _mixed(12, tag=ctl[:6])
    reqs.insert(5, Request(uri=CTL_URIS[ctl], headers={}, body=b"",
                           request_id="ctl-" + ctl))
    _j, want = _confirm(inline, reqs)
    job, got = _confirm(pooled, reqs)
    assert job.share_workers == [0, 1, 2]
    assert [_rt(r) for r in got] == [_rt(r) for r in want]
    tripped = want[5]
    if ctl == "ruleEngine=DetectionOnly":
        assert tripped.detection_only and tripped.confirmed
    elif ctl == "ruleEngine=Off":
        assert tripped.excluded is not None and tripped.excluded.all()
    elif ctl == "ruleRemoveById":
        assert tripped.excluded is not None and not tripped.confirmed
    else:
        assert tripped.excluded is None and tripped.confirmed
    assert ([_vt(v) for v in pooled.detect(reqs)]
            == [_vt(v) for v in inline.detect(reqs)])


def test_verdicts_carry_the_worker_that_walked_them(pair):
    inline, pooled = pair
    reqs = _mixed(14)
    stamps = [v.confirm_worker for v in pooled.detect(reqs)]
    # ceil(14 / 3) = 5 a share, three shares, request i to share i % 3
    assert stamps == [i % 3 for i in range(14)]
    assert {v.confirm_worker for v in inline.detect(reqs)} == {0}


def test_flood_memo_and_verdict_cache_live_in_the_walkers(small):
    """The per-cycle memo is per walker per share, and under
    `--confirm-cache` each walker keeps a cache of its own: the hit
    counts come back with the results, the verdicts do not move."""
    flood = [Request(uri="/flood?q=1 union select pw from users",
                     headers={}, body=b"", request_id="f%d" % i)
             for i in range(24)]
    inline = DetectionPipeline(small, mode="block")
    want = [_vt(v) for v in inline.detect(flood)]
    for kw in ({}, {"confirm_cache_entries": 256}):
        p = DetectionPipeline(small, mode="block", confirm_workers=2, **kw)
        try:
            assert [_vt(v) for v in p.detect(flood)] == want
            first = p.stats.confirm_memo_hits
            assert first > 0
            assert [_vt(v) for v in p.detect(flood)] == want
            second = p.stats.confirm_memo_hits - first
            # a cache outlives the cycle: its second pass hits at once
            assert second > first if kw else second == first
        finally:
            p.confirm_pool.close()


# -------------------------------------------------- the walker process

def test_a_walker_imports_no_jax(pair):
    _inline, pooled = pair
    for i in range(3):
        w = pooled.confirm_pool._workers[i]
        modules = w.ask(("modules",), 20.0)
        assert "ingress_plus_tpu.models.confirm_walker" in modules \
            or "__main__" in modules
        bad = [m for m in modules
               if m.split(".")[0] in ("jax", "jaxlib", "libtpu")]
        assert bad == []


def test_an_unknown_generation_is_an_error_not_a_walk(pair):
    _inline, pooled = pair
    w = pooled.confirm_pool._workers[0]
    with pytest.raises(RuntimeError, match="not installed"):
        w.ask(("walk", 10 ** 9, [({"uri": b"/"}, 0, b"")], 0, 0), 20.0)
    # and the walker still serves the generation it holds
    assert [_vt(v) for v in pooled.detect(_mixed(6))] \
        == [_vt(v) for v in _inline.detect(_mixed(6))]


def test_a_batch_of_one_never_crosses_a_pipe(pair, monkeypatch):
    inline, pooled = pair
    pool = pooled.confirm_pool

    def _no_post(self, *a, **kw):
        raise AssertionError("a batch of one went to a walker")

    monkeypatch.setattr(confirm_plane._ConfirmWorker, "post", _no_post)
    before = (pool.requests_inline, pool.requests_process)
    req = Request(uri="/p?q=" + SQLI, headers={}, body=b"",
                  request_id="one")
    (v,) = pooled.detect([req])
    assert _vt(v) == _vt(inline.detect([req])[0]) and v.attack
    assert v.confirm_worker == 0
    assert (pool.requests_inline, pool.requests_process) \
        == (before[0] + 1, before[1])


@pytest.mark.parametrize("n,workers,shares", [
    (1, 8, 0), (2, 8, 2), (5, 8, 5), (9, 8, 5), (16, 8, 8), (17, 8, 6),
    (16, 4, 4), (3, 2, 2), (64, 1, 0)])
def test_shares_are_dealt_no_wider_than_they_must(n, workers, shares):
    """ceil(n / N) requests a share, and only as many workers as that
    takes; 0 = the caller walks inline."""
    pool = ConfirmPool(n_workers=1)

    class _W:
        def __init__(self, i):
            self.worker_index, self.held, self.failed_at = i, {7: 0}, None
            self.installing = set()

    class _P:
        confirm_gen = 7

    if workers > 1:
        pool._workers = [_W(i) for i in range(workers)]
    dealt = pool.deal(_P, n)
    assert len(dealt) == shares
    if shares:
        per = -(-n // workers)
        assert max(len(range(s, n, shares)) for s in range(shares)) == per


@pytest.mark.parametrize("n,workers,holding,dealt", [
    (1, 8, 8, [7]), (1, 8, 1, [0]), (1, 8, 0, []), (1, 2, 2, [1]),
    (1, 1, 0, []), (0, 8, 8, []), (5, 8, 8, [0, 1, 2, 3, 4]),
    (2, 8, 1, [])])
def test_the_side_lanes_lone_walk_needs_one_walker_holding_the_pack(
        n, workers, holding, dealt):
    """``lone_to_walker``: a batch of one goes to the last walker that
    holds the generation (none: inline, [] here; an inline pool: the
    same); a larger batch is dealt as ever."""
    pool = ConfirmPool(n_workers=1)

    class _W:
        def __init__(self, i):
            self.worker_index, self.failed_at = i, None
            self.held = {7: 0} if i < holding else {}
            self.installing = set()

    class _P:
        confirm_gen = 7

    if workers > 1:
        pool._workers = [_W(i) for i in range(workers)]
    # the installs a stub cannot take are left out: ready = holding
    pool._post_installs = lambda pl: (
        [w for w in pool._workers if 7 in w.held], [])
    got = pool.deal(_P, n, lone_to_walker=True)
    assert [w.worker_index for w in got] == dealt
    if n == 1 and holding:
        assert pool.deal(_P, 1) == []      # what every other caller gets


def test_lone_walks_and_batched_shares_deal_from_two_threads_at_once(pair):
    """As in the server, where the dispatch thread deals batched shares
    while the side lane's finish thread deals its lone walks to the same
    walkers: three threads of each, a 10 us switch interval.  Every
    result is the inline walk's and every request is counted once,
    under ``process``."""
    inline, pooled = pair
    pool = pooled.confirm_pool
    batch, lones = _mixed(6, tag="s"), _mixed(8, tag="l")
    rh_b = pooled.mask_hits(batch, pooled.prefilter(batch))
    rh_l = pooled.mask_hits(lones, pooled.prefilter(lones))
    want_b = [_rt(r) for r in join_confirm(
        inline, launch_confirm(inline, batch, rh_b))]
    want_l = [_rt(r) for r in join_confirm(
        inline, launch_confirm(inline, lones, rh_l))]
    rounds, errors = 20, []

    def _batched():
        for _ in range(rounds):
            got = join_confirm(pooled, launch_confirm(pooled, batch, rh_b))
            if [_rt(r) for r in got] != want_b:
                errors.append("batched")

    def _lone():
        for k in range(rounds):
            i = k % len(lones)
            (got,) = join_confirm(pooled, launch_confirm(
                pooled, lones[i:i + 1], rh_l[i:i + 1], lone_to_walker=True))
            if _rt(got) != want_l[i]:
                errors.append("lone %d" % i)

    before = (pool.requests_inline, pool.requests_process)
    threads = [threading.Thread(target=f) for f in (_batched, _lone) * 3]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert (pool.requests_inline, pool.requests_process) == (
        before[0], before[1] + 3 * rounds * (len(batch) + 1))


def test_until_two_walkers_hold_a_generation_the_walk_is_inline(small):
    """A generation nobody installed ahead of its traffic: the batch
    that finds it missing is walked inline (and queues the install),
    a later one goes to the walkers."""
    p = DetectionPipeline(small, mode="block", confirm_workers=2)
    inline = DetectionPipeline(small, mode="block")
    try:
        pool = p.confirm_pool
        p.WALKER_INSTALL_WAIT_S = 0.0
        p.swap_ruleset(compile_ruleset(parse_seclang(RULES)))
        reqs = _mixed(8)
        want = [_vt(v) for v in inline.detect(reqs)]
        assert [_vt(v) for v in p.detect(reqs)] == want
        assert _wait_for(lambda: all(p.confirm_gen in w.held
                                     for w in pool._workers))
        before = pool.requests_process
        assert [_vt(v) for v in p.detect(reqs)] == want
        assert pool.requests_process == before + len(reqs)
    finally:
        p.confirm_pool.close()


# ------------------------------------------------------ death and hangs

BACKTRACK = ('SecRule ARGS:z "@rx ^(a+)+$" "id:900001,phase:2,block,'
             'severity:CRITICAL,tag:\'attack-generic\'"\n')


def test_a_walker_killed_mid_share_fails_only_its_share_open():
    """Worker 1's walker is killed while it walks (a request that
    backtracks without bound keeps it busy): that share fails open, the
    sibling's verdicts are exact, the worker is replaced and counted,
    and nothing booked a hang."""
    cr = compile_ruleset(parse_seclang(RULES + BACKTRACK))
    inline = DetectionPipeline(cr, mode="block")
    p = DetectionPipeline(cr, mode="block", confirm_workers=2,
                          confirm_hang_budget_s=30.0)
    try:
        pool = p.confirm_pool
        reqs = _mixed(12)
        want = [_vt(v) for v in inline.detect(reqs)]
        assert [_vt(v) for v in p.detect(reqs)] == want
        busy = list(reqs)
        busy[3] = Request(uri="/x?z=" + "a" * 40 + "!", headers={},
                          body=b"", request_id="forever")
        victim = pool._workers[1]
        got = []
        t = threading.Thread(target=lambda: got.extend(p.detect(busy)))
        t.start()
        time.sleep(0.5)
        assert t.is_alive()            # the share is being walked
        os.kill(victim.proc.pid, signal.SIGKILL)
        t.join(30)
        assert len(got) == len(reqs)
        for i, v in enumerate(got):
            if i % 2 == 1:
                assert v.fail_open and v.confirm_worker == -1
            else:
                assert _vt(v) == want[i]      # the sibling's are exact
                assert v.confirm_worker == 0
        assert pool.workers_replaced == 1
        assert p.stats.confirm_hangs == 0     # it died, it did not hang
        assert pool._workers[1] is not victim
        assert _wait_for(lambda: _gone(victim.proc.pid))
        # the slot comes back once its fresh walker holds the rules
        assert _wait_for(lambda: p.confirm_gen in pool._workers[1].held)
        before = pool.requests_process
        assert [_vt(v) for v in p.detect(reqs)] == want
        assert pool.requests_process == before + len(reqs)
    finally:
        p.confirm_pool.close()


def test_a_hang_past_the_budget_kills_the_walker():
    """A walk that never ends (a rule that backtracks without bound):
    past the budget the share fails open and the process is killed —
    what a thread stuck in `re` never could be."""
    cr = compile_ruleset(parse_seclang(BACKTRACK))
    p = DetectionPipeline(cr, mode="block", confirm_workers=2,
                          confirm_hang_budget_s=0.5)
    try:
        pool = p.confirm_pool
        reqs = [Request(uri="/x?z=aaa", headers={}, body=b"",
                        request_id="fine"),
                Request(uri="/x?z=" + "a" * 40 + "!", headers={}, body=b"",
                        request_id="forever")]
        stuck = pool._workers[1]
        fine, forever = p.detect(reqs)
        assert fine.attack and not fine.fail_open
        assert forever.fail_open and not forever.attack
        assert p.stats.confirm_hangs == 1 and pool.workers_replaced == 1
        assert pool._workers[1] is not stuck
        assert _wait_for(lambda: _gone(stuck.proc.pid))
        assert stuck.proc.poll() is not None or _wait_for(
            lambda: stuck.proc.poll() is not None)
    finally:
        p.confirm_pool.close()


# ------------------------------------------------------------ hot swap

def test_a_hot_swap_is_served_by_the_walkers_and_old_shares_keep_their_rules(
        small):
    b = Batcher(DetectionPipeline(small, mode="block", confirm_workers=2),
                max_batch=16, max_delay_s=0.001)
    try:
        old = b.pipeline
        pool = old.confirm_pool
        reqs = _mixed(8, tag="a")
        job, want_old = _confirm(old, reqs)
        assert job.share_workers == [0, 1]
        assert any(r.confirmed for r in want_old)

        b.swap_ruleset(compile_ruleset(parse_seclang(RULES_B)))
        new = b.pipeline
        assert new is not old and new.confirm_pool is pool
        assert new.confirm_gen != old.confirm_gen
        # installed before it served a request, the old one kept
        for w in pool._workers:
            assert new.confirm_gen in w.held and old.confirm_gen in w.held

        before = pool.requests_process
        futs = [b.submit(r) for r in _mixed(8, tag="b")]
        served = [f.result(timeout=30) for f in futs]
        assert pool.requests_process > before
        assert all(v.generation == new.generation_tag for v in served)
        assert 942100 not in {rid for v in served for rid in v.rule_ids}
        assert not any(v.fail_open for v in served)

        # a cycle still pinned to the old generation: its shares go to
        # the same walkers and are walked against the OLD rules
        job, got_old = _confirm(old, reqs)
        assert job.share_workers == [0, 1]
        assert [_rt(r) for r in got_old] == [_rt(r) for r in want_old]
        ids = old.ruleset.rule_ids
        assert 942100 in {int(ids[r]) for res in got_old
                          for r in res.confirmed}
    finally:
        b.close()


# ------------------------------------------------------------ lifetime

def test_close_leaves_no_walker():
    pool = ConfirmPool(n_workers=3)
    pids = [w.proc.pid for w in pool._workers]
    assert len(set(pids)) == 3 and not any(_gone(p) for p in pids)
    pool.close()
    assert all(_gone(p) for p in pids)
    assert all(w.proc.poll() is not None for w in pool._workers)


def test_the_servers_death_leaves_no_walker():
    """SIGKILL, which nothing can handle: the walkers read end-of-file
    on their pipes and exit."""
    code = ("import sys, time\n"
            "from ingress_plus_tpu.models.confirm_plane import ConfirmPool\n"
            "pool = ConfirmPool(n_workers=2)\n"
            "print(*[w.proc.pid for w in pool._workers], flush=True)\n"
            "time.sleep(120)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in (env.get("PYTHONPATH"),) if p])
    server = subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, text=True)
    try:
        pids = [int(x) for x in server.stdout.readline().split()]
        assert len(pids) == 2 and not any(_gone(p) for p in pids)
        # let them come up: a walker still importing would exit anyway
        time.sleep(1.0)
        server.kill()
        server.wait(10)
        assert _wait_for(lambda: all(_gone(p) for p in pids))
    finally:
        server.kill()


# -------------------------------------------------------------- sizing

@pytest.mark.parametrize("cores,lanes,want", [
    (1, 1, 1), (2, 1, 1), (4, 1, 1), (5, 1, 2), (8, 1, 5), (13, 1, 8),
    (64, 1, 8), (7, 4, 1), (8, 4, 2), (30, 4, 8)])
def test_auto_leaves_room_and_stays_inline_on_a_narrow_host(
        cores, lanes, want):
    assert auto_workers(lanes, cores) == want


def test_auto_reads_the_affinity_not_the_machine(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda _pid: {0, 1, 2})
    monkeypatch.setattr(os, "cpu_count", lambda: 64)
    assert auto_workers() == 1
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda _pid: set(range(6)))
    assert auto_workers() == 3


def test_the_cli_derives_the_pool_by_default():
    import inspect

    from ingress_plus_tpu.serve import server

    assert ('add_argument("--confirm-workers", default="auto"'
            in inspect.getsource(server.main))
    assert server._parse_confirm_workers("auto") == 0
    # a pipeline built directly stays inline
    sig = inspect.signature(DetectionPipeline.__init__)
    assert sig.parameters["confirm_workers"].default == 1


# ------------------------------------------------- counters and spans

def test_the_counter_and_the_hop_span(small):
    flight.configure(enabled=True)
    b = Batcher(DetectionPipeline(small, mode="block", confirm_workers=2),
                max_batch=16, max_delay_s=0.002)
    try:
        from ingress_plus_tpu.serve.server import ServeLoop

        futs = [b.submit(r) for r in _mixed(32)]
        assert not any(f.result(timeout=30).fail_open for f in futs)
        (v,) = [b.submit(Request(uri="/p?q=" + SQLI, headers={}, body=b"",
                                 request_id="solo")).result(timeout=30)]
        assert v.attack
        pool = b.pipeline.confirm_pool
        assert pool.requests_process + pool.requests_inline == 33
        assert pool.requests_process >= 2 and pool.requests_inline >= 1
        text = ServeLoop(b, "/tmp/unused.sock")._metrics_text()
        assert ('ipt_confirm_requests_total{where="process"} %d'
                % pool.requests_process) in text
        assert ('ipt_confirm_requests_total{where="inline"} %d'
                % pool.requests_inline) in text
        assert 'ipt_stage_us_count{stage="confirm_ipc"}' in text
        # a dispatch that went to the walkers books a hop, one walked
        # inline books none
        hops = [t["sub_us"]["confirm_ipc"] for t in b.traces.snapshot()]
        assert any(h > 0 for h in hops) and any(h == 0 for h in hops)
        assert b.subhist["confirm_ipc"].total == b.hist["batch"].total
    finally:
        b.close()


def test_walker_died_is_what_a_broken_pipe_reads_as():
    pool = ConfirmPool(n_workers=2)
    try:
        w = pool._workers[0]
        os.kill(w.proc.pid, signal.SIGKILL)
        with pytest.raises(WalkerDied):
            w.ask(("modules",), 20.0)
    finally:
        pool.close()


def test_a_send_into_a_walker_that_stopped_reading_gives_up():
    """The share goes out on the dispatch thread: a walker that has
    stopped reading must not hold that thread past the budget."""
    import pickle

    pool = ConfirmPool(n_workers=2, hang_budget_s=0.5)
    w = pool._workers[0]
    try:
        assert w.ask(("modules",), 20.0)         # it is up and reading
        os.kill(w.proc.pid, signal.SIGSTOP)
        big = pickle.dumps(("modules", b"x" * (8 << 20)), 5)
        with pytest.raises(WalkerDied):
            w.post(big, w.recv_reply)
    finally:
        os.kill(w.proc.pid, signal.SIGCONT)
        pool.close()


def test_a_walker_keeps_four_generations_and_the_least_recently_dealt_goes(
        small):
    """The server keeps the books: a fifth generation's install names
    the one dealt to longest ago, a generation in use stays, and a
    dropped one that comes back is installed again."""
    pool = ConfirmPool(n_workers=2)
    inline = DetectionPipeline(small, mode="block")
    pipes = []
    try:
        for _ in range(4):
            p = DetectionPipeline(small, mode="block")
            p.confirm_pool = pool
            pool.install(p, wait_s=30.0)
            pipes.append(p)
        reqs = _mixed(6)
        want = [_vt(v) for v in inline.detect(reqs)]
        # the first is dealt to last of all: the second is now the oldest
        for p in pipes[1:] + pipes[:1]:
            assert [_vt(v) for v in p.detect(reqs)] == want
        fifth = DetectionPipeline(small, mode="block")
        fifth.confirm_pool = pool
        pool.install(fifth, wait_s=30.0)
        for w in pool._workers:
            assert set(w.held) == {p.confirm_gen for p in
                                   pipes[:1] + pipes[2:] + [fifth]}
            held = w.ask(("generations",), 20.0)
            assert set(held) == set(w.held)
        before = pool.requests_process
        assert [_vt(v) for v in pipes[0].detect(reqs)] == want
        assert pool.requests_process == before + len(reqs)
        # the dropped generation: inline now, from the walkers later
        assert [_vt(v) for v in pipes[1].detect(reqs)] == want
        assert pool.requests_process == before + len(reqs)
        assert _wait_for(lambda: all(pipes[1].confirm_gen in w.held
                                     for w in pool._workers))
        assert [_vt(v) for v in pipes[1].detect(reqs)] == want
        assert pool.requests_process == before + 2 * len(reqs)
    finally:
        pool.close()
