"""Streaming body scan (benchmark config #5): incremental-normalizer
equivalence, chunk-boundary factor matching via carried NFA state,
batcher streaming API, and one-shot↔streaming verdict parity."""

import numpy as np
import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.batcher import Batcher
from ingress_plus_tpu.serve.normalize import Request, variant_chain
from ingress_plus_tpu.serve.stream import IncrementalVariant, StreamEngine

RULES = """
SecRule REQUEST_URI|ARGS|REQUEST_BODY "@rx (?i)union\\s+select" \
    "id:942100,phase:2,block,t:urlDecodeUni,severity:CRITICAL,tag:'attack-sqli'"
SecRule REQUEST_BODY "@rx (?i)<script" \
    "id:941100,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|REQUEST_BODY "@rx /etc/passwd" \
    "id:930120,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
"""


@pytest.fixture(scope="module")
def pipeline():
    return DetectionPipeline(compile_ruleset(parse_seclang(RULES)),
                             mode="block")


# ------------------------------------------------- incremental decoders

@pytest.mark.parametrize("variant", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("payload", [
    b"hello%20world%u0041&lt;script&gt;alert(1)",
    b"a=1%2",                      # trailing incomplete escape
    b"x&#x3C;script&#62;y&amp",    # entities, one unterminated
    b"%75nion%20%73elect a from b",
    b"plain ascii only",
    b"&#none;&bogus;%zz%",         # junk escapes must pass through
])
def test_incremental_variant_equivalence(variant, payload):
    # every split point must reproduce the one-shot normalization
    for cut in range(len(payload) + 1):
        inc = IncrementalVariant(variant)
        got = inc.feed(payload[:cut]) + inc.feed(payload[cut:]) + inc.flush()
        assert got == variant_chain(payload, variant), \
            (variant, cut, payload)


def test_incremental_variant_many_chunks():
    payload = (b"a%3Cscript%3E" * 50) + b"&lt;" * 30 + b"%u0041%4"
    for variant in range(5):
        inc = IncrementalVariant(variant)
        got = b"".join(inc.feed(payload[i : i + 7])
                       for i in range(0, len(payload), 7)) + inc.flush()
        assert got == variant_chain(payload, variant)


# ------------------------------------------- engine chunk-boundary scan

def test_stream_engine_boundary_spanning_match(pipeline):
    eng = StreamEngine(pipeline)
    st = eng.begin(Request(uri="/upload", request_id="s1"))
    st.base_hits = np.zeros((pipeline.ruleset.n_rules,), bool)
    # split "union select" across three chunks mid-factor
    eng.scan(st.feed(b"x=1 unio"))
    eng.scan(st.feed(b"n sel"))
    eng.scan(st.feed(b"ect secret from t"))
    eng.scan(st.flush())
    v = eng.finish(st)
    assert v.attack and 942100 in v.rule_ids


def test_stream_engine_split_urlencoded_payload(pipeline):
    eng = StreamEngine(pipeline)
    st = eng.begin(Request(uri="/u", request_id="s2"))
    st.base_hits = np.zeros((pipeline.ruleset.n_rules,), bool)
    # %3Cscript%3E split INSIDE an escape: decoded variant must still hit
    whole = b"a=%3Cscri%70t%3E alert"
    eng.scan(st.feed(whole[:6]))   # "a=%3Cs" — cuts nothing
    eng.scan(st.feed(whole[6:11]))  # cuts inside %70
    eng.scan(st.feed(whole[11:]))
    eng.scan(st.flush())
    v = eng.finish(st)
    assert v.attack and 941100 in v.rule_ids


def test_stream_engine_clean_body_no_hits(pipeline):
    eng = StreamEngine(pipeline)
    st = eng.begin(Request(uri="/ok", request_id="s3"))
    st.base_hits = np.zeros((pipeline.ruleset.n_rules,), bool)
    for chunk in (b"perfectly ", b"normal ", b"form data " * 100):
        eng.scan(st.feed(chunk))
    eng.scan(st.flush())
    v = eng.finish(st)
    assert not v.attack and not v.rule_ids


def test_stream_engine_uri_hits_merge_with_body(pipeline):
    # attack in URI (base prefilter), clean body: verdict must carry it
    eng = StreamEngine(pipeline)
    req = Request(uri="/dl?f=/etc/passwd", request_id="s4")
    st = eng.begin(req)
    st.base_hits = pipeline.prefilter([req])[0]
    eng.scan(st.feed(b"clean body"))
    eng.scan(st.flush())
    v = eng.finish(st)
    assert v.attack and 930120 in v.rule_ids


# ------------------------------------------------------- batcher path

@pytest.fixture()
def batcher(pipeline):
    b = Batcher(pipeline, max_batch=32, max_delay_s=0.001)
    yield b
    b.close()


def test_batcher_stream_roundtrip(batcher):
    h = batcher.begin_stream(Request(uri="/post", request_id="b1"))
    batcher.feed_chunk(h, b"1 uni")
    batcher.feed_chunk(h, b"on se")
    batcher.feed_chunk(h, b"lect 2")
    v = batcher.finish_stream(h).result(timeout=60)
    assert v.attack and v.blocked and 942100 in v.rule_ids
    assert batcher.stats.streams == 1
    assert batcher.stats.stream_chunks == 3


def test_batcher_stream_interleaved_with_requests(batcher):
    h = batcher.begin_stream(Request(uri="/post", request_id="b2"))
    batcher.feed_chunk(h, b"nothing here ")
    fut_req = batcher.submit(Request(uri="/q?a=1+union+select+2",
                                     request_id="b3"))
    batcher.feed_chunk(h, b"still clean")
    v_stream = batcher.finish_stream(h).result(timeout=60)
    v_req = fut_req.result(timeout=60)
    assert not v_stream.attack
    assert v_req.attack


def test_batcher_stream_parity_with_oneshot(batcher, pipeline):
    """Streaming a body in arbitrary chunks == sending it whole."""
    body = (b"user=bob&bio=" + b"x" * 300
            + b" 1' union select tok from s --" + b"y" * 200)
    whole = pipeline.detect(
        [Request(uri="/form", body=body, request_id="w")])[0]
    h = batcher.begin_stream(Request(uri="/form", request_id="c"))
    for i in range(0, len(body), 37):
        batcher.feed_chunk(h, body[i : i + 37])
    chunked = batcher.finish_stream(h).result(timeout=60)
    assert chunked.attack == whole.attack
    assert set(chunked.rule_ids) == set(whole.rule_ids)
    assert chunked.score == whole.score


def test_stream_scan_cap_flags_fail_open(pipeline):
    """Bytes past scan_cap pass unscanned but the verdict is flagged
    (pass-and-flag, never a silent miss)."""
    eng = StreamEngine(pipeline)
    st = eng.begin(Request(uri="/big", request_id="cap1"))
    st.base_hits = np.zeros((pipeline.ruleset.n_rules,), bool)
    st.scan_cap = 64
    eng.scan(st.feed(b"A" * 64))
    eng.scan(st.feed(b"1 union select 2"))  # beyond the scan bound
    eng.scan(st.flush())
    v = eng.finish(st)
    assert not v.attack
    assert v.fail_open  # truncation surfaced
    assert st.truncated


def test_stream_scan_dedup_shares_rows(pipeline):
    """Plain-ASCII increments are identical across variants → the scan
    groups them into one device row (and stays correct)."""
    eng = StreamEngine(pipeline)
    st = eng.begin(Request(uri="/d", request_id="d1"))
    st.base_hits = np.zeros((pipeline.ruleset.n_rules,), bool)
    items = st.feed(b"plain ascii no escapes")
    # all variants produced an increment; states identical pre-scan
    eng.scan(items)
    states = {st.state[vi].tobytes() for vi in range(len(st.variants))}
    # raw/urldec/urldec_html identical; squash variants identical to each
    # other (whitespace removed) — at most 2 distinct state vectors
    assert len(states) <= 2
    eng.scan(st.feed(b" 1 union sele"))
    eng.scan(st.feed(b"ct 2 "))
    eng.scan(st.flush())
    v = eng.finish(st)
    assert v.attack and 942100 in v.rule_ids


def test_batcher_stream_abort_resolves_nothing(batcher):
    h = batcher.begin_stream(Request(uri="/gone", request_id="b4"))
    batcher.feed_chunk(h, b"data")
    batcher.abort_stream(h)
    # no finish — state must simply be skipped without error
    fut = batcher.submit(Request(uri="/after", request_id="b5"))
    assert not fut.result(timeout=60).attack


def test_oversized_body_auto_routed_to_stream(batcher):
    """A 1MB padded-prefix attack body submitted on the NON-streaming API
    must be caught (no silent 16KB truncation): Batcher.submit reroutes
    it through the StreamEngine."""
    body = b"A" * (1 << 20) + b" 1' union select password from users --"
    v = batcher.submit(Request(method="POST", uri="/upload", body=body,
                               request_id="big")).result(timeout=120)
    assert v.attack and v.blocked and 942100 in v.rule_ids
    assert batcher.stats.oversized_rerouted == 1


def test_small_gzip_bomb_pad_auto_routed(batcher):
    """A <16KB gzip body inflating to ~1MB with the attack at the end —
    the zip-pad evasion — must also reroute and be caught."""
    import gzip

    raw = b"B" * (1 << 20) + b" 1' union select password from users --"
    comp = gzip.compress(raw)
    assert len(comp) < 16384
    v = batcher.submit(Request(method="POST", uri="/upload", body=comp,
                               headers={"Content-Encoding": "gzip"},
                               request_id="zip")).result(timeout=120)
    assert v.attack and v.blocked and 942100 in v.rule_ids
    assert batcher.stats.oversized_rerouted == 1


@pytest.mark.parametrize("frame,frames", [(1 << 10, 10), (20 << 10, 3),
                                          (70 << 10, 2)])
def test_wire_stream_frames_of_any_size_one_verdict(batcher, frame, frames):
    """Chunk frames of 1 KB, 20 KB and 70 KB: the wave's width follows
    the bytes a step has pending, so the small frames launch 2,048-wide
    waves only (as before the wide wave existed) and the large ones
    also 16,384-wide ones.  Each payload is the only one of its rule:
    one split over the last frame boundary, and in the large frames one
    over byte 16,384 of the first frame (a wide/narrow boundary at
    20 KB, wide/wide at 70 KB) and one over byte 32,768 (wide/wide)."""
    import time

    from ingress_plus_tpu.serve.stream import CHUNK_L, WIDE_L

    eng = batcher.stream_engine
    body = bytearray(b"lorem ipsum dolor " * (frame * frames // 18 + 1)
                     )[:frame * frames]
    plants = [(frame * (frames - 1), b" 1' union select tok from s ", 942100)]
    plants += [(at, payload, rule_id) for at, payload, rule_id in (
        (WIDE_L, b" <script>alert(1) ", 941100),
        (2 * WIDE_L, b" /etc/passwd ", 930120)) if at + len(payload) < frame]
    for at, payload, _rule_id in plants:
        body[at - 6:at - 6 + len(payload)] = payload
    w0, s0 = eng.waves, eng.wave_steps
    h = batcher.begin_stream(Request(uri="/form", request_id="w%d" % frame))
    for i in range(frames):
        batcher.feed_chunk(h, bytes(body[i * frame:(i + 1) * frame]))
        # one frame a dispatch step, as frames arrive off a socket
        deadline = time.monotonic() + 60
        while h.chunks <= i and time.monotonic() < deadline:
            time.sleep(0.002)
        assert h.chunks == i + 1
    v = batcher.finish_stream(h).result(timeout=120)
    assert v.attack and v.blocked and not v.fail_open
    assert sorted(v.rule_ids) == sorted(r for _at, _p, r in plants)
    waves, steps = eng.waves - w0, eng.wave_steps - s0
    assert steps >= len(body)
    wide = (steps - waves * CHUNK_L) // (WIDE_L - CHUNK_L)
    assert steps == wide * WIDE_L + (waves - wide) * CHUNK_L
    assert wide == (frame // WIDE_L) * frames
