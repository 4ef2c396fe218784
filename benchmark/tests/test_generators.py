"""Every seed offers the same work in another order, and the same seed the
same bytes."""

import json
from pathlib import Path

import pytest

from harness.wire import decode_response, encode_request, with_req_id
from reference.walk import build_pool

BENCH = Path(__file__).resolve().parent.parent
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def traffic(name):
    return json.loads((BENCH / "traffic" / (name + ".json")).read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_frames(mix):
    t = traffic(mix)
    assert build_pool(t, 2**31 + 11, 64) == build_pool(t, 2**31 + 11, 64)
    assert build_pool(t, 1, 64) != build_pool(t, 2, 64)


def test_body_post_sizes_are_one_set_in_another_order():
    from generators import body_post

    p = traffic("body-post")["params"]
    a = body_post.generate(5, 256, p)
    b = body_post.generate(6, 256, p)
    assert sorted(len(r.body) for r in a) == sorted(len(r.body) for r in b)
    assert [len(r.body) for r in a] != [len(r.body) for r in b]
    assert min(len(r.body) for r in a) >= p["min_body"]
    assert max(len(r.body) for r in a) <= p["max_body"]
    assert all(r.method == "POST" for r in a)


@pytest.mark.parametrize("mix", MIXES)
def test_attack_share_is_exact(mix):
    from urllib.parse import quote_plus

    from generators.corpus import attack_payloads
    from reference.walk import load_generator

    t = traffic(mix)
    gen = load_generator(t["generator"])
    marks = set()
    for p in attack_payloads():
        marks |= {p, p.replace(" ", "+"), quote_plus(p), json.dumps(p)[1:-1]}
    marks = [m.encode() for m in marks]
    n = 500
    for seed in (3, 4):
        reqs = gen.generate(seed, n, t["params"])
        blobs = [r.uri.encode() + b"\n" + r.body + b"\n"
                 + "\n".join(r.headers.values()).encode() for r in reqs]
        hit = sum(1 for b in blobs if any(m in b for m in marks))
        assert hit == round(n * t["params"]["attack_fraction"])


def test_wire_round_trip():
    from harness.wire import Request

    frame = encode_request(Request("POST", "/a?b=c", {"host": "h"}, b"xyz"), 7)
    assert frame[:4] == b"QTPI"
    again = with_req_id(frame, 2**40 + 5)
    assert len(again) == len(frame) and again[8:16] != frame[8:16]
    assert again[16:] == frame[16:]
    import struct

    payload = struct.pack("<QBIBH", 9, 1 | 2, 15, 1, 2) + b"\x03" + struct.pack(
        "<QQ", 942100, 941100)
    v = decode_response(payload)
    assert (v["req_id"], v["attack"], v["blocked"], v["fail_open"]) == (
        9, True, True, False)
    assert v["rule_ids"] == [942100, 941100]
