"""Generator `body_post`: POSTs with KB-sized JSON or urlencoded bodies.

Every request is a POST to an API path whose body is benign word filler.
Body lengths lie on a fixed log-uniform grid between `min_body` and
`max_body` (the n quantiles of that distribution, shuffled by the seed),
so every seed offers the same set of sizes in another order.  A share
`attack_fraction` of the requests (exactly round(n * share)) carries one
payload of the corpus generator's attack table at a seeded offset in the
filler; every fourth of those has it at the very tail of the body.

Parameters (the traffic file's `params`):
  min_body, max_body  body length in bytes, both ends included
  attack_fraction     share of pool entries that carry one payload
"""

from __future__ import annotations

import json
import math
import random
from typing import List
from urllib.parse import quote_plus

from generators.corpus import _BENIGN_AGENTS, attack_payloads
from harness.wire import Request

_PATHS = ["/api/v1/comments", "/api/v1/orders", "/api/v1/articles",
          "/api/v1/tickets", "/api/v1/users/%d/notes", "/api/v1/feedback"]
_WORDS = ["alpha", "bravo", "delta", "tango", "report", "monthly",
          "invoice", "total", "window", "garden", "planet", "silver",
          "delivery", "account", "please", "update", "the", "and", "for",
          "with", "schedule", "meeting", "thanks", "regards", "customer",
          "order", "number", "address", "street", "shipping", "quality",
          "product", "works", "described", "arrived", "yesterday"]


def _sizes(n: int, lo: int, hi: int) -> List[int]:
    """The n mid-quantiles of a log-uniform law on [lo, hi]."""
    span = math.log(hi / lo)
    return [min(hi, max(lo, round(lo * math.exp(span * (i + 0.5) / n))))
            for i in range(n)]


def _filler(rng: random.Random, size: int) -> str:
    words = []
    have = 0
    while have < size:
        w = rng.choice(_WORDS)
        words.append(w)
        have += len(w) + 1
    return " ".join(words)[:size]


def _json_escape(text: str) -> str:
    return json.dumps(text)[1:-1]


def _body(rng: random.Random, size: int, payload: str, at_tail: bool):
    """A body of exactly `size` bytes; `payload` ('' = none) inside it."""
    as_json = rng.random() < 0.5
    if as_json:
        enc = _json_escape
        head, tail = '{"title": "note", "text": "', '"}'
        ctype = "application/json"
    else:
        enc = quote_plus
        head, tail = "rating=5&comment=", ""
        ctype = "application/x-www-form-urlencoded"
    mark = enc(payload)
    room = size - len(head) - len(tail) - len(mark)
    text = enc(_filler(rng, room))[:room]
    # an encoded escape cut in half would change what the server parses
    while text and (text[-1] in "%\\" or text[-2:-1] == "%"):
        text = text[:-1]
    text = text + "x" * (room - len(text))
    cut = room if at_tail else rng.randrange(0, room + 1)
    if mark and not at_tail:
        # land between two words, never inside an escape
        cut = max(text.rfind(" " if as_json else "+", 0, cut), 0)
    body = (head + text[:cut] + mark + text[cut:] + tail).encode()
    if len(body) != size:
        raise ValueError("body of %d bytes, wanted %d" % (len(body), size))
    return body, ctype


def generate(seed: int, n: int, params: dict) -> List[Request]:
    rng = random.Random(seed)
    sizes = _sizes(n, int(params["min_body"]), int(params["max_body"]))
    rng.shuffle(sizes)
    payloads = attack_payloads()
    n_attack = round(n * float(params["attack_fraction"]))
    attack_at = rng.sample(range(n), n_attack)
    tail_at = set(attack_at[::4])
    attack_at = set(attack_at)
    out = []
    for i, size in enumerate(sizes):
        path = rng.choice(_PATHS)
        if "%d" in path:
            path = path % rng.randrange(1, 99999)
        payload = rng.choice(payloads) if i in attack_at else ""
        body, ctype = _body(rng, size, payload, i in tail_at)
        headers = {"host": "shop.example.com",
                   "user-agent": rng.choice(_BENIGN_AGENTS),
                   "accept": "*/*",
                   "content-length": str(len(body)),
                   "content-type": ctype}
        if rng.random() < 0.3:
            headers["cookie"] = "session=%032x" % rng.getrandbits(128)
        out.append(Request(method="POST", uri=path, headers=headers,
                           body=body))
    return out
