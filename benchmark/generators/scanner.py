"""Generator `scanner`: a GoTestWAF-shaped sweep beside ordinary clients.

A scanner such as GoTestWAF (github.com/wallarm/gotestwaf) sends its
attack set in every placement it knows, from a few source addresses, and
repeats the sweep.  Here a share `attack_fraction` of the pool (exactly
round(n * share) entries, their positions drawn from the seed) carries
one payload of the corpus generator's attack table, drawn uniformly from
its 58 payloads, from one of `scanners` source addresses.  The rest are
the corpus generator's benign requests, each from one of `clients`
addresses.  The source travels in `X-Real-IP`, which the node's
postanalytics keys its attacks by; no rule reads it.

Placements are the corpus generator's (query 50%, form body 30%, path
10%, User-Agent 10%) with the body share split between a form body and a
JSON body (15% each): the shapes the plain reference models, so no
multipart, XML or compressed body.  As in the corpus generator, the
`rfi` class never goes in the User-Agent, and `protocol` and `nodejs`
only go in the query or a body.

Parameters (the traffic file's `params`):
  attack_fraction  share of pool entries that carry one payload
  scanners         source addresses the attack entries come from
  clients          source addresses the benign entries come from
"""

from __future__ import annotations

import json
import random
from typing import List

# the class of each payload decides where it may go (see corpus._attack)
from generators.corpus import _ATTACKS, _BENIGN_AGENTS, _benign
from harness.wire import Request

#: (payload, class) for every payload of the corpus's table
_PAYLOADS = [(p, cls) for cls, payloads in _ATTACKS for p in payloads]


def scanner_address(i: int) -> str:
    return "203.0.113.%d" % (10 + i)           # TEST-NET-3


def client_address(i: int) -> str:
    return "198.51.100.%d" % i                 # TEST-NET-2


def _attack(rng: random.Random, source: str) -> Request:
    payload, cls = _PAYLOADS[rng.randrange(len(_PAYLOADS))]
    slot = rng.random()
    if cls == "rfi" and slot >= 0.9:
        slot = rng.random() * 0.9
    elif cls in ("protocol", "nodejs"):
        slot = rng.random() * 0.8
    headers = {"host": "shop.example.com",
               "user-agent": rng.choice(_BENIGN_AGENTS),
               "x-real-ip": source}
    method, uri, body = "GET", "/", b""
    if slot < 0.5:
        uri = "/search?q=" + payload.replace(" ", "+")
    elif slot < 0.8:
        method = "POST"
        uri = "/api/v1/comments"
        if slot < 0.65:
            body = ("comment=" + payload).encode("utf-8", "surrogateescape")
            headers["content-type"] = "application/x-www-form-urlencoded"
        else:
            body = json.dumps({"comment": payload}).encode()
            headers["content-type"] = "application/json"
        headers["content-length"] = str(len(body))
    elif slot < 0.9:
        uri = "/files/" + payload
    else:
        headers["user-agent"] = payload
        uri = "/index.html"
    return Request(method=method, uri=uri, headers=headers, body=body)


def generate(seed: int, n: int, params: dict) -> List[Request]:
    rng = random.Random(seed)
    n_attack = round(n * float(params["attack_fraction"]))
    attack_at = set(rng.sample(range(n), n_attack))
    scanners = int(params["scanners"])
    clients = int(params["clients"])
    out = []
    for i in range(n):
        if i in attack_at:
            req = _attack(rng, scanner_address(rng.randrange(scanners)))
        else:
            req = _benign(rng)
            req.headers["x-real-ip"] = client_address(
                rng.randrange(clients))
        out.append(req)
    return out
