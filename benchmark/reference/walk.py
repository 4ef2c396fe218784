#!/usr/bin/env python3
"""The reference's verdicts for a cell's request pool.

    python benchmark/reference/walk.py --traffic F --seed S --pool N \
        --rules-dir D [--sigpack P] --slice I/K --out O \
        [--control '{"kind": ...}']

Builds the pool from the seed with the benchmark's own generator, decodes
each frame, and walks every (request, rule) pair with the plain walker
(`reference/plainwaf.py`), which shares no code with the program: it
reads the deployment's rule text under `benchmark/rules/` and owns its
semantics.  Nothing here imports JAX or the package under test.

`--control` walks a deliberately weakened deployment instead (see
`CONTROLS`): what a later PR might be tempted to serve.  Its verdicts
must differ from the reference's, or the comparison proves nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from reference import plainwaf                                # noqa: E402


def load_generator(name: str):
    spec = importlib.util.spec_from_file_location(
        "generators." + name, BENCH / "generators" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_pool(traffic: dict, seed: int, n: int) -> list:
    """The cell's request pool as wire frames; req_id = pool index."""
    from harness.wire import encode_request

    gen = load_generator(traffic["generator"])
    reqs = gen.generate(seed, n, traffic["params"])
    if len(reqs) != n:
        raise SystemExit("generator made %d requests, not %d" % (len(reqs), n))
    return [encode_request(r, req_id=i) for i, r in enumerate(reqs)]


def scanned_bytes(request: plainwaf.HttpRequest) -> int:
    """The size the server's admission compares with its side-lane
    threshold: the unpacked body, and for a form body its url-decoded
    copy beside it."""
    var = plainwaf.Variables(request)
    size = len(var.unpacked_body())
    if b"urlencoded" in var.ctype:
        decoded = plainwaf.url_decode_uni(request.body)
        if decoded != request.body:
            size += 1 + len(decoded)
    return size


#: the controls: one stated guarantee broken each
#:   paranoia    the served paranoia level; a thinner pack is served
#:   value_head  every value is examined whole; only its first `bytes` are
CONTROLS = ("paranoia", "value_head")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pool", type=int, required=True)
    ap.add_argument("--rules-dir", required=True)
    ap.add_argument("--sigpack", default="")
    ap.add_argument("--slice", default="0/1")
    ap.add_argument("--control", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    t0 = time.monotonic()
    control = json.loads(args.control) if args.control else None
    if control and control["kind"] not in CONTROLS:
        raise SystemExit("unknown control %r" % control["kind"])
    paranoia = head = None
    if control and control["kind"] == "paranoia":
        paranoia = int(control["level"])
    if control and control["kind"] == "value_head":
        head = int(control["bytes"])
    dep = plainwaf.Deployment(Path(args.rules_dir),
                              Path(args.sigpack) if args.sigpack else None,
                              paranoia=paranoia)
    traffic = json.loads(Path(args.traffic).read_text())
    frames = build_pool(traffic, args.seed, args.pool)
    i, k = (int(x) for x in args.slice.split("/"))
    t_ready = time.monotonic()
    # a mix that has to stay on the batched path states the size its
    # bodies may reach once unpacked (body plus extracted segments)
    cap = traffic.get("max_unpacked_bytes")
    out = {}
    for idx in range(i, len(frames), k):
        req_id, request = plainwaf.decode_frame(frames[idx])
        if req_id != idx:
            raise SystemExit("frame %d carries request id %d" % (idx, req_id))
        if cap and request.body and scanned_bytes(request) > cap:
            raise SystemExit(
                "pool entry %d unpacks to more than %d bytes: it would "
                "leave the batched path" % (idx, cap))
        attack, blocked, ids = plainwaf.verdict(dep, request, value_head=head)
        out[str(idx)] = [attack, blocked, sorted(ids)]
    Path(args.out).write_text(json.dumps(out))
    print("reference: %d of %d pool entries walked (%s, %d rules of %d at "
          "paranoia level %d, control=%s): %.1fs to load, %.1fs to walk"
          % (len(out), len(frames), args.slice, len(dep.served),
             len(dep.rules), dep.paranoia, args.control or "none",
             t_ready - t0, time.monotonic() - t_ready), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
