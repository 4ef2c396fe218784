#!/usr/bin/env python3
"""Cut a recorded `.xplane.pb` down to a fixture `harness/hostspans.py`
can be tested on: what `trim_xplane.py` keeps of the device planes, and of
the host planes the lines that hold `ipt:` events, those events alone,
over the same stretch.

    python benchmark/tests/trim_hostspans.py <in.xplane.pb> <out.xplane.pb> [seconds]

Walks the wire format with `trim_xplane.py`'s helpers (its docstring has
the field numbers); event stats (`cycle`, `n`) are dropped with the rest.
"""

import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from trim_xplane import emit, fields, get, trim               # noqa: E402

DEVICE = re.compile(r"^/device:TPU:\d+$")


def device_start_ps(data: bytes) -> int:
    """Absolute start of the first device event (what `trim` cuts from)."""
    starts = []
    for num, _wt, plane in fields(data):
        if num != 1:
            continue
        p = fields(plane)
        if not DEVICE.match(get(p, 2, b"").decode()):
            continue
        for n, _w, v in p:
            if n != 3:
                continue
            ln = fields(v)
            if get(ln, 2, b"").decode() not in ("XLA Ops", "XLA Modules"):
                continue
            starts += [get(ln, 3, 0) * 1000 + get(fields(ev), 2, 0)
                       for k, _w2, ev in ln if k == 4]
    return min(starts)


def host_planes(data: bytes, first_ps: int, horizon_ps: int) -> bytes:
    out = []
    for num, _wt, plane in fields(data):
        if num != 1:
            continue
        p = fields(plane)
        if DEVICE.match(get(p, 2, b"").decode()):
            continue
        names = {}
        for n, _w, v in p:
            if n == 4:
                entry = fields(v)
                names[get(entry, 1)] = get(
                    fields(get(entry, 2)), 2, b"").decode(errors="replace")
        span_ids = {i for i, name in names.items() if name.startswith("ipt:")}
        kept_lines, used = [], set()
        for n, _w, v in p:
            if n != 3:
                continue
            ln = fields(v)
            base = get(ln, 3, 0) * 1000
            kept = []
            for k, w, ev_raw in ln:
                if k == 4:
                    ev = fields(ev_raw)
                    start = base + get(ev, 2, 0)
                    if (get(ev, 1) not in span_ids or start < first_ps
                            or start + get(ev, 3, 0) > horizon_ps):
                        continue
                    used.add(get(ev, 1))
                    ev_raw = emit([f for f in ev if f[0] in (1, 2, 3)])
                kept.append((k, w, ev_raw))
            if any(k == 4 for k, _w2, _v2 in kept):
                kept_lines.append((3, 2, emit(kept)))
        if not kept_lines:
            continue
        meta = [(4, 2, emit([(1, 0, i), (2, 2, emit(
            [(1, 0, i), (2, 2, names[i].encode())]))])) for i in sorted(used)]
        head = [(n, w, v) for n, w, v in p if n in (1, 2)]
        out.append((1, 2, emit(head + kept_lines + meta)))
    return emit(out)


if __name__ == "__main__":
    src, dst = sys.argv[1], sys.argv[2]
    secs = float(sys.argv[3]) if len(sys.argv) > 3 else 0.25
    data = open(src, "rb").read()
    first = device_start_ps(data)
    out = trim(data, secs) + host_planes(data, first, first + int(secs * 1e12))
    open(dst, "wb").write(out)
    print("%s: %d bytes -> %s: %d bytes" % (src, len(data), dst, len(out)))
