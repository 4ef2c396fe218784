#!/usr/bin/env python3
"""Cut a recorded `.xplane.pb` down to a fixture a test can carry.

    python benchmark/tests/trim_xplane.py <in.xplane.pb> <out.xplane.pb> [seconds]

Keeps the device planes' `XLA Ops` and `XLA Modules` lines, only the
events of the first `seconds` (default 0.25) after the first device
event, and only the event metadata those events name, with the long HLO
text of each name cut to its head.  Everything else (host planes, stats)
is dropped.  No protobuf schema is installed here, so this walks the wire
format: XSpace{1: planes}, XPlane{1: id, 2: name, 3: lines,
4: event_metadata map}, XLine{1: id, 2: name, 3: timestamp_ns, 4: events,
9: duration_ps, 10/11: display}, XEvent{1: metadata_id, 2: offset_ps,
3: duration_ps}, XEventMetadata{1: id, 2: name}.
"""

import re
import sys


def varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def put_varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def fields(buf):
    """[(field number, wire type, value)]: value is int or bytes."""
    i, out = 0, []
    while i < len(buf):
        key, i = varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(buf, i)
        elif wt == 2:
            n, i = varint(buf, i)
            v = bytes(buf[i:i + n])
            i += n
        elif wt == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError("wire type %d" % wt)
        out.append((num, wt, v))
    return out


def emit(items):
    out = bytearray()
    for num, wt, v in items:
        out += put_varint(num << 3 | wt)
        if wt == 0:
            out += put_varint(v)
        elif wt == 2:
            out += put_varint(len(v)) + v
        else:
            out += v
    return bytes(out)


def get(items, num, default=None):
    for n, _wt, v in items:
        if n == num:
            return v
    return default


def trim(data: bytes, seconds: float) -> bytes:
    planes_out = []
    for num, wt, plane in fields(data):
        if num != 1:
            continue
        p = fields(plane)
        name = get(p, 2, b"").decode()
        if not re.match(r"^/device:TPU:\d+$", name):
            continue
        lines = [fields(v) for n, _w, v in p if n == 3]
        lines = [ln for ln in lines
                 if get(ln, 2, b"").decode() in ("XLA Ops", "XLA Modules")]
        # absolute start of an event: line timestamp_ns * 1000 + offset_ps
        first = min(get(ln, 3, 0) * 1000 + get(fields(ev), 2, 0)
                    for ln in lines for n, _w, ev in ln if n == 4)
        horizon = first + int(seconds * 1e12)
        used = set()
        kept_lines = []
        for ln in lines:
            base = get(ln, 3, 0) * 1000
            kept = []
            for n, w, v in ln:
                if n == 4:
                    ev = fields(v)
                    start = base + get(ev, 2, 0)
                    if start + get(ev, 3, 0) > horizon:
                        continue
                    used.add(get(ev, 1))
                    v = emit([f for f in ev if f[0] in (1, 2, 3)])
                kept.append((n, w, v))
            kept_lines.append((3, 2, emit(kept)))
        meta = []
        for n, w, v in p:
            if n != 4:
                continue
            entry = fields(v)
            if get(entry, 1) not in used:
                continue
            m = [(k, t, (x[:96] if k == 2 else x))
                 for k, t, x in fields(get(entry, 2)) if k in (1, 2)]
            meta.append((4, 2, emit([(1, 0, get(entry, 1)),
                                     (2, 2, emit(m))])))
        head = [(n, w, v) for n, w, v in p if n in (1, 2)]
        planes_out.append((1, 2, emit(head + kept_lines + meta)))
    return emit(planes_out)


if __name__ == "__main__":
    src, dst = sys.argv[1], sys.argv[2]
    secs = float(sys.argv[3]) if len(sys.argv) > 3 else 0.25
    out = trim(open(src, "rb").read(), secs)
    open(dst, "wb").write(out)
    print("%s: %d bytes -> %s: %d bytes" % (src, len(open(src, "rb").read()),
                                            dst, len(out)))
