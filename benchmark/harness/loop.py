"""The load loop: a closed loop at a fixed number in flight.

One thread, one selector, `connections` Unix sockets to the sidecar's
listen socket, `in_flight / connections` requests outstanding on each:
a connection sends its next request when a verdict comes back, as a
proxy worker does.  Copied in spirit from `chip_smoke.drive`, which
drove one connection; the per-request record is what the reduction and
the comparison read.
"""

from __future__ import annotations

import selectors
import socket
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from harness.wire import FrameReader, decode_response


@dataclass
class Sent:
    req_id: int
    pool_index: int
    t_send: float                 # time.monotonic()
    t_recv: Optional[float] = None
    verdict: Optional[dict] = None
    doubled: bool = False


def run_closed(sock_path: str, next_frame: Callable[[], tuple],
               in_flight: int, connections: int, seconds: float,
               drain_s: float = 60.0) -> tuple:
    """Drive the closed loop for `seconds`, then wait up to `drain_s` for
    what is still outstanding.  `next_frame()` returns (req_id,
    pool_index, frame bytes).  Returns (records in send order, t_start,
    t_close): the window is [t_start, t_close] on time.monotonic()."""
    if in_flight % connections:
        raise ValueError("in_flight %d does not divide over %d connections"
                         % (in_flight, connections))
    quota = in_flight // connections
    sel = selectors.DefaultSelector()
    conns = []
    for _ in range(connections):
        s = socket.socket(socket.AF_UNIX)
        s.connect(sock_path)
        conns.append({"sock": s, "reader": FrameReader(), "out": 0})
        sel.register(s, selectors.EVENT_READ, conns[-1])
    records: List[Sent] = []
    by_id = {}
    outstanding = 0

    def send_one(conn) -> None:
        nonlocal outstanding
        req_id, pool_index, frame = next_frame()
        rec = Sent(req_id, pool_index, time.monotonic())
        records.append(rec)
        by_id[req_id] = rec
        conn["sock"].sendall(frame)
        conn["out"] += 1
        outstanding += 1

    try:
        t_start = time.monotonic()
        t_close = t_start + seconds
        for conn in conns:
            for _ in range(quota):
                send_one(conn)
        t_give_up = t_close + drain_s
        while outstanding:
            now = time.monotonic()
            if now >= t_give_up:
                break
            for key, _ in sel.select(timeout=min(0.05, t_give_up - now)):
                conn = key.data
                data = conn["sock"].recv(1 << 16)
                if not data:
                    raise ConnectionError(
                        "the sidecar closed a connection with %d verdicts "
                        "outstanding on it" % conn["out"])
                t_recv = time.monotonic()
                for payload in conn["reader"].feed(data):
                    v = decode_response(payload)
                    rec = by_id.get(v["req_id"])
                    if rec is None:
                        raise ConnectionError(
                            "verdict for request %d, which was never sent"
                            % v["req_id"])
                    if rec.verdict is not None:
                        rec.doubled = True
                        continue
                    rec.verdict, rec.t_recv = v, t_recv
                    conn["out"] -= 1
                    outstanding -= 1
                    if t_recv < t_close:
                        send_one(conn)
        return records, t_start, t_close
    finally:
        for conn in conns:
            sel.unregister(conn["sock"])
            conn["sock"].close()
        sel.close()


LOOPS = {"closed": run_closed}
