"""The UDS wire format, client side (copied from serve/protocol.py at PR 21).

The benchmark is a client of the sidecar's listen socket: it encodes
request frames and decodes verdict frames.  Kept here so that a later
change to the program's protocol module cannot move the yardstick; the
layout is the one `native/sidecar/protocol.hpp` mirrors.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, List

REQ_MAGIC = b"QTPI"
RESP_MAGIC = b"RTPI"
_REQ_HEAD = struct.Struct("<QIBB III")  # req_id tenant mode m_len | uri hdr body
_RESP_HEAD = struct.Struct("<QBIBH")    # req_id flags score n_cls n_rules
FLAG_ATTACK, FLAG_BLOCKED, FLAG_FAIL_OPEN = 1, 2, 4
MAX_FRAME = 8 << 20
#: byte offset of req_id inside a request frame (magic + length come first)
REQ_ID_OFFSET = 8


class ProtocolError(Exception):
    pass


@dataclass
class Request:
    """One HTTP request as the sidecar ships it."""

    method: str = "GET"
    uri: str = "/"
    headers: Dict[str, str] = field(default_factory=dict)
    body: bytes = b""
    tenant: int = 0


def encode_request(req: Request, req_id: int, mode: int = 2) -> bytes:
    """mode 2 = block, the deployment's `wallarm_mode`."""
    method = req.method.encode()
    uri = req.uri.encode("utf-8", "surrogateescape")
    hdr = "\x1f".join("%s: %s" % kv for kv in req.headers.items()).encode(
        "utf-8", "surrogateescape")
    payload = _REQ_HEAD.pack(req_id, req.tenant, mode, len(method),
                             len(uri), len(hdr), len(req.body))
    payload += method + uri + hdr + req.body
    return REQ_MAGIC + struct.pack("<I", len(payload)) + payload


def with_req_id(frame: bytes, req_id: int) -> bytes:
    """The same frame under another request id."""
    return (frame[:REQ_ID_OFFSET] + struct.pack("<Q", req_id)
            + frame[REQ_ID_OFFSET + 8:])


def decode_response(payload: bytes) -> dict:
    req_id, flags, score, n_cls, n_rules = _RESP_HEAD.unpack_from(payload)
    off = _RESP_HEAD.size + n_cls
    rules = list(struct.unpack_from("<%dQ" % n_rules, payload, off))
    return {"req_id": req_id,
            "attack": bool(flags & FLAG_ATTACK),
            "blocked": bool(flags & FLAG_BLOCKED),
            "fail_open": bool(flags & FLAG_FAIL_OPEN),
            "score": score,
            "rule_ids": rules}


class FrameReader:
    """Incremental splitter of one connection's verdict frames."""

    def __init__(self, magic: bytes = RESP_MAGIC):
        self.magic = magic
        self.buf = bytearray()

    def feed(self, data: bytes) -> List[bytes]:
        self.buf += data
        out = []
        while len(self.buf) >= 8:
            if bytes(self.buf[:4]) != self.magic:
                raise ProtocolError("bad magic %r" % bytes(self.buf[:4]))
            (length,) = struct.unpack_from("<I", self.buf, 4)
            if length > MAX_FRAME:
                raise ProtocolError("frame too large: %d" % length)
            if len(self.buf) < 8 + length:
                break
            out.append(bytes(self.buf[8:8 + length]))
            del self.buf[:8 + length]
        return out
