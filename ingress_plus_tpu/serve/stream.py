"""Streaming body scan — benchmark config #5 (BASELINE.md: chunked 1 MB
POST bodies, pipelined sliding-window NFA).

The reference's wallarm module parses request bodies incrementally as
nginx feeds it chunks (SURVEY.md §5 "long-context": `client_body_buffer_
size`†, incremental parse†).  TPU-native equivalent: the bitap NFA state
vector (W uint32 words per scan row) is carried across chunk scans —
``ops.scan.scan_bytes`` takes and returns (state, match) — so a body is
scanned exactly once no matter how it arrives, and a factor spanning a
chunk boundary is matched by the carried automaton state, no overlap
window needed.

Pieces:

- ``IncrementalVariant`` — streaming normalization: the one-shot
  ``variant_chain`` decoders (urlDecodeUni, htmlEntityDecode, squash)
  applied incrementally, holding back the longest suffix that could be a
  split escape/entity (≤5 B for ``%uXXXX``, ≤9 B for ``&entity;``) until
  the next chunk completes it.  Guaranteed: concat(feed*, flush) ==
  variant_chain(concat(chunks)) — the equivalence test's contract.
- ``StreamState`` — per-request carry: per-variant (match, state) word
  vectors + decoder tails + what the CPU confirm stage walks: the capped
  raw body of a wire stream, or the request itself where the caller
  holds it whole (the batcher's oversized side lane).
- ``StreamEngine`` — batches chunk scans across concurrent streams into
  fixed-shape ``scan_bytes_jit`` dispatches (waves of two widths chosen
  from the bytes pending, ``wave_width``: WIDE_L = 16,384 steps while
  that much is pending, CHUNK_L = 2,048 for the rest; pow2 row padding:
  few executables, any chunk size), and at stream end folds the
  final match words into rule hits (host factor→rule math, the same
  mapping engine.detect_rows does on-device) and hands them to
  ``DetectionPipeline.finalize``.

Sequence-parallel note: this is the single-core sequential chunk chain —
the SURVEY.md §5 default.  The cross-chip ring (state handoff via
``ppermute`` when one giant body is sharded over the mesh) lives in
``parallel/stream.py``; both carry the same O(W) state.
"""

from __future__ import annotations

import re
import time
from contextlib import nullcontext
from dataclasses import replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ingress_plus_tpu.compiler.bitap import (
    factors_to_rules,
    matches_to_factors,
)
from ingress_plus_tpu.compiler.seclang import STREAM_INDEX
from ingress_plus_tpu.compiler.ruleset import VARIANTS
from ingress_plus_tpu.models.confirm_plane import join_confirm
from ingress_plus_tpu.models.pipeline import DetectionPipeline, Verdict
from ingress_plus_tpu.ops.scan import pad_rows, scan_bytes_jit
from ingress_plus_tpu.serve.normalize import (
    Request,
    fold_overlong_utf8,
    html_entity_decode,
    remove_nulls,
    squash,
    url_decode_uni_raw,
)
from ingress_plus_tpu.serve.unpack import (
    GZIP_MAGIC,
    IncrementalBase64,
    IncrementalGrpc,
    IncrementalInflate,
    grpc_content_kind,
    header_lookup,
)

# longest suffix that might be an incomplete %-escape: %, %X, %u, %uX..%uXXX
_URL_TAIL = re.compile(rb"%(?:u[0-9a-fA-F]{0,3}|[0-9a-fA-F])?$")
# longest suffix that might be an incomplete &entity; (decoder looks for
# ';' within 9 bytes of '&', so anything longer can never decode)
_ENT_TAIL = re.compile(rb"&[#a-zA-Z0-9]{0,8}$")

CHUNK_L = 2048          # the narrow scan-wave width: small increments, tails
WIDE_L = 16384          # the wide one (the batched path's top row tier):
                        # the device's time a byte is the same at either
                        # width, a wave's host part is paid once a wave;
                        # each width is one executable per row tier
DEFAULT_BODY_CAP = 1 << 20   # raw bytes kept for the confirm stage
DEFAULT_SCAN_CAP = 16 << 20  # bytes scanned per stream (DoS bound): the
                             # reference bounds body inspection the same
                             # way (client_body_buffer_size† and module
                             # parse limits); beyond it chunks pass
                             # unscanned and the verdict is flagged


def _split_tail(buf: bytes, pat: re.Pattern) -> Tuple[bytes, bytes]:
    m = pat.search(buf)
    return (buf[: m.start()], buf[m.start():]) if m else (buf, b"")


class IncrementalVariant:
    """Streaming ``variant_chain``: feed() returns the next decoded
    increment, flush() releases held tails at end of stream."""

    def __init__(self, variant: int):
        self.variant = variant
        self._url_tail = b""   # undecoded bytes (possible split escape)
        self._fold_tail = b""  # decoded bytes (possible split overlong seq)
        self._ent_tail = b""   # url-decoded bytes (possible split entity)

    @staticmethod
    def _overlong_split(buf: bytes):
        """Split off the longest suffix that could be an incomplete
        overlong-UTF-8 sequence (C0/C1/E0 lead, or E0 80-9F pair) so
        fold_overlong_utf8 over chunked input equals the one-shot fold."""
        if buf and buf[-1] in (0xC0, 0xC1, 0xE0):
            return buf[:-1], buf[-1:]
        if len(buf) >= 2 and buf[-2] == 0xE0 and 0x80 <= buf[-1] <= 0x9F:
            return buf[:-2], buf[-2:]
        return buf, b""

    def feed(self, data: bytes) -> bytes:
        v = self.variant
        if v == 0:
            return data
        if v == 3:
            return squash(data)
        safe, self._url_tail = _split_tail(self._url_tail + data, _URL_TAIL)
        raw = self._fold_tail + url_decode_uni_raw(safe)
        raw, self._fold_tail = self._overlong_split(raw)
        dec = remove_nulls(fold_overlong_utf8(raw))
        if v == 1:
            return dec
        if v == 5:                   # squash(urldec) — NO html stage
            return squash(dec)
        safe2, self._ent_tail = _split_tail(self._ent_tail + dec, _ENT_TAIL)
        out = html_entity_decode(safe2)
        return squash(out) if v == 4 else out

    def flush(self) -> bytes:
        v = self.variant
        if v in (0, 3):
            return b""
        raw = self._fold_tail + url_decode_uni_raw(self._url_tail)
        self._url_tail, self._fold_tail = b"", b""
        out = remove_nulls(fold_overlong_utf8(raw))
        if v == 1:
            return out
        if v == 5:
            return squash(out)
        out = html_entity_decode(self._ent_tail + out)
        self._ent_tail = b""
        return squash(out) if v == 4 else out


class StreamState:
    """Carry for one streaming request.  Touched only by the batcher's
    dispatch thread — no locking."""

    def __init__(self, request: Request,
                 variants: Sequence[Tuple[int, int, int]],
                 n_words: int, version: str, body_cap: int,
                 scan_cap: int = DEFAULT_SCAN_CAP,
                 pb_kind: Optional[str] = None,
                 confirm_request: Optional[Request] = None):
        self.request = request          # body stays b"" (scanned separately)
        # the request the confirm stage walks, where the caller holds it
        # whole: what is fed is then only what is SCANNED (it may be an
        # unpacked copy) and no raw body is accumulated
        self.confirm_request = confirm_request
        # [(variant_id, sv_id, src)] — src 0 scans the (inflated) body,
        # src 1 scans its incremental base64 decode (same sv ids: decoded
        # base64 is just another normalization of the body stream)
        self.variants = list(variants)
        self.norms = [IncrementalVariant(v) for v, _, _ in self.variants]
        self.match = np.zeros((len(self.variants), n_words), np.uint32)
        self.state = np.zeros((len(self.variants), n_words), np.uint32)
        self.version = version          # ruleset fingerprint at begin
        self.base_hits: Optional[np.ndarray] = None  # (R,) from prefilter
        self.acc = bytearray()          # capped raw body for confirm
        self.body_cap = body_cap
        self.scan_cap = scan_cap
        self.body_len = 0
        self.scanned_len = 0
        self.chunks = 0
        self.truncated = False
        self.aborted = False
        self.error = False
        self.t0 = time.perf_counter()
        # unpack stage (SURVEY.md §3.3): gzip by Content-Encoding here,
        # by magic-byte sniff on the first chunk in feed(); base64
        # opportunistically (the decoder self-deactivates on the first
        # non-base64 chunk, so non-b64 streams scan zero extra rows).
        # JSON/XML field extraction is batch-path only — the decompressed
        # byte stream is scanned as-is here (escape-hidden payloads in
        # giant streamed JSON are a documented bound).
        self._parsers_off = request.parsers_off
        ce = header_lookup(request.headers, "content-encoding").lower()
        self.inflater: Optional[IncrementalInflate] = None
        # _sniff_buf holds the first byte(s) until the 2-byte gzip magic
        # can be decided — attacker-chosen 1-byte chunking must not defeat
        # the sniff; _sniff_done short-circuits it once decided
        self._sniff_buf = b""
        self._sniff_done = "gzip" in self._parsers_off
        if "gzip" not in self._parsers_off and ce in (
                "gzip", "x-gzip", "deflate"):
            self.inflater = IncrementalInflate(
                raw_deflate_ok=("deflate" in ce), max_total=scan_cap)
            self._sniff_done = True
        self.b64: Optional[IncrementalBase64] = (
            IncrementalBase64() if any(s == 1 for _, _, s in self.variants)
            else None)
        # gRPC/protobuf extraction rows (src=2; BASELINE config #5):
        # ``pb_kind`` comes from StreamEngine.begin's ONE
        # grpc_content_kind call — the same decision that gated the
        # src=2 rows, so gating and framing can never disagree.  Bare
        # protobuf (x-protobuf, no gRPC framing) buffers and extracts at
        # flush — the 5-byte-frame walker would go dead on its first
        # tag byte.
        self.grpc: Optional[IncrementalGrpc] = (
            IncrementalGrpc(framed=(pb_kind != "bare"))
            if any(s == 2 for _, _, s in self.variants) else None)

    def _unpack(self, data: bytes) -> bytes:
        """Raw chunk → scannable base bytes (inflate stage)."""
        if not self._sniff_done:
            self._sniff_buf += data
            if len(self._sniff_buf) < 2:
                return b""          # hold until the magic is decidable
            data, self._sniff_buf = self._sniff_buf, b""
            self._sniff_done = True
            if data[:2] == GZIP_MAGIC:
                self.inflater = IncrementalInflate(max_total=self.scan_cap)
        if self.inflater is None:
            return data
        out = self.inflater.feed(data)
        if self.inflater.error:
            # corrupt/overrun: scanned prefix stands, rest passes
            # unscanned → surfaced as truncated/fail-open at finish
            self.truncated = True
        return out

    def feed(self, data: bytes) -> List[Tuple["StreamState", int, bytes]]:
        """Raw chunk → per-variant scan increments."""
        self.chunks += 1
        self.body_len += len(data)
        if self.confirm_request is None:
            room = self.body_cap - len(self.acc)
            if room > 0:
                self.acc += data[:room]
            if len(data) > max(room, 0):
                self.truncated = True
        base = self._unpack(data)
        scan_room = self.scan_cap - self.scanned_len
        if scan_room <= 0:
            if base:
                self.truncated = True
            return []  # scan bound hit: remaining bytes pass unscanned
        if len(base) > scan_room:
            self.truncated = True
            base = base[:scan_room]
        b64_inc = self.b64.feed(base) if (self.b64 and base) else b""
        grpc_inc = self.grpc.feed(base) if (self.grpc and base) else b""
        # scan_cap bounds TOTAL scanned bytes — the base64-decoded and
        # grpc-extracted duplicate rows (src=1/2) are scanned too, so
        # they consume budget (round-2 advisor: counting only base
        # understated the per-stream DoS scan bound)
        self.scanned_len += len(base) + len(b64_inc) + len(grpc_inc)
        out = []
        for vi, (_v, _sv, src) in enumerate(self.variants):
            inp = (base, b64_inc, grpc_inc)[src]
            if inp and (inc := self.norms[vi].feed(inp)):
                out.append((self, vi, inc))
        return out

    def flush(self) -> List[Tuple["StreamState", int, bytes]]:
        held = b""
        if not self._sniff_done and self._sniff_buf:
            # stream ended before the magic was decidable: the held
            # byte(s) are plain body bytes
            held, self._sniff_buf = self._sniff_buf, b""
            self._sniff_done = True
        if self.inflater is not None and not self.inflater.finished:
            # compressed stream ended without its end marker (corrupt or
            # cut): only a prefix was scanned — surface at finish
            self.truncated = True
        b64_tail = self.b64.flush() if self.b64 is not None else b""
        grpc_tail = b""
        if self.grpc is not None:
            grpc_tail = (self.grpc.feed(held) if held else b"") \
                + self.grpc.flush()
            # flush-time extraction consumes scan budget like feed-time
            self.scanned_len += len(grpc_tail)
        out = []
        for vi, (_v, _sv, src) in enumerate(self.variants):
            inc = b""
            if src == 0 and held:
                inc += self.norms[vi].feed(held)
            if src == 1 and b64_tail:
                inc += self.norms[vi].feed(b64_tail)
            if src == 2 and grpc_tail:
                inc += self.norms[vi].feed(grpc_tail)
            inc += self.norms[vi].flush()
            if inc:
                out.append((self, vi, inc))
        return out


def wave_width(pending: int) -> int:
    """The wave plan's one rule: the steps of the next scan wave, from
    the bytes still pending in the longest row of the call.  WIDE_L
    while at least that much is pending, CHUNK_L for the rest, so a
    call's last wave pads at most CHUNK_L - 1 idle steps whatever the
    input's length and a small increment (a wire stream's chunk frame)
    launches only narrow waves."""
    return WIDE_L if pending >= WIDE_L else CHUNK_L


class StreamEngine:
    """Chunk-batch scanner + stream finisher.  Two callers, both under
    the batcher's swap lock: its dispatch thread (wire streams: holds
    the lock around the whole step) and its oversized side worker, which
    passes ``hold`` and takes the lock once a wave, for the generation
    check and the counters (the launch itself runs outside it).

    What is scanned and what is confirmed: ``scan`` sees exactly the
    bytes that were fed (through the incremental unpack and variant
    chain); ``finish`` confirms on ``StreamState.confirm_request`` where
    the caller gave one, else on the request with the accumulated raw
    body in place.  The side lane feeds the batched path's own scan
    stream (``unpack_body`` of the whole body, scan-only segments
    included) and confirms on the request as it arrived, so both of its
    stages see what the batched path's stages see."""

    #: row tiers ``warm`` compiles, powers of two from 8: a side-lane
    #: body's rows are its needed body variants (five: 8 rows); a wire
    #: stream's are those times up to three sources (body, base64,
    #: gRPC: 16), and two wire streams may share a wave (32)
    WARM_MAX_ROWS = 32

    def __init__(self, pipeline: DetectionPipeline,
                 body_cap: int = DEFAULT_BODY_CAP):
        self.pipeline = pipeline
        self.body_cap = body_cap
        # scan waves launched, the live rows in them, the bytes those
        # rows carried and the waves' widths summed (/metrics
        # ipt_stream_wave*_total: steps over waves is the mean width,
        # steps over bytes the padding); bumped under the swap lock by
        # either caller
        self.waves = 0
        self.wave_rows = 0
        self.wave_bytes = 0
        self.wave_steps = 0
        self.warmed = False

    def warm(self) -> int:
        """Compile every ``scan_bytes_jit`` shape one stream's scan can
        launch (rows 8 .. ``WARM_MAX_ROWS`` by both wave widths), so the
        first oversized request pays no compile.  Returns the shapes
        made."""
        tables = self.pipeline.engine.tables.scan
        W = self.pipeline.ruleset.tables.n_words
        n, B = 0, 8
        while B <= self.WARM_MAX_ROWS:
            zeros = np.zeros((B, W), np.uint32)
            for L in (CHUNK_L, WIDE_L):
                tokens, lengths = pad_rows([b""] * B, max_len=L, round_to=L)
                # as ``scan`` calls it: the first wave's carry comes
                # from the host, every later one from the wave before
                match, state = scan_bytes_jit(tables, tokens, lengths,
                                              zeros, zeros)
                np.asarray(scan_bytes_jit(tables, tokens, lengths, state,
                                          match)[0])
                n += 1
            B *= 2
        self.warmed = True
        return n

    # -------------------------------------------------------- lifecycle

    def begin(self, request: Request,
              confirm_request: Optional[Request] = None) -> StreamState:
        """``confirm_request``: the caller holds the whole request (the
        batcher's oversized reroute) — ``finish`` confirms on it, and
        what is fed is only scanned, never accumulated."""
        p = self.pipeline
        si = STREAM_INDEX[getattr(request, "body_stream", "body")]
        base = [(v, si * len(VARIANTS) + v, 0) for v in range(len(VARIANTS))
                if si * len(VARIANTS) + v in p.needed_sv]
        off = request.parsers_off
        variants = list(base)
        if "base64" not in off:
            # a second row group scanning the incremental base64 decode
            # of the body; costs nothing unless the body is base64-shaped
            variants += [(v, sv, 1) for v, sv, _ in base]
        pb_kind = grpc_content_kind(
            header_lookup(request.headers, "content-type"))
        if "json" not in off and pb_kind is not None:
            # gRPC text-field extraction rows (src=2; config #5) — same
            # sv ids: extracted strings are another body normalization
            variants += [(v, sv, 2) for v, sv, _ in base]
        return StreamState(request, variants, p.ruleset.tables.n_words,
                           p.ruleset.version,
                           self.body_cap, pb_kind=pb_kind,
                           confirm_request=confirm_request)

    # ------------------------------------------------------------ scan

    def scan(self, items: List[Tuple[StreamState, int, bytes]],
             hold=None) -> int:
        """Scan increments for many (stream, variant) rows, batched into
        waves whose width ``wave_width`` chooses from the bytes pending
        in the call's longest row (WIDE_L while that much is pending,
        then CHUNK_L; a shorter row rides a wave with ``lengths`` short
        of its width).  Items for the same (stream, variant) are
        concatenated in arrival order (state carry makes that exact).
        ``hold``: a context-manager factory entered once a wave (the
        side worker's hold of the swap lock: the version check, the
        tables of that generation and the counters; the launch follows
        outside it, and nothing waits for the device before the last
        wave); None where the caller holds the lock already.  Returns
        the waves launched."""
        hold = hold or nullcontext
        merged: Dict[Tuple[int, int], List] = {}
        for st, vi, data in items:
            if st.aborted or st.error:
                continue
            if st.version != self.pipeline.ruleset.version:
                # ruleset swapped mid-stream: old state words are
                # meaningless against the new tables → fail-open at finish
                st.error = True
                continue
            merged.setdefault((id(st), vi), [st, vi, bytearray()])[2].extend(
                data)
        all_rows = list(merged.values())
        if not all_rows:
            return 0
        # Dedup identical scan work — the streaming twin of merge_rows'
        # one-shot row dedup: rows whose (state, match, pending bytes) are
        # byte-identical produce identical results (pure recurrence), so
        # scan one representative and broadcast.  Dominant benign case: a
        # plain-ASCII body makes every variant's increment equal raw's and
        # their carried states stay equal → 1 scanned row, not ~5.
        groups: Dict[bytes, List] = {}
        for r in all_rows:
            st, vi, data = r
            key = (st.state[vi].tobytes() + st.match[vi].tobytes()
                   + bytes(data))
            groups.setdefault(key, []).append(r)
        rows = [g[0] for g in groups.values()]
        followers = {id(g[0]): g[1:] for g in groups.values()}
        # one row set for the whole call: a row that runs out of bytes
        # rides on with a length short of the wave's width, then 0
        # (padded steps are the identity on state and match), so the
        # carry stays ON THE DEVICE from wave to wave and comes back to
        # the host once, after the last one; nothing in between waits
        # for the device
        B = 8
        while B < len(rows):
            B *= 2
        W = rows[0][0].state.shape[1]
        state = np.zeros((B, W), np.uint32)
        match = np.zeros_like(state)
        for j, (st, vi, _data) in enumerate(rows):
            state[j] = st.state[vi]
            match[j] = st.match[vi]
        waves = off = 0
        pending = max(len(r[2]) for r in rows)
        while off < pending:
            L = wave_width(pending - off)
            chunks = [bytes(r[2][off:off + L]) for r in rows]
            tokens, lengths = pad_rows(
                chunks + [b""] * (B - len(rows)), max_len=L, round_to=L)
            with hold():
                p = self.pipeline
                if any(r[0].version != p.ruleset.version for r in rows):
                    # swapped since this scan began: only a caller that
                    # takes the lock per wave can see it, and its rows
                    # are one stream's
                    for r in rows:
                        for st, _vi, _ in (r, *followers[id(r)]):
                            st.error = True
                    return waves
                tables = p.engine.tables.scan
                self.waves += 1
                self.wave_rows += sum(1 for c in chunks if c)
                self.wave_bytes += sum(len(c) for c in chunks)
                self.wave_steps += L
            # launched outside the hold: the program runs on the tables
            # of the generation just checked whatever is installed while
            # it does, and on this machine the call into JAX returns
            # only after milliseconds, which a batched cycle must not
            # wait out
            match, state = scan_bytes_jit(tables, tokens, lengths, state,
                                          match)
            waves += 1
            off += L
        m_out = np.asarray(match)
        s_out = np.asarray(state)
        for j, r in enumerate(rows):
            for st, vi, _ in (r, *followers[id(r)]):
                st.state[vi] = s_out[j]
                st.match[vi] = m_out[j]
        return waves

    # ---------------------------------------------------------- finish

    def _failed_open(self, st: StreamState) -> Verdict:
        """A stream that errored, or whose ruleset was swapped under it."""
        self.pipeline.stats.count_fail_open()
        return Verdict(request_id=st.request.request_id, blocked=False,
                       attack=False, classes=[], rule_ids=[], score=0,
                       fail_open=True, elapsed_us=int(
                           (time.perf_counter() - st.t0) * 1e6))

    def finish(self, st: StreamState, hold=None,
               lone_to_walker: bool = False) -> Verdict:
        """Fold the stream's match words into rule hits and confirm.
        ``hold`` as in :meth:`scan`: given, the confirm walk and the
        wait for it run OUTSIDE the lock and only the mask and the
        single-threaded fold take it; a ruleset swapped in meanwhile
        fails the stream open at the fold.  ``lone_to_walker``: the
        walk of this batch of one goes to a walker process
        (``ConfirmPool.deal``) and this thread blocks on its pipe, the
        interpreter lock released — the oversized side lane asks it,
        so that its body's walk of tens of ms leaves the lock to its
        scan thread.  Otherwise (a wire stream), and where no walker
        holds the generation, the walk is inline on this thread."""
        hold = hold or nullcontext
        p = self.pipeline
        req = st.request
        if st.error or st.version != p.ruleset.version:
            return self._failed_open(st)
        cr = p.ruleset
        bt = cr.tables
        R = cr.n_rules
        body_hits = np.zeros((R,), dtype=bool)
        applies_any = np.zeros((R,), dtype=bool)
        for vi, (_v, sv, _src) in enumerate(st.variants):
            rr = factors_to_rules(bt, matches_to_factors(bt, st.match[vi]))
            applies = cr.rule_sv_mask[:, sv]
            body_hits |= rr & applies
            applies_any |= applies
        # rules with no prefilter factors must always reach confirm when
        # any applicable row was scanned (mirrors engine.detect_rows)
        body_hits |= (bt.rule_nfactors == 0) & applies_any

        hits = body_hits
        if st.base_hits is not None:
            hits = hits | st.base_hits
        # the confirm twin: the request as the caller holds it, else
        # the accumulated (capped) raw body in the stream's request.
        # parsers_off carries over either way: the confirm stage
        # re-unpacks the body and must not run a decoder the scan stage
        # had disabled (the "both stages see identical bytes" contract).
        # dataclasses.replace keeps every other field AND the concrete
        # type (a Response reroutes through here too — its confirm twin
        # must stay a Response so resp_* streams rebuild)
        confirm_req = (st.confirm_request if st.confirm_request is not None
                       else replace(req, body=bytes(st.acc)))
        with hold():
            if st.version != p.ruleset.version:
                return self._failed_open(st)
            hits = p.mask_hits([req], hits[None])
        # the walk of a body of tens of KB lasts tens of ms: it, and a
        # walker's answer, are waited for outside the hold, so a
        # batched cycle never waits them out
        cjob = p.finalize_launch([confirm_req], hits, lone_to_walker)
        join_confirm(p, cjob)
        with hold():
            if st.version != self.pipeline.ruleset.version:
                return self._failed_open(st)
            v = p.finalize_join(cjob, st.t0)[0]
            p.stats.requests += 1
        # scan/confirm caps were hit: the verdict is based on a prefix —
        # surface it the fail-open way (pass-and-flag, never silently)
        if st.truncated and not v.attack:
            v.fail_open = True
        return v
