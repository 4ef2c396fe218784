"""Walker process of the confirm plane (docs/CONFIRM_PLANE.md).

One of these runs behind every confirm worker of a pool with more than
one worker: the worker's thread in the server ships a share of a
dispatch over a pipe and blocks on the answer (the interpreter lock
released), and this process walks it — the unchanged
:func:`confirm_one` against a read-only stand-in for the pipeline
(``confirms``, ``ctl_rules``, ``_ctl_pass_idx``, ``ruleset.rule_ids``)
and for each request (``confirm_streams()``, ``tenant``).

A fresh interpreter (``python -m`` this module; the pipe's descriptor
is the one argument), never a fork of the server: the server holds the
accelerator runtime's threads.  It imports what the walk needs and
nothing else — **never jax, never the device**: the server is the only
process that touches the chip.  It holds no state but the generations
installed in it, and exits when its pipe reads end-of-file (the pool
closed, or the server gone by any signal).

Messages, parent to child (each answered by exactly one reply):

* ``("install", gen, state, drop)`` — ``state`` holds the generation's
  compiled rule descriptors and ctl resolution; the ``ConfirmRule``
  closures are rebuilt here.  ``drop`` names the generations to forget:
  the server keeps the books (the ones it dealt to least recently go,
  so that cycles in flight, a rollout's candidate and its incumbent
  stay).
* ``("walk", gen, items, memo_cap, cache_cap)`` — ``items`` is
  ``[(streams, tenant, candidate rule indices as int32 bytes)]``.
  A generation this process does not hold is an error, never a walk
  against other rules.

Replies: ``("ok", payload)`` or ``("err", text)``.
"""

from __future__ import annotations

import signal
import sys
import time
from multiprocessing.connection import Connection
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from ingress_plus_tpu.models.confirm import ConfirmRule
from ingress_plus_tpu.models.confirm_plane import (
    ConfirmMemo,
    VerdictCache,
    confirm_one,
)


class _Generation:
    """What :func:`confirm_one` reads of a pipeline, for one installed
    generation, and the scratch candidate row its walks share."""

    __slots__ = ("confirms", "ctl_rules", "_ctl_pass_idx", "ruleset",
                 "chains", "row")

    def __init__(self, state: dict) -> None:
        self.confirms = [ConfirmRule(d) for d in state["descs"]]
        self.ctl_rules = state["ctl_rules"]
        self._ctl_pass_idx = state["ctl_pass_idx"]
        self.ruleset = SimpleNamespace(rule_ids=state["rule_ids"])
        # a rule then its chain links, flattened once: the quick-reject
        # counters of a share are gathered from these
        self.chains = [tuple(c.walk_chain()) for c in self.confirms]
        self.row = np.zeros((len(self.confirms),), dtype=bool)


class _Request:
    __slots__ = ("_streams", "tenant")

    def __init__(self, streams: Dict[str, bytes], tenant: int) -> None:
        self._streams = streams
        self.tenant = tenant

    def confirm_streams(self) -> Dict[str, bytes]:
        return self._streams


class Walker:
    """The child's whole state: installed generations and, under
    ``--confirm-cache``, this process's own verdict cache."""

    def __init__(self) -> None:
        self.generations: Dict[int, _Generation] = {}
        self.cache: Optional[VerdictCache] = None

    def handle(self, msg: tuple) -> tuple:
        kind = msg[0]
        if kind == "walk":
            return self.walk(*msg[1:])
        if kind == "install":
            _kind, gen, state, drop = msg
            for old in drop:
                self.generations.pop(old, None)
            self.generations[gen] = _Generation(state)
            return ("ok", gen)
        if kind == "modules":       # what a test asks: no jax in here
            return ("ok", sorted(sys.modules))
        if kind == "generations":
            return ("ok", sorted(self.generations))
        return ("err", "unknown message %r" % (kind,))

    def walk(self, gen: int, items: List[tuple], memo_cap: int,
             cache_cap: int) -> tuple:
        g = self.generations.get(gen)
        if g is None:
            return ("err", "generation %r is not installed here (held: %r)"
                    % (gen, list(self.generations)))
        t0 = time.perf_counter_ns()
        memo: Optional[ConfirmMemo] = None
        if cache_cap:
            if self.cache is None or self.cache.cap != cache_cap:
                self.cache = VerdictCache(cache_cap)
            memo = self.cache.view(gen)
        elif memo_cap and len(items) > 1:
            memo = ConfirmMemo(memo_cap)
        row = g.row
        results = []
        touched: set = set()
        for streams, tenant, idx_bytes in items:
            idx = np.frombuffer(idx_bytes, dtype=np.int32)
            row[idx] = True
            try:
                results.append(
                    confirm_one(g, _Request(streams, tenant), row, memo))
            finally:
                row[idx] = False
            touched.update(idx.tolist())
        # the share's quick-reject deltas, booked against the top-level
        # rule's row as RuleStats reads them (chain links included)
        qr = []
        chains = g.chains
        for r in touched:
            skips = evals = 0
            for link in chains[r]:
                if link.qr_skips or link.qr_evals:
                    skips += link.qr_skips
                    evals += link.qr_evals
                    link.qr_skips = link.qr_evals = 0
            if skips or evals:
                qr.append((r, skips, evals))
        hits = memo.hits if memo is not None else 0
        misses = memo.misses if memo is not None else 0
        return ("ok", (results, qr, hits, misses,
                       time.perf_counter_ns() - t0))


def main(argv: Optional[List[str]] = None) -> None:
    args = sys.argv[1:] if argv is None else argv
    # the server's lifetime is this process's: an interrupt meant for
    # the server's group ends it through the pipe's end-of-file
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    conn = Connection(int(args[0]))
    walker = Walker()
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        try:
            reply = walker.handle(msg)
        except Exception as e:  # noqa: BLE001 — relayed to the server
            reply = ("err", "%s: %s" % (type(e).__name__, e))
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            return


if __name__ == "__main__":
    main()
