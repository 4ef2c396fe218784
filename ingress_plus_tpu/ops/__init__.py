"""The scan: the per-byte automaton hot loop (SURVEY.md §3.3 #2) as the
bitap recurrence of compiler/bitap.py.

- ``scan.py``   — the two XLA lowerings, `lax.scan` over the time axis
  with the batch x words update vectorized: ``scan_bytes`` (one reach
  gather per byte; the plain reference, and what carries the NFA state
  vector across streamed chunks) and ``scan_pairs`` (class-pair stride:
  one reach gather per two bytes; what serves).  Runs anywhere (CPU
  tests, TPU) and is what multi-chip sharding wraps.
- ``pallas_scan.py`` — a hand-scheduled Pallas kernel with
  ``scan_bytes``' contract; by name only (``scan_impl="pallas"``),
  nothing serves it (its docstring says why it stays).
- ``parity.py`` — the one comparison of a scan against ``scan_bytes``
  (tests, ``chip_smoke.py`` compiled on the chip).

``models/engine.py resolve_scan_impl`` picks the lowering that serves
from the pack's tables.
"""

from ingress_plus_tpu.ops.scan import (  # noqa: F401
    ScanTables,
    scan_bytes,
    scan_bytes_reference,
)
