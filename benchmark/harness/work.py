"""What the scan needs to move, counted from shapes and counters.

Per live row of tier L: L token bytes in, and one match-word vector of
the pack's word count x 4 B out.  Per scan program launched: the
byte-class table once (256 byte values x words x 4 B).  Padding rows, a
widened token type and intermediate state are an implementation's own
and are not counted.
"""

from typing import Dict

WORD_BYTES = 4
BYTE_VALUES = 256


def scan_bytes(live_rows_by_tier: Dict[int, float], words: int,
               launches: float) -> float:
    if words <= 0:
        raise ValueError("a pack has at least one scan word")
    per_rows = sum(n * (L + words * WORD_BYTES)
                   for L, n in live_rows_by_tier.items())
    return per_rows + launches * BYTE_VALUES * words * WORD_BYTES
