"""What the stream engine's scan waves need to move, counted from the
program's counters (`ipt_stream_waves_total`, `ipt_stream_wave_rows_total`,
`ipt_stream_wave_bytes_total`).

A wave is one `scan_bytes_jit` program over the live rows of the streams
in it, at most 2,048 bytes a row, with the automaton carried between
waves on the host.  Per live row: the token bytes it carried in, and the
state and the match vector in and out (four vectors of the pack's word
count x 4 B).  Per wave launched: the byte-class table once (256 byte
values x words x 4 B).  Padding rows, the padding of a short row to
2,048 tokens and a widened token type are an implementation's own and
are not counted.
"""

from harness.work import BYTE_VALUES, WORD_BYTES

#: state in, state out, match in, match out
CARRIED_VECTORS = 4


def wave_bytes(live_rows: float, row_bytes: float, words: int,
               launches: float) -> float:
    if words <= 0:
        raise ValueError("a pack has at least one scan word")
    return (row_bytes + live_rows * CARRIED_VECTORS * words * WORD_BYTES
            + launches * BYTE_VALUES * words * WORD_BYTES)
