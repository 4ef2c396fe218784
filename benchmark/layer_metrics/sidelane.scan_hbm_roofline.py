"""Memory side of the stream waves' roofline: the least time the chip's
HBM could take to move what the live wave rows of the traced slice
needed (harness/work_stream.py: their token bytes in, state and match
in and out, the byte-class table once a launch), over the summed device
time of the `scan_bytes_jit` programs in the trace.  Rows, bytes and
launches are the slice's own counter differences (scraped when the
profiler comes on), never what a wave was padded to.  The recurrence is
32-bit integer VPU work for which no peak is published, so this is a
lower bound of the true roofline share.  `None`, never 0, where there is
nothing to read.  Layer: oversized side lane."""

from harness import peaks, work_stream

#: the stream engine's wave program, as the trace names it
WAVE_PROGRAM = "scan_bytes_jit"


def read(ctx):
    trace, sl = ctx["trace"], ctx["slice"]
    if not trace or sl is None:
        return None
    if sl.last("ipt_stream_waves_total") is None:
        return None
    seconds = sum(p["seconds"] for name, p in trace["programs"].items()
                  if WAVE_PROGRAM in name)
    launches = sl.delta_unlabelled("ipt_stream_waves_total")
    if seconds <= 0 or launches <= 0:
        return None
    needed = work_stream.wave_bytes(
        sl.delta_unlabelled("ipt_stream_wave_rows_total"),
        sl.delta_unlabelled("ipt_stream_wave_bytes_total"),
        ctx["config"]["scan_words"], launches)
    least_s = needed / peaks.hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / seconds
