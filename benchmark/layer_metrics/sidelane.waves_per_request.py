"""Scan waves launched by the stream engine (`ipt_stream_waves_total`:
one device program and two copies back each) per rerouted request
(`ipt_oversized_rerouted_total`).  Nothing to read from a program
without the counters.  Layer: oversized side lane."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_stream_waves_total") is None:
        return None
    rerouted = w.delta("ipt_oversized_rerouted_total")
    if rerouted <= 0:
        return None
    return w.delta_unlabelled("ipt_stream_waves_total") / rerouted
