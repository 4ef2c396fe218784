"""Share of the window's requests that left the batched path for the
oversized side lane, in %: `ipt_oversized_rerouted_total` (both kinds)
over `ipt_requests_total`.  Nothing to read from a program without the
counter.  Layer: oversized side lane."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_oversized_rerouted_total") is None:
        return None
    served = w.delta_unlabelled("ipt_requests_total")
    if served <= 0:
        return None
    return 100.0 * w.delta("ipt_oversized_rerouted_total") / served
