"""Rerouted requests (`ipt_oversized_rerouted_total`, both kinds) per
batch the side lane scanned and confirmed together
(`ipt_oversized_batches_total`): 1.0 where every rerouted request is
served alone.  Nothing to read from a program without the batch counter.
Layer: oversized side lane."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_oversized_batches_total") is None:
        return None
    batches = w.delta_unlabelled("ipt_oversized_batches_total")
    if batches <= 0:
        return None
    return w.delta("ipt_oversized_rerouted_total") / batches
