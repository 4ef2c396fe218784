"""The window's requests over its count of `stage="batch"` spans.  Layer:
admission + batching."""


def read(ctx):
    batches = ctx["window"].stage_count("batch")
    served = ctx["window"].delta_unlabelled("ipt_requests_total")
    if batches <= 0 or served <= 0:
        return None
    return served / batches
