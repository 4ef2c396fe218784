"""Mean of `ipt_stage_us{stage="side_lock_wait"}` over the window, per
rerouted request: asking for the batcher's swap lock -> holding it,
summed over the request's holds on both side threads (the head's
prefilter, each wave's generation check, the mask and the verdict's
fold).  Nothing to read from a program without the span.  Layer:
oversized side lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_lock_wait")
