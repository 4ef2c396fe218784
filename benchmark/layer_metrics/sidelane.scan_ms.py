"""Mean of `ipt_stage_us{stage="side_scan"}` over the window, per
rerouted request: the unpack, the stream's begin with the head's
prefilter, and every wave to the flush.  Layer: oversized side lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_scan")
