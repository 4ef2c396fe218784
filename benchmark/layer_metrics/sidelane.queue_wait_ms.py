"""Mean of `ipt_stage_us{stage="side_wait"}` over the window, per
rerouted request: from its submit to the side worker taking it (the
admission queue, the reroute on the dispatch thread, the side queue
behind the requests the one worker serves first).  Layer: oversized side
lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_wait")
