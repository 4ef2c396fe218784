"""Mean of `ipt_stage_us{stage="side_confirm"}` over the window, per
rerouted request: the stream's finish, which is the fold of the match
words into candidates, the confirm walk of the whole body and the
verdict's fold.  Layer: oversized side lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_confirm")
