"""Mean of `ipt_stage_us{stage="prep"}` over the window.  Layer: host prep."""


def read(ctx):
    return ctx["window"].stage_mean_ms("prep")
