"""Mean of `ipt_stage_us{stage="confirm_walk"}` over the window: the
candidate walk of a dispatch (summed over confirm workers where there are
several).  A sub-span of `stage="confirm"`; nothing to read from a program
without it.  Layer: confirm."""


def read(ctx):
    return ctx["window"].stage_mean_ms("confirm_walk")
