"""Mean of `ipt_stage_us{stage="confirm"}` over the window.  Layer: confirm."""


def read(ctx):
    return ctx["window"].stage_mean_ms("confirm")
