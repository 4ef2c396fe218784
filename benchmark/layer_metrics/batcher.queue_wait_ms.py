"""Mean of `ipt_stage_us{stage="queue"}` over the window.  Layer:
admission + batching."""


def read(ctx):
    return ctx["window"].stage_mean_ms("queue")
