"""Mean of `ipt_stage_us{stage="confirm_fold"}` over the window: the
single-threaded fold after the walk (telemetry, scoring, ACL, verdict
assembly).  A sub-span of `stage="confirm"`; nothing to read from a
program without it.  Layer: confirm."""


def read(ctx):
    return ctx["window"].stage_mean_ms("confirm_fold")
