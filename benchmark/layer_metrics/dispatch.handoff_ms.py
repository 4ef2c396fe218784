"""Mean of `ipt_stage_us{stage="lane_handoff"}` over the window: the two
thread hand-offs around a dispatch's lane call (the caller's wait less the
closure's run time).  Nothing to read from a program without the span.
Layer: device dispatch."""


def read(ctx):
    return ctx["window"].stage_mean_ms("lane_handoff")
