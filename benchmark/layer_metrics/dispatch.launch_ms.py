"""Mean of `ipt_stage_us{stage="scan_launch"}` over the window: the
host->device transfers and the enqueue of every device program of a
dispatch (nothing in it blocks).  A sub-span of `stage="scan"`; nothing to
read from a program without it.  Layer: device dispatch."""


def read(ctx):
    return ctx["window"].stage_mean_ms("scan_launch")
