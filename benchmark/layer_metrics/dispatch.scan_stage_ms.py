"""Mean of `ipt_stage_us{stage="scan"}` over the window: host clock around
the launches plus the wait for the device.  Layer: device dispatch."""


def read(ctx):
    return ctx["window"].stage_mean_ms("scan")
