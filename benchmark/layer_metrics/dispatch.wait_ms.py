"""Mean of `ipt_stage_us{stage="scan_wait"}` over the window: blocked on
the device's result and its copy back to the host.  A sub-span of
`stage="scan"`; nothing to read from a program without it.  Layer: device
dispatch."""


def read(ctx):
    return ctx["window"].stage_mean_ms("scan_wait")
