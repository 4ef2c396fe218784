"""Mean of `ipt_stage_us{stage="scan_pack"}` over the window: pad/pack of
the rows into tier buckets, on the host.  A sub-span of `stage="scan"`;
nothing to read from a program without it.  Layer: device dispatch."""


def read(ctx):
    return ctx["window"].stage_mean_ms("scan_pack")
