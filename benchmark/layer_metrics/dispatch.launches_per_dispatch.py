"""The window's difference of `ipt_device_launches_total` (device programs
enqueued) over its count of `stage="batch"` spans.  Nothing to read from a
program without the counter.  Layer: device dispatch."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_device_launches_total") is None:
        return None
    batches = w.stage_count("batch")
    if batches <= 0:
        return None
    return w.delta_unlabelled("ipt_device_launches_total") / batches
