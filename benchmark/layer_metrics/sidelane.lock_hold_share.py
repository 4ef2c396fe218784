"""Share of the window during which the side lane held the batcher's
swap lock, in %: the sum of `ipt_stage_us{stage="side_lock"}` (per
rerouted request, its holds summed: the head's prefilter, each wave,
the verdict's fold) against `ctx["seconds"]`.  Every batched cycle takes
the same lock.  The side lane books a request's holds when its verdict
resolves, so requests still in the lane at the window's close are not
in the difference.  Layer: oversized side lane."""


def read(ctx):
    w = ctx["window"]
    if w.stage_count("side_lock") <= 0 or ctx["seconds"] <= 0:
        return None
    held_s = w.delta("ipt_stage_us_sum", stage="side_lock") / 1e6
    return 100.0 * held_s / ctx["seconds"]
