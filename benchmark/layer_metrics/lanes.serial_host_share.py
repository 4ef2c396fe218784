"""The dispatch thread's own work for all lanes over the window's
length: the sum of `ipt_lane_cycle_us{span="dispatch_own"}` (a cycle's
classify, prep, pack, split, confirm walk, fold and resolve on the one
dispatch thread; its waits for the lanes' results and its drains are not
in it) against `ctx["seconds"]`.  Near 100 says the serial host work,
not the chips, sets the rate.  The window and not `stage="batch"` is the
denominator: the mesh loop keeps two cycles open at once, so the batch
spans of a window add up to about twice its length.  Nothing to read
from one lane, or from a program without the counter.  Layer: lane
router."""


def read(ctx):
    w = ctx["window"]
    if (w.delta("ipt_lane_cycle_us_count", span="dispatch_own") <= 0
            or ctx["seconds"] <= 0):
        return None
    own_s = w.delta("ipt_lane_cycle_us_sum", span="dispatch_own") / 1e6
    return 100.0 * own_s / ctx["seconds"]
