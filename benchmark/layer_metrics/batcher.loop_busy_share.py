"""100 x (1 - time the dispatch thread spent waiting for work / the
window's length): the sum of `ipt_stage_us{stage="drain_idle"}` over the
window against `ctx["seconds"]`.  Near 100 says the serial dispatch loop,
not the offered load, sets the rate.  The program books the loop's waits
to the dispatch that ends them, so the idle stretch after the window's
close (a traced run scrapes only once the trace is written) is not in
the window's difference.  Nothing to read from a program without the
span.  Layer: admission + batching."""


def read(ctx):
    w = ctx["window"]
    if w.stage_count("drain_idle") <= 0 or ctx["seconds"] <= 0:
        return None
    idle_s = w.delta("ipt_stage_us_sum", stage="drain_idle") / 1e6
    return 100.0 * (1.0 - idle_s / ctx["seconds"])
