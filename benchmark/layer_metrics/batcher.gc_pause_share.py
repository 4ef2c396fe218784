"""The window's interpreter collection pauses
(`ipt_gc_pause_us_total`, summed over generations) as a share of the
window's length, in %: a pause holds the interpreter lock, so every
thread of the server stands still for it.  Nothing to read from a program
without the counter.  Layer: admission + batching."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_gc_pause_us_total") is None or ctx["seconds"] <= 0:
        return None
    return 100.0 * w.delta("ipt_gc_pause_us_total") / 1e6 / ctx["seconds"]
