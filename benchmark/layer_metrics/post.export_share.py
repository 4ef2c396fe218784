"""The exporter thread's flushes (`ipt_stage_us{stage="post_export"}`
summed: drain the hit queue, fold the hits into attacks, brute and
dirbust detection, append to the spool) as a share of the window's
length, in %: the time a Python thread beside the serve threads spent on
postanalytics, holding the interpreter lock for most of it.  0 where no
flush fell inside the window.  Nothing to read from a program without
the span, or a deployment without postanalytics.  Layer:
postanalytics."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_stage_us_sum", stage="post_export") is None \
            or ctx["seconds"] <= 0:
        return None
    return (100.0 * w.delta("ipt_stage_us_sum", stage="post_export") / 1e6
            / ctx["seconds"])
