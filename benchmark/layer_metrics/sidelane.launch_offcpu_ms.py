"""Mean time off the CPU, per rerouted request, inside the side lane's
calls into JAX: the window's difference of `ipt_stage_us_sum` less that of
`ipt_stage_cpu_us_total` for `stage="side_launch"` (each wave's launch)
and `stage="side_prefilter"` (the head's prefilter), over the count of
`side_launch` (one a rerouted request).  What those calls spend off the
CPU is the wait for the interpreter lock (the prefilter also waits for
its results on the device).  Nothing to read from a program without the
CPU counter.  Layer: oversized side lane."""

STAGES = ("side_launch", "side_prefilter")


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_stage_cpu_us_total", stage="side_launch") is None:
        return None
    n = w.stage_count("side_launch")
    if n <= 0:
        return None
    off_us = sum(w.delta("ipt_stage_us_sum", stage=s)
                 - w.delta("ipt_stage_cpu_us_total", stage=s)
                 for s in STAGES)
    return off_us / n / 1e3
