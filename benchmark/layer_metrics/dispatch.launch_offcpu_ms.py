"""Mean time off the CPU inside `ipt_stage_us{stage="scan_launch"}` per
dispatch, in ms: the window's difference of the stage's `_sum` less that
of `ipt_stage_cpu_us_total{stage="scan_launch"}` (the launching thread's
CPU time inside the same spans, bumped with each observation), over the
stage's `_count`.  The span is calls into JAX that enqueue work and block
on nothing, so what it spends off the CPU is the wait for the interpreter
lock (and preemption by other processes on the host).  Nothing to read
from a program without the CPU counter.  Layer: device dispatch."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_stage_cpu_us_total", stage="scan_launch") is None:
        return None
    n = w.stage_count("scan_launch")
    if n <= 0:
        return None
    off_us = (w.delta("ipt_stage_us_sum", stage="scan_launch")
              - w.delta("ipt_stage_cpu_us_total", stage="scan_launch"))
    return off_us / n / 1e3
