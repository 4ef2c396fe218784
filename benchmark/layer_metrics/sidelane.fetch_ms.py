"""Mean of `ipt_stage_us{stage="side_fetch"}` over the window, per
rerouted request: the scan thread's waits for the device's results, the
copies of match and state back at the end of each `StreamEngine.scan`
call, summed over the request.  Inside `sidelane.scan_ms`.  Nothing to
read from a program without the span.  Layer: oversized side lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_fetch")
