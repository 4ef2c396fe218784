"""1 - device busy time / traced window, from the server's profiler trace
(harness/xplane.py).  Nothing to read without a device trace.  Layer:
device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
