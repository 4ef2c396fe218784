"""99th percentile of the client's round trips (send to verdict, through
the sidecar), over the requests answered before the profiler came on: the
tail as a cell's users see it, where it swings too widely from run to run
to carry a bound (`latency_p99_ms` is the bounded twin in the cells that
list it).  A failed request counts at the worst latency.  Layer: sidecar
hop."""

from harness import reduce


def read(ctx):
    t_on = ctx["slice_t_on"]
    records = [r for r in ctx["records"]
               if t_on is None or (r.t_recv is not None and r.t_recv <= t_on)]
    if not records:
        return None
    t_end = max(r.t_recv for r in records if r.t_recv is not None)
    return reduce.percentile(reduce.latencies_ms(records, t_end), 99.0)
