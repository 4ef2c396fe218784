"""Share of the window's confirm-walked requests that were walked in a
walker process, in %: `ipt_confirm_requests_total{where="process"}` over
the sum of its `where` series (`inline`: a one-worker pool, a batch of
one, a generation the walkers do not hold yet).  Says how often the
process pool engages.  Nothing to read from a program without the
counter.  Layer: confirm."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_confirm_requests_total") is None:
        return None
    total = w.delta("ipt_confirm_requests_total")
    if total <= 0:
        return None
    return 100.0 * w.delta("ipt_confirm_requests_total",
                           where="process") / total
