"""Share of the window's dispatch cycles whose confirm the loop held open
across the next cycle's launch, in %: `ipt_cycles_total{confirm="held"}`
over the sum of its `confirm` series (`direct`: no next cycle had been
launched when the scan was collected, or the pool has no walkers to hold
a confirm on, so the cycle resolved at once).  Says how often a cycle's
walk overlaps the next cycle's scan.  Nothing to read from a program
without the counter.  Layer: confirm."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_cycles_total") is None:
        return None
    total = w.delta("ipt_cycles_total")
    if total <= 0:
        return None
    return 100.0 * w.delta("ipt_cycles_total", confirm="held") / total
