"""Share of the cross-cycle verdict cache's lookups that it answered,
in %: `ipt_confirm_cache_total{result="hit"}` over both results, summed
over the walker processes (each keeps its own cache) and the inline
walk.  A lookup is one candidate rule of a request whose confirm streams
that walk's cache had seen before (a request's first sighting walks
without one).  Says whether the cache pays for itself (ROADMAP D5).
Nothing to read from a program without the counter, or a deployment
without `--confirm-cache`.  Layer: confirm."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_confirm_cache_total") is None:
        return None
    total = w.delta("ipt_confirm_cache_total")
    if total <= 0:
        return None
    return 100.0 * w.delta("ipt_confirm_cache_total", result="hit") / total
