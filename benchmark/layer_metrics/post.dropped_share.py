"""Hits postanalytics dropped (`ipt_post_dropped_total`: the bounded
queue full, its oldest hit dropped) over the hits recorded
(`ipt_post_hits_total`, one an answered request), in %: should read 0.
Nothing to read from a program without the counters, or a deployment
without postanalytics.  Layer: postanalytics."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_post_hits_total") is None:
        return None
    hits = w.delta_unlabelled("ipt_post_hits_total")
    if hits <= 0:
        return None
    return 100.0 * w.delta_unlabelled("ipt_post_dropped_total") / hits
