"""Mean of `ipt_stage_us{stage="reply"}` over the window: from the verdict
resolved on the dispatch thread to its frame handed to the socket by the
event loop (wake-up, postanalytics record, encode): the part of
`sidecar.outside_serve_ms` spent inside the server.  Nothing to read from
a program without the span.  Layer: sidecar hop."""


def read(ctx):
    return ctx["window"].stage_mean_ms("reply")
