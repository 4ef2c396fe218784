"""Mean of `ipt_stage_us{stage="post_record"}` over the window: one
request's postanalytics record on the event loop (node counters, top-K
sketches, one hit into the bounded queue), inside `sidecar.reply_lag_ms`.
Nothing to read from a program without the span, or a deployment
without postanalytics.  Layer: postanalytics."""


def read(ctx):
    return ctx["window"].stage_mean_ms("post_record")
