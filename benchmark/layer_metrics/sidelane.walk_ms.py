"""Mean of `ipt_stage_us{stage="side_walk"}` over the window, per
rerouted request: the confirm walk's own time as the walker process
reports it (inline: the finish thread's walk span), inside
`sidelane.confirm_ms`; the rest of that stage is the host's part
(candidates, `confirm_streams()`, pickling, the hop, the mask and the
fold).  Nothing to read from a program without the span.  Layer:
oversized side lane."""


def read(ctx):
    return ctx["window"].stage_mean_ms("side_walk")
