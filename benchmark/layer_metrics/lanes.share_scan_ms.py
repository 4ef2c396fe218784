"""Mean scan interval of one lane's share, in ms: hand-over to the
lane's worker -> result on the host (`ipt_lane_stage_us{stage=
"lane_scan"}`, `_sum` over `_count`, all lanes together): what
`dispatch.scan_stage_ms` is for one lane, plus the hand-over, less the
pack.  The host's clock.  Nothing to read from a program without the
span.  Layer: lane router."""


def read(ctx):
    w = ctx["window"]
    n = w.delta("ipt_lane_stage_us_count", stage="lane_scan")
    if n <= 0:
        return None
    return w.delta("ipt_lane_stage_us_sum", stage="lane_scan") / n / 1e3
