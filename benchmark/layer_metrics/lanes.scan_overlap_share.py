"""How far the lanes' scans overlapped: 100 x (sum over lanes of a
share's scan interval / the cycle's wall span of the same - 1) / (N - 1).
A share's interval runs from its hand-over to the lane's worker to its
result on the host (`ipt_lane_stage_us{stage="lane_scan"}`, summed over
`device`); the wall span from the cycle's first hand-over to its last
result (`ipt_lane_cycle_us{span="scan_wall"}`).  100 says all N lanes
were in flight together, 0 that they ran one after another; a cycle too
small to give every lane a share pulls it down too.  Both on the host's
clock.  Nothing to read from one lane, or from a program without the
spans.  Layer: lane router."""


def read(ctx):
    w = ctx["window"]
    n = w.last("ipt_lane_count")
    wall_us = w.delta("ipt_lane_cycle_us_sum", span="scan_wall")
    if n is None or n < 2 or wall_us <= 0:
        return None
    lanes_us = w.delta("ipt_lane_stage_us_sum", stage="lane_scan")
    return 100.0 * (lanes_us / wall_us - 1.0) / (n - 1.0)
