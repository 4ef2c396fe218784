"""100 x N x the least lane's requests over all lanes' requests in the
window, from `ipt_lane_requests_total{device=}`: 100 says the splitter
gave every lane an equal share, 0 that a lane served nothing.  Nothing
to read from a server with one lane.  Layer: lane router."""


def read(ctx):
    by_lane = ctx["window"].labelled("ipt_lane_requests_total", "device")
    total = sum(by_lane.values())
    if len(by_lane) < 2 or total <= 0:
        return None
    return 100.0 * len(by_lane) * min(by_lane.values()) / total
