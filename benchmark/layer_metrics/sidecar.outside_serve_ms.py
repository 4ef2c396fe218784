"""Mean client round trip minus the server's own mean `e2e` stage: what
the sidecar hop, two sockets and the client add.  Layer: sidecar hop."""


def read(ctx):
    inside = ctx["window"].stage_mean_ms("e2e")
    lat = ctx["latencies_ms"]
    if inside is None or not lat:
        return None
    return sum(lat) / len(lat) - inside
