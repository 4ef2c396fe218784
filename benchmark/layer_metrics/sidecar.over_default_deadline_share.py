"""Share of round trips over the sidecar's default 50 ms `--deadline-ms`:
what the deployment's default would have passed unscanned.  Layer:
sidecar hop."""

DEFAULT_DEADLINE_MS = 50.0


def read(ctx):
    lat = ctx["latencies_ms"]
    if not lat:
        return None
    return 100.0 * sum(1 for x in lat if x > DEFAULT_DEADLINE_MS) / len(lat)
