"""entry points: how late the load generator sent (send time minus due time,
95th percentile over the window's requests).  Every latency is timed from due,
so a starved generator shows here before it shows as a slow server."""


def read(ctx):
    return ctx["summary"].get("loadgen_late_p95_ms")
