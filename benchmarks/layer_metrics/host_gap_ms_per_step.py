"""scheduler: ``sched_host_gap_ms`` (host time between two device steps that
the device had to wait for) sum over count, as deltas over the window."""


def read(ctx):
    a, b = ctx["after"].get("sched_host_gap_ms"), ctx["before"].get("sched_host_gap_ms")
    if not a or not b or a["count"] == b["count"]:
        return None
    return (a["sum"] - b["sum"]) / (a["count"] - b["count"])
