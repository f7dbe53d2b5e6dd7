"""scheduler: mean wait from submission to a slot (`queue_wait_seconds` sum
over count, window delta): the part of `serve_ttft_p50_ms` spent queued."""


def read(ctx):
    a, b = ctx["after"].get("queue_wait_seconds"), ctx["before"].get("queue_wait_seconds")
    if not a or not b or a["count"] == b["count"]:
        return None
    return 1e3 * (a["sum"] - b["sum"]) / (a["count"] - b["count"])
