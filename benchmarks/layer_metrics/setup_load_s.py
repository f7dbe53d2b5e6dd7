"""engine: seconds of the model load, file to host stacks plus each chip's
shard uploaded (`engine_load_seconds` read + place, the gauge the spans
`engine.load_read` / `engine.load_place` set)."""

from _host import at_start


def read(ctx):
    load = at_start(ctx, "engine_load_seconds")
    return load.get("read", 0.0) + load.get("place", 0.0) if load else None
