"""engine: seconds of the start-up inside the backend's compile-or-load call
(`backend_compile_seconds` at the window's first instant; JAX times the call
around the persistent cache's look-up, so a hit's load is in it:
`setup_cache_miss_s` is the part that is not)."""

from _host import at_start


def read(ctx):
    return at_start(ctx, "backend_compile_seconds")
