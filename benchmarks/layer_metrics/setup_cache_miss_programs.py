"""engine: programs of the start-up that the persistent compile cache did not
hold (`compile_cache_requests` - `compile_cache_hits` at the window's first
instant): each was compiled by the backend inside `setup_s`."""

from _host import at_start


def read(ctx):
    asked, hit = at_start(ctx, "compile_cache_requests"), \
        at_start(ctx, "compile_cache_hits")
    return None if asked is None or hit is None else asked - hit
