"""engine: programs the start-up wrote to the persistent compile cache
(`compile_cache_writes` at the window's first instant).  Above 0 the start
found the cache unfilled and its `setup_s` holds compiles the next start will
not make: compare `setup_s` between starts that wrote nothing."""

from _host import at_start


def read(ctx):
    return at_start(ctx, "compile_cache_writes")
