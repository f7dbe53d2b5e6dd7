"""engine: seconds of the start-up the backend spent on programs the
persistent compile cache did not hold (`backend_compile_seconds` less
`compile_cache_retrieval_seconds`, the loads of the hits, at the window's
first instant): what `setup_cache_miss_programs` cost."""

from _host import at_start


def read(ctx):
    total, loads = at_start(ctx, "backend_compile_seconds"), \
        at_start(ctx, "compile_cache_retrieval_seconds")
    return None if total is None or loads is None else max(total - loads, 0.0)
