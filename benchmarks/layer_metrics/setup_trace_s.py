"""engine: seconds of the start-up spent tracing Python functions to jaxprs
(`jaxpr_trace_seconds` at the window's first instant): paid by every start,
whatever the compile cache holds."""

from _host import at_start


def read(ctx):
    return at_start(ctx, "jaxpr_trace_seconds")
