"""engine: milliseconds a landed step uploading a slot program's operands
(`sched_host_ms{phase="h2d"}`, the span `engine.h2d`, whole window)."""

from _host import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "h2d")
