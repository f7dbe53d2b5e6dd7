"""engine: milliseconds a landed step inside the jitted call
(`sched_host_ms{phase="launch"}`, the span `engine.launch`: executable
look-up, argument handling, PJRT enqueue and any wait inside it; a launch
that compiled is in phase `compile` instead; whole window)."""

from _host import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "launch")
