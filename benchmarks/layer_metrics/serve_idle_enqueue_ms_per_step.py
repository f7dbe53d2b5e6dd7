"""engine: device idle time a step whose innermost program span is on the
enqueue path (`sched.build`, `engine.h2d`, `engine.launch`, `engine.compile`,
or their parents `engine.slot_enqueue` / `sched.enqueue`): where the chip
waits while the host builds and launches a step.  A traced-window figure,
inflated by the profiler's Python tracer (`engine.h2d` reads 1.8 times its
untraced length, `_host.py`): the figures a `perf_opt` must move are
`serve_host_h2d_ms_per_step` and `serve_host_launch_ms_per_step`."""

from _host import idle_ms_per_step

SPANS = ("sched.build", "engine.h2d", "engine.launch", "engine.compile",
         "engine.slot_enqueue", "sched.enqueue")


def read(ctx):
    return idle_ms_per_step(ctx, SPANS)
