"""scheduler: device idle time a step whose innermost program span is
`sched.admit` or the `sched.evict` inside it: where the chip waits at the head
of a round.  A traced-window figure, inflated by the profiler's Python tracer
(`sched.evict`'s walk reads 18 times its untraced length, `_host.py`): the
figure a `perf_opt` must move is `serve_host_evict_ms_per_step`."""

from _host import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, ("sched.admit", "sched.evict"))
