"""scheduler: host work a landed step, the working phases of `sched_host_ms`
(admit, evict, build, h2d, launch, fanout, verdict; not `land_wait`, the host
waiting, nor `compile`, a program compiled inside the window), whole window.
Above the step's busy time the host sets the pace."""

from _host import WORK, phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, *WORK)
