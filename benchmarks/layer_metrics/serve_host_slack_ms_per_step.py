"""scheduler: milliseconds a landed step that the host spent waiting for the
device (`sched_host_ms{phase="land_wait"}`, whole window): its slack.  Near 0,
a device-side gain cannot show in the token gap."""

from _host import WAIT, phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, WAIT)
