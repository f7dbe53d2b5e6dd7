"""scheduler: milliseconds a landed step inside `RadixTree.evict`
(`sched_host_ms{phase="evict"}`, whole window): one walk of the tree a page."""

from _host import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "evict")
