"""scheduler: wall time of a step that carries a prefill chunk
(`sched_step_wall_ms{mixed}` over `sched_steps{mixed}`, window delta)."""


def read(ctx):
    after, before = ctx["after"], ctx["before"]
    if not after.get("sched_steps"):
        return None
    n = after["sched_steps"].get("mixed", 0) \
        - (before.get("sched_steps") or {}).get("mixed", 0)
    ms = (after.get("sched_step_wall_ms") or {}).get("mixed", 0) \
        - (before.get("sched_step_wall_ms") or {}).get("mixed", 0)
    return ms / n if n else None
