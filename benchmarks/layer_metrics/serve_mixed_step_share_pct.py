"""scheduler: share of the dispatches landed in the window that carried at
least one prefill row (`sched_steps{mixed}` over all kinds, window delta)."""


def read(ctx):
    a, b = ctx["after"].get("sched_steps"), ctx["before"].get("sched_steps") or {}
    if not a:
        return None
    d = {k: v - b.get(k, 0) for k, v in a.items()}
    return 100.0 * d.get("mixed", 0) / sum(d.values()) if sum(d.values()) else None
