"""scheduler: mean of the ``sched_slots_occupied`` gauge over the 5 Hz samples
taken inside the window."""


def read(ctx):
    lo, hi = ctx["window"]
    vals = [m.get("sched_slots_occupied") for t, m in ctx["samples"] if lo <= t < hi]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
