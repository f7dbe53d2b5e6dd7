"""Helpers the readers share.  ``ctx`` is what ``run.py`` hands a reader:
``summary`` (harness/e2e.py), ``before``/``after`` (the server's /metrics JSON at
the window's edges), ``samples`` ((time, /metrics) at 5 Hz), ``trace``
(harness/xplane.py ``reduce``), ``records`` (the load generator's requests),
``window`` and ``traced_window`` (host clock), ``config``, ``cell``, ``mix``,
``chips``, ``peaks``, ``device``."""


from harness.server import counter_total


def delta(ctx: dict, key: str) -> float:
    return counter_total(ctx["after"], key) - counter_total(ctx["before"], key)


def tokens_between(ctx: dict, lo: float, hi: float) -> int:
    return sum(1 for r in ctx["records"] if r["ok"] or r["cut"]
               for t in r["times"] if lo <= t < hi)


def traced_tokens(ctx: dict) -> int:
    return tokens_between(ctx, *ctx["traced_window"])
