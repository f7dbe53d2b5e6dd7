"""scheduler: median over the window's requests of (last - first)/(n - 1) per request, on the
client's clock (harness/e2e.py).  In a served cell it spreads too widely from
run to run to carry a bound (PERF.md), so it is recorded here, per layer."""


def read(ctx):
    return ctx["summary"].get("tpot_p50_ms")
