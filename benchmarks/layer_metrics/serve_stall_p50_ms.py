"""scheduler: median over the window's requests of the longest gap between successive tokens of a request, on the
client's clock (harness/e2e.py).  In a served cell it spreads too widely from
run to run to carry a bound (PERF.md), so it is recorded here, per layer."""


def read(ctx):
    return ctx["summary"].get("stall_p50_ms")
