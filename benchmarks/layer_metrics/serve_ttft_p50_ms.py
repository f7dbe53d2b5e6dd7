"""scheduler: median over the window's requests of first token minus due (queue wait and prefill), on the
client's clock (harness/e2e.py).  In a served cell it spreads too widely from
run to run to carry a bound (PERF.md), so it is recorded here, per layer."""


def read(ctx):
    return ctx["summary"].get("ttft_p50_ms")
