"""engine: compiled programs launched per output token: executable look-ups
(``engine_executable_cache_hits`` + ``engine_recompiles``, one per prefill
step or decode chunk) as a delta over the window, over the tokens received in
it.  (The ``matmul_dispatch`` ledger counts call sites at trace time, not
launches, so it cannot give a rate; PERF.md.)"""

from _common import delta, tokens_between


def read(ctx):
    toks = tokens_between(ctx, *ctx["window"])
    n = delta(ctx, "engine_executable_cache_hits") + delta(ctx, "engine_recompiles")
    return n / toks if toks and n else None
