"""scheduler: token events stamped inside the window over the window, all
requests together (harness/e2e.py ``out_tok_s``).  It follows the share of
prefill steps in the window, which is chaotic: two sets of runs spread by 3.0%
and 4.3%, too wide for a bound of at most 10% (PERF.md), so it is recorded here."""


def read(ctx):
    return ctx["summary"].get("out_tok_s")
