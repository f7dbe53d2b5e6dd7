"""kernels: how full the blocks of the grouped expert launches were over the
window, tracing off: the (row, expert) pairs the prefill calls placed over the
rows of the blocks those pairs took (`moe_grouped_rows{what="pairs"}` over
`{what="slots"}`, summed over layers; the engine adds one pair of integers a
prefill call, which it has waited for anyway).  A launch's time follows the
blocks that hold rows, so a low fill says that routing spread a call's rows
over more blocks than their count needs.  Reads nothing on a program without
the counter, or whose prompts took another strategy."""


def read(ctx):
    a = ctx["after"].get("moe_grouped_rows")
    b = ctx["before"].get("moe_grouped_rows") or {}
    if not a:
        return None
    slots = a.get("slots", 0) - b.get("slots", 0)
    return 100.0 * (a.get("pairs", 0) - b.get("pairs", 0)) / slots if slots else None
