"""kernels: `w13_roof_pct` on a mesh, where the fused gate+up weight is split
into `w1` and `w3` (runtime/engine.py ``_unfuse``) and each has a scope of its
own: the two weights' Q40 bytes a token per chip over 819 GB/s, over the device
time under both scopes per token.  Each scope goes through
``_scopes.weight_roof_pct``; the two have the same bytes, so the share of
their summed time is the harmonic mean of the two shares."""

from _scopes import weight_roof_pct


def read(ctx):
    cfg = ctx["config"]
    values = cfg["hidden_size"] * cfg["intermediate_size"]
    w1 = weight_roof_pct(ctx, "w1", values)
    w3 = weight_roof_pct(ctx, "w3", values)
    if not w1 or not w3:
        return None
    return 2.0 * w1 * w3 / (w1 + w3)
