"""kernels: the fused gate+up weight's share of the HBM roof: its Q40 bytes a
token over 819 GB/s, over the device time under the `w13` scope per token."""

from _scopes import weight_roof_pct


def read(ctx):
    cfg = ctx["config"]
    return weight_roof_pct(ctx, "w13",
                           2 * cfg["hidden_size"] * cfg["intermediate_size"])
