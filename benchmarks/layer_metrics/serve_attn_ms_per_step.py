"""kernels: device time under the `attn` scope (fused paged kernel at one row
a slot, gather attention for prefill chunks) per scheduler step."""

from _scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ["attn"])
