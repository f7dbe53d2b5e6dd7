"""kernels: device time under the `kv_write` scope (pool scatter, cache update,
the int8 quantize that feeds them) per scheduler step, from the traced window."""

from _scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ["kv_write"])
