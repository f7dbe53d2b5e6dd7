"""kernels: device time under the `moe` scope (router, the expert matmuls of
the select / scan / unrolled strategy with the scan's bookkeeping, the combine
and the residual add) per scheduler step.  Follows the window's share of
pure-decode steps, as every `serve_*_per_step` does; `by-scope.json` splits it
by program."""

from _scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ["moe"]) or None  # a dense program has no such time
