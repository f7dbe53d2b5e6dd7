"""kernels: device time under the matmul scopes (`qkv`, `wo`, `w13` or `w1`/`w3`,
`w2`, `head`: Pallas Q40 kernels at decode rows, XLA dequant above 128 rows)
per scheduler step."""

from _scopes import MATMUL_SCOPES, ms_per_step


def read(ctx):
    return ms_per_step(ctx, MATMUL_SCOPES)
