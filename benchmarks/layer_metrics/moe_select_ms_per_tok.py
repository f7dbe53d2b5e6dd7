"""kernels: device time under scope `moe` in the decode programs (router,
`moe_ffn`'s `select` strategy: the chosen experts' matmuls one launch each, the
combine), per output token received in the traced window."""

from _common import traced_tokens
from _decode import seconds


def read(ctx):
    secs, toks = seconds(ctx), traced_tokens(ctx)
    if not secs or not toks or not secs.get(("moe", "")):
        return None
    return secs[("moe", "")] * 1e3 / toks
