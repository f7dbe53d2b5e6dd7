"""kernels: device time under `attn` + `kv_write` + `rope` (attention over the
live prefix, the cache update, the rotation) per output token received in the
traced window."""

from _common import traced_tokens
from _scopes import scope_s, scoped, table


def read(ctx):
    tab, toks = table(ctx), traced_tokens(ctx)
    if not scoped(tab) or not toks:
        return None
    return scope_s(tab, ["attn", "kv_write", "rope"]) * 1e3 / toks
