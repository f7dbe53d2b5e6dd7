"""kernels: device time under `attn/window` (the sliding-window layers' read of
their ring, ops/window.py) in the decode programs, per output token received in
the traced window.  SmallThinker: 39 of 52 layers; the full layers' read is
`attn/full`, in `by-scope.json`'s op table."""

from _common import traced_tokens
from _decode import seconds


def read(ctx):
    secs, toks = seconds(ctx), traced_tokens(ctx)
    if not secs or not toks or not secs.get(("attn", "window")):
        return None
    return secs[("attn", "window")] * 1e3 / toks
