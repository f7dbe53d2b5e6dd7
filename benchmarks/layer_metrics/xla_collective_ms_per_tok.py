"""collectives: device time in XLA collective instructions (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; per chip) over the
output tokens of the traced window.  Named for what it can see: under the fused
ring reduce the traffic is inside Pallas custom calls and this reads 0."""

from _common import traced_tokens


def read(ctx):
    toks, tr = traced_tokens(ctx), ctx["trace"]
    if not toks or not tr["chips"]:
        return None
    return tr["collective_s"] * 1e3 / toks
