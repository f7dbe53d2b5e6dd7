"""kernels: device time inside Pallas custom calls (per chip) over the output
tokens received in the traced window."""

from _common import traced_tokens


def read(ctx):
    toks, tr = traced_tokens(ctx), ctx["trace"]
    if not toks or not tr["chips"]:
        return None
    return tr["custom_call_s"] * 1e3 / toks
