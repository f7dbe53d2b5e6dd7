"""kernels: device time of the gated short-convolution operator in the decode
programs, per output token received in the traced window: the part `conv` of
the four scopes the operator passes through (`qkv/conv`: `W_in` and the `B * X`
gate; `kv_write/conv`: the state's write; `attn/conv`: the state's read, the
taps, the `C` gate; `wo/conv`: `W_out`; dllama_tpu/ops/conv.py).  LFM2: 24 of
32 layers.  `None` where no program carries the part (a program without such
layers, as the parent of the PR that added them)."""

from _common import traced_tokens
from _decode import seconds

SCOPES = ("qkv", "kv_write", "attn", "wo")


def read(ctx):
    secs, toks = seconds(ctx), traced_tokens(ctx)
    if not secs or not toks:
        return None
    total = sum(secs.get((s, "conv"), 0.0) for s in SCOPES)
    return total * 1e3 / toks if total else None
