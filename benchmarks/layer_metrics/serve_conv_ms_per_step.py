"""kernels: device time of the gated short-convolution operator per scheduler
step: the part `conv` of the scopes `qkv`, `kv_write`, `attn` and `wo`
(dllama_tpu/ops/conv.py; `_parts.py` reads the sub-name).  LFM2's served cell:
24 of 32 layers, the state a ring of positions a slot.  `None` where no program
carries the part."""

from _parts import part_ms_per_step
from conv_ms_per_tok import SCOPES


def read(ctx):
    parts = [part_ms_per_step(ctx, s, ["conv"]) for s in SCOPES]
    total = sum(p for p in parts if p)
    return total or None
