"""kernels: device time of the power-retention operator per scheduler step: the
parts `retention` of scope `qkv` (the gate), `state` and `recent` of `attn` (the
read of the state matrix and of the ring of recent positions) and `recent` and
`fold` of `kv_write` (the ring's write, a block folded into the state)
(dllama_tpu/ops/retention.py; `_parts.py` reads the sub-names).  Brumby's served
cell: every layer.  `None` where no program carries the parts."""

from _parts import part_ms_per_step

PARTS = (("qkv", ["retention"]), ("attn", ["state", "recent"]),
         ("kv_write", ["recent", "fold"]))


def read(ctx):
    total = sum(part_ms_per_step(ctx, s, names) or 0.0 for s, names in PARTS)
    return total or None
