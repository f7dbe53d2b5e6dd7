"""kernels: device time under `attn/window` (a windowed model's sliding-window
layers: the read of each slot's ring of pages, `kv_dense/window-ring`,
ops/window.py) per scheduler step; `_parts.py` reads the sub-name.  K-EXAONE's
cell: 18 of 24 layers, ten pages a slot whatever the context's depth."""

from _parts import part_ms_per_step


def read(ctx):
    return part_ms_per_step(ctx, "attn", ["window"])
