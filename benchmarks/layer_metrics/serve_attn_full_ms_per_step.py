"""kernels: device time under `attn/full` (a windowed model's full, unrotated
layers: the fused page walk over every live position of the full layers' pool)
per scheduler step; `_parts.py` reads the sub-name.  K-EXAONE's cell: 6 of 24
layers, 16 slots 3-5k positions deep."""

from _parts import part_ms_per_step


def read(ctx):
    return part_ms_per_step(ctx, "attn", ["full"])
