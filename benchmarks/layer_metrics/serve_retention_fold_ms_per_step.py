"""kernels: device time per scheduler step under the part `fold` of scope
`kv_write` alone: what lagging the state behind the position clock costs
(dllama_tpu/ops/retention.py: a slot's oldest 64 ring positions folded into its
state matrix, about one step in eight at eight slots; a step in which no slot
folds runs the loop's test and nothing else).  `None` where no program carries
the part."""

from _parts import part_ms_per_step


def read(ctx):
    return part_ms_per_step(ctx, "kv_write", ["fold"])
