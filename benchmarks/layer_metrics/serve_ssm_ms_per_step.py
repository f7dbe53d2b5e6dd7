"""kernels: device time of the state-space mixer (Mamba-2, beside attention in
every block of Falcon-H1) per scheduler step: the parts `ssm` of scopes `qkv`
(`W_in`, the `dt` rows, softplus) and `wo` (the gate, the grouped norm,
`W_out`), `conv`, `state` and `recent` of `attn` (the convolution's ring read
and taps, the read of the state matrix and of the rings of recent positions) and
`conv`, `recent` and `fold` of `kv_write` (the rings' writes, a block folded into
the state) (dllama_tpu/ops/ssm.py; `_parts.py` reads the sub-names).  Attention's
own ops in the same layer keep the bare scopes and are not counted.  `None`
where no program carries the part `ssm` (every other arch, and the parent of the
PR that added it)."""

from _parts import part_ms_per_step

PARTS = (("qkv", ["ssm"]), ("wo", ["ssm"]), ("attn", ["conv", "state", "recent"]),
         ("kv_write", ["conv", "recent", "fold"]))


def read(ctx):
    ms = [part_ms_per_step(ctx, s, names) or 0.0 for s, names in PARTS]
    return sum(ms) if ms[0] else None      # no part ``ssm``: no mixer in the program
