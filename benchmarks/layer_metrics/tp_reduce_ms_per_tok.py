"""collectives: device time per chip in the tensor-parallel reduces, over the
output tokens of the traced window: the fused ring's Pallas kernels (op names
`q40_ring*`, the kernel's ``name=`` in ops/q40.py ``_tp_ring_allreduce``)
plus every XLA collective instruction (what `xla_collective_ms_per_tok`
reads: the psum path, and the gathers of the vocab-sharded head and its
sampling).  The two column matmuls of a layer (wo, w2) each end in one
reduce; prefill's reduces in the window are counted too."""

from _common import traced_tokens

RING_KERNEL = "q40_ring"


def reduce_s(trace: dict) -> float:
    """Seconds per chip inside ring kernels and XLA collectives."""
    ring = sum(s for name, s in trace["ops"].items()
               if name.startswith(RING_KERNEL))
    return ring + trace["collective_s"]


def read(ctx):
    toks, tr = traced_tokens(ctx), ctx["trace"]
    if not toks or not tr["chips"]:
        return None
    return reduce_s(tr) * 1e3 / toks
