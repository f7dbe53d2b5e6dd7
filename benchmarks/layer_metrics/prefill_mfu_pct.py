"""engine: the share of the chip's bf16 peak that the prefill calls of the
traced window reached, counting the operations their prompt tokens NEED: a
call of `n` tokens from position `pos` is `harness/cost.py step_flops` at `n`
rows whose live context sums to `n * pos + n (n + 1) / 2` (a row multiplies
through its own k experts, not through all of them, and scores the keys it may
see; the head is counted a row though a prompt pays it once: a few per cent
over), summed over the calls (`_prefill.py`: the `engine.prefill_chunk` spans
of a chunked prompt, else the `engine.prefill` spans), over their host time,
which holds the enqueue, the device and the wait, and over the peak
(harness/peaks.py).  Work done for rows that did not ask for it (every expert
over every row and a mask; a bucket's padding) is time here and no
operations, so this is the first token's distance from what the chip allows."""

from _prefill import calls
from harness import cost


def read(ctx):
    spans = calls(ctx)
    seconds = sum(s for s, _, _ in spans)
    if not seconds or ctx["peaks"] is None:
        return None
    flops = sum(cost.step_flops(ctx["config"], n, n * pos + n * (n + 1) / 2,
                                ctx["chips"]) for _, pos, n in spans)
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops_per_s"]
