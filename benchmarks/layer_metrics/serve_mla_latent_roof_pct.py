"""kernels: the latent walk's and the absorb's share of the chip's roof.  The
least time one pure-decode step of `slots_busy_mean` rows, each at the traced
window's mean live context, needs for the work under `attn/latent` and
`attn/absorb` (the configuration's `models/<name>.py`: `mla_bytes` over the
peak bandwidth, `mla_flops` over the peak bf16 rate, harness/peaks.py; the
larger of the two: 128 heads on one latent row are near the chip's ridge of 242
FLOP a byte, so which floor binds depends on the context, and `by-scope.json`
does not say: this reader's `floor` does, in `out/mla-roof.json`), over the
device time under those two parts per scheduler step.  A mixed step's chunk
rows do 16 times a decode row's pair work and are charged as decode rows, so a
window with more mixed steps reads lower."""

import json
import os

import slots_busy_mean
from _parts import part_ms_per_step
from _scopes import OUT
from harness import models


def _mean_context(ctx) -> float | None:
    """Mean over the requests that emitted a token inside the traced window of
    their context at its middle: the prompt and the tokens out by then."""
    lo, hi = ctx["traced_window"]
    mid = (lo + hi) / 2
    live = [r["n_prompt"] + sum(1 for t in r["times"] if t < mid)
            for r in ctx["records"] if any(lo <= t < hi for t in r["times"])]
    return sum(live) / len(live) if live else None


def read(ctx):
    model = models.for_config(ctx["config"])
    need_b, need_f = getattr(model, "mla_bytes", None), getattr(model, "mla_flops", None)
    ms = part_ms_per_step(ctx, "attn", ["latent", "absorb"])
    rows, context = slots_busy_mean.read(ctx), _mean_context(ctx)
    if not ms or not rows or not context or need_b is None or ctx["peaks"] is None:
        return None
    by_bytes = need_b(ctx["config"], rows, context) / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = need_f(ctx["config"], rows, context) / ctx["peaks"]["bf16_flops_per_s"]
    with open(os.path.join(OUT, "mla-roof.json"), "w") as f:
        json.dump({"rows": rows, "mean_context": context, "ms_per_step": ms,
                   "floor_ms_bytes": by_bytes * 1e3, "floor_ms_flops": by_flops * 1e3,
                   "floor": "bytes" if by_bytes >= by_flops else "flops"}, f, indent=1)
    return 100.0 * max(by_bytes, by_flops) / (ms / 1e3)
