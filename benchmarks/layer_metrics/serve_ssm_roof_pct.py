"""kernels: the state-space mixer's share of the chip's roof in a served cell.
The least one pure-decode step of `slots_busy_mean` rows needs for the mixer
(the configuration's `models/<name>.py`: `ssm_bytes` over the peak bandwidth,
`ssm_flops` over the peak bf16 rate, harness/peaks.py; the larger of the two,
and which one binds goes to `out/ssm-roof.json`), over `serve_ssm_ms_per_step`'s
device time per scheduler step.  The floor counts the least any exact
implementation does: `W_in` and `W_out` once, one read of the state a busy row a
layer and no write of it, the convolution's three live rows, 32 recent rows; the
time also holds the fold and the rings' writes, so nothing reads over 100.  A
mixed step's chunk rows do sixteen times a decode row's work and are charged as
decode rows, so a window with more mixed steps reads lower."""

import json
import os

import serve_ssm_ms_per_step
import slots_busy_mean
from _scopes import OUT
from harness import models


def read(ctx):
    model = models.for_config(ctx["config"])
    need_b = getattr(model, "ssm_bytes", None)
    need_f = getattr(model, "ssm_flops", None)
    if need_b is None or ctx["peaks"] is None:
        return None
    ms = serve_ssm_ms_per_step.read(ctx)
    rows = slots_busy_mean.read(ctx)
    if not ms or not rows:
        return None
    by_bytes = need_b(ctx["config"], rows, ctx["chips"]) / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = need_f(ctx["config"], rows, ctx["chips"]) / ctx["peaks"]["bf16_flops_per_s"]
    with open(os.path.join(OUT, "ssm-roof.json"), "w") as f:
        json.dump({"rows": rows, "ms_per_step": ms,
                   "floor_ms_bytes": by_bytes * 1e3, "floor_ms_flops": by_flops * 1e3,
                   "floor": "bytes" if by_bytes >= by_flops else "flops"}, f, indent=1)
    return 100.0 * max(by_bytes, by_flops) / (ms / 1e3)
