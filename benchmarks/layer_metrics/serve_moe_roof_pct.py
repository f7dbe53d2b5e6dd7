"""kernels: the expert layers' share of the HBM roof.  The packed Q40 bytes one
step of `slots_busy_mean` rows needs from the expert layers (every layer's
router and the experts its rows hit, `E (1 - (1 - k/E)^rows)` of them: the
configuration's `models/<name>.py moe_bytes`, kept with the benchmark) over the
peak bandwidth (harness/peaks.py), over the device time under scope `moe` per
scheduler step.  Memory-bound: at 16 rows an expert's matmuls are 0.2 GFLOP
against 3.5 MB.  A mixed step's rows hit every expert and take longer, so a
window with more mixed steps reads lower; the masked scan reads all E experts
whatever the rows hit, which this share charges to it."""

import slots_busy_mean
from _scopes import ms_per_step
from harness import models


def read(ctx):
    ms = ms_per_step(ctx, ["moe"])
    rows = slots_busy_mean.read(ctx)
    need = getattr(models.for_config(ctx["config"]), "moe_bytes", None)
    if not ms or not rows or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], ctx["chips"], rows) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
