"""kernels: a looped model's weight streaming as a share of the HBM roof.  The
packed Q40 bytes one scheduler step must stream (the configuration's
`models/<name>.py loop_weight_bytes`, kept with the benchmark: every layer's
matrices once a pass, `total_ut_steps` passes, and the head once) over the peak
bandwidth (harness/peaks.py), over the device time under the matmul scopes
(`qkv`, `wo`, `w13`, `w2`, `head`) per scheduler step: the same work whatever
implements it.  Memory-bound: 8 rows are 0.16 TFLOP a step, 0.8 ms at the bf16
peak, against 5.6 GB.  A mixed step's chunk rows run the same weights over more
rows and take longer, so a window with more mixed steps reads lower.  A
configuration whose module has no `loop_weight_bytes` (every model that runs its
layers once) has nothing to read."""

from _scopes import MATMUL_SCOPES, ms_per_step
from harness import models


def read(ctx):
    need = getattr(models.for_config(ctx["config"]), "loop_weight_bytes", None)
    ms = ms_per_step(ctx, MATMUL_SCOPES)
    if not ms or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], ctx["chips"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
