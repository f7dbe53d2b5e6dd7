"""kernels: the short-convolution operator's share of the HBM roof in a served
cell.  The bytes one step of `slots_busy_mean` rows needs from the conv layers
(`models/<name>.py conv_bytes`: the packed `W_in` and `W_out` and the taps
once, the state rows of each row) over the peak bandwidth (harness/peaks.py),
over the device time under the part `conv` per scheduler step
(`serve_conv_ms_per_step`).  A mixed step's chunk rows multiply sixteen times
as much for the same weights, so a window with more mixed steps reads lower."""

import serve_conv_ms_per_step
import slots_busy_mean
from harness import models


def read(ctx):
    ms, rows = serve_conv_ms_per_step.read(ctx), slots_busy_mean.read(ctx)
    need = getattr(models.for_config(ctx["config"]), "conv_bytes", None)
    if not ms or not rows or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], ctx["chips"], rows) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
