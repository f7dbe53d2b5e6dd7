"""kernels: the short-convolution operator's share of the HBM roof on the
one-stream path.  The bytes one decoded token needs from the conv layers (each
layer's `W_in` and `W_out` as packed Q40, its taps, the state rows read and the
one written: `models/<name>.py conv_bytes` at one row) over the peak bandwidth
(harness/peaks.py), over the device time under the part `conv` in the decode
programs a token (`conv_ms_per_tok`): the work under the part, whatever
implements it.  Memory-bound: two one-row matmuls a layer."""

import conv_ms_per_tok
from harness import models


def read(ctx):
    ms = conv_ms_per_tok.read(ctx)
    need = getattr(models.for_config(ctx["config"]), "conv_bytes", None)
    if not ms or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], ctx["chips"], 1) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
