"""kernels: the one-stream expert path's share of the HBM roof.  The packed Q40
bytes one decoded token needs from the expert layers (every layer's router and
its k chosen experts: `models/<name>.py moe_bytes` at one row) over the peak
bandwidth (harness/peaks.py), over the device time under scope `moe` in the
decode programs a token.  Memory-bound: a chosen expert is 3 matmuls of one
row."""

import moe_select_ms_per_tok
from harness import models


def read(ctx):
    ms = moe_select_ms_per_tok.read(ctx)
    need = getattr(models.for_config(ctx["config"]), "moe_bytes", None)
    if not ms or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], ctx["chips"], 1) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
