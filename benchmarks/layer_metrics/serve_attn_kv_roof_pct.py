"""kernels: the attention reads' share of the HBM roof in a served cell.  The
live keys and values one step of `slots_busy_mean` rows must read, each row at
the traced window's mean live context (`models/<name>.py kv_read_bytes`: every
live position in a full layer, the last `sliding_window` in a window layer)
over the peak bandwidth (harness/peaks.py), over the device time under scope
`attn` per scheduler step: the page walk's and the ring read's share of their
roofline, whatever implements them.  Memory-bound: a query row's scores are 2
FLOP a cached byte.  A mixed step's chunk rows read what a decode row reads and
score sixteen times as much, so a window with more mixed steps reads lower."""

import slots_busy_mean
from _scopes import ms_per_step
from harness import models
from serve_mla_latent_roof_pct import _mean_context as mean_context


def read(ctx):
    ms, rows, context = ms_per_step(ctx, ["attn"]), slots_busy_mean.read(ctx), \
        mean_context(ctx)
    need = getattr(models.for_config(ctx["config"]), "kv_read_bytes", None)
    if not ms or not rows or not context or need is None or ctx["peaks"] is None:
        return None
    floor_s = need(ctx["config"], context, ctx["chips"], rows=rows) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
