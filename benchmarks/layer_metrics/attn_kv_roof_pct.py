"""kernels: the attention reads' share of the HBM roof.  The live keys and
values one decoded token must read at the traced window's mean context
(`models/<name>.py kv_read_bytes`: all of them in a full layer, the last
`sliding_window_size` in a window layer) over the peak bandwidth
(harness/peaks.py), over the device time under scope `attn` in the decode
programs a token.  Memory-bound: a query row's scores are 2 FLOP a cached
byte."""

from _common import traced_tokens
from _decode import seconds
from harness import models
from serve_mla_latent_roof_pct import _mean_context as mean_context


def read(ctx):
    secs, toks, context = seconds(ctx), traced_tokens(ctx), mean_context(ctx)
    need = getattr(models.for_config(ctx["config"]), "kv_read_bytes", None)
    if not secs or not toks or not context or need is None \
            or ctx["peaks"] is None or not secs.get(("attn", "")):
        return None
    floor_s = need(ctx["config"], context, ctx["chips"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (secs[("attn", "")] / toks)
