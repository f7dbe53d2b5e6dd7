"""kernels: the least time the chip's memory could take for the decode work of
the traced window, over the time the device was busy in it.  Per output token a
step must read the weights once (shared by the rows decoding together) and the
token's own live context (harness/cost.py, which asks the configuration's
models/<name>.py); peak bandwidth from
harness/peaks.py.  Memory-bound: at these batch sizes the compute bound is far
lower.  Prefill steps stream weights too and count only as busy time, so a cell
with much prefill reads lower."""

from _common import traced_tokens
from harness import cost


def read(ctx):
    toks, tr = traced_tokens(ctx), ctx["trace"]
    if not toks or not tr["busy_s"] or ctx["peaks"] is None:
        return None
    lo, hi = ctx["window"]
    occ = [m.get("sched_slots_occupied") for t, m in ctx["samples"] if lo <= t < hi]
    occ = [v for v in occ if v]
    rows = sum(occ) / len(occ) if occ else 1.0
    good = [r for r in ctx["records"] if r["times"]]
    live = sum(r["n_prompt"] + r["n_out"] / 2 for r in good) / max(len(good), 1)
    per_tok = (cost.weight_bytes(ctx["config"], ctx["chips"], rows) / rows
               + cost.kv_bytes_per_token(ctx["config"], ctx["chips"]) * live)
    return 100.0 * toks * per_tok / ctx["peaks"]["hbm_bytes_per_s"] / tr["busy_s"]
