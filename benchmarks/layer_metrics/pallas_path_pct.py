"""kernel choice: share of the dispatch ledger's matmul and attention call
sites (``matmul_dispatch``, recorded once per compiled call site) that resolved
to a Pallas kernel.  Prefill sites above 128 rows take XLA by rule, so the
figure is below 100 on a clean run; it drops when a decode site degrades."""

PALLAS = ("pallas-fused", "pallas-blocked", "paged-fused", "tp_fused_reduce")


def read(ctx):
    sites = {k: v for k, v in (ctx["after"].get("matmul_dispatch") or {}).items()
             if k.split("/")[0] in ("q40", "q8") or k.startswith("kv_")}
    n = sum(sites.values())
    if not n:
        return None
    return 100.0 * sum(v for k, v in sites.items() if k.split("/")[1] in PALLAS) / n
