"""kernel choice: share of the dispatch ledger's matmul and attention call
sites (``matmul_dispatch``, recorded once per compiled call site) that resolved
to a Pallas kernel.  One device takes the Q40 kernel at any row count, so a
clean one-chip run reads 100; a mesh still sends more than 128 rows to XLA
(``q40/xla-dequant``), as does the gather attention of a served chunk step, so
those cells read below 100 on a clean run.  It drops when a decode site
degrades."""

PALLAS = ("pallas-fused", "paged-fused", "tp_fused_reduce")


def read(ctx):
    sites = {k: v for k, v in (ctx["after"].get("matmul_dispatch") or {}).items()
             if k.split("/")[0] in ("q40", "q8") or k.startswith("kv_")}
    n = sum(sites.values())
    if not n:
        return None
    return 100.0 * sum(v for k, v in sites.items() if k.split("/")[1] in PALLAS) / n
