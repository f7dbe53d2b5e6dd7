"""kernels: executions of device ops under the `moe` scope per scheduler step,
per chip (the table's `runs`: kernel calls, the scan's loop and its own
bookkeeping ops, fusions of the router and the combine).  The launch count a
grouped expert path has to bring down: the masked scan makes three kernel
calls an expert a layer whatever the rows hit."""

from _scopes import scoped, table


def read(ctx):
    tab = table(ctx)
    runs = tab["scopes"].get("moe", {}).get("runs") if scoped(tab) else None
    if not runs or not tab["steps"]:
        return None
    return runs / tab["chips"] / tab["steps"]
