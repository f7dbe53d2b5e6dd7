"""kernels: the `moe` scope's share of the device's busy time in the traced
window (the table's `share_pct`).  The cell exists to keep the experts the
largest scope; `by-scope.json` has every scope's share beside it."""

from _scopes import scoped, table


def read(ctx):
    tab = table(ctx)
    if not scoped(tab):
        return None
    return tab["scopes"].get("moe", {}).get("share_pct") or None
