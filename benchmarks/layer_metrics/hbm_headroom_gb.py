"""device: what the run left of the fullest chip at its peak, GB: the
allocator's `bytes_limit` (`hbm_account_bytes{owner="limit"}`; where it reports
none, the published HBM of `harness/peaks.py`) less `hbm_bytes_peak`.  The
ledger's memory losses are measured against this."""

from _memory import owner_bytes, peak_bytes


def read(ctx):
    peak = peak_bytes(ctx)
    if peak is None or "hbm_account_bytes" not in ctx["after"]:
        return None
    limit = owner_bytes(ctx, "limit")
    if limit is None:
        limit = float(ctx["peaks"]["hbm_bytes"])
    return (limit - peak) / 1e9
