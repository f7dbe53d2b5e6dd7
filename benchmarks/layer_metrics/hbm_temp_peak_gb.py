"""device: the temporaries' high water, GB: the fullest chip's
`hbm_bytes_peak` less `hbm_account_bytes{owner="resident_idle"}` (what stays
when nothing is in flight).  What `hbm_peak_gb` cannot see: a program whose
temporaries shrink moves this and not the residents."""

from _memory import owner_bytes, peak_bytes


def read(ctx):
    idle, peak = owner_bytes(ctx, "resident_idle"), peak_bytes(ctx)
    return None if idle is None or peak is None else (peak - idle) / 1e9
