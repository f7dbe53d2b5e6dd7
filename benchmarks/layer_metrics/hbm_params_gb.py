"""device: the placed parameters on the fullest chip
(`hbm_account_bytes{owner="params"}`, the gauge `param_bytes_resident`, GB):
to be read beside the model file's size a chip."""

from _memory import owner_gb


def read(ctx):
    return owner_gb(ctx, "params")
