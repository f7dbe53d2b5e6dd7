"""device: what the process already held on the fullest chip when the engine
began to place parameters (`hbm_account_bytes{owner="found"}`, GB): under the
harness, whatever its in-process float32 reference left resident.  It is inside
the peak and no other counter shows it."""

from _memory import owner_gb


def read(ctx):
    return owner_gb(ctx, "found")
