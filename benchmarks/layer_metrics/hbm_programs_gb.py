"""device: what stays on the fullest chip while nothing is in flight and is
neither found, parameters nor cache (`hbm_account_bytes{owner="programs"}` =
resident_idle - found - params - cache, GB): loaded executables with their
constants, retained outputs, the rest.  A remainder on purpose."""

from _memory import owner_gb


def read(ctx):
    return owner_gb(ctx, "programs")
