"""device: 1 - (union of device op intervals) / traced window, mean over chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["chips"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
