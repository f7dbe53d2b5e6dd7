"""engine: the high water of the process's resident set, GB
(`host_rss_peak_bytes`, `VmHWM` of `/proc/self/status`).  The process is the
harness's: the model file's synthesis and the in-process reference of a
checkout's first run are inside it, which is what a watchdog on the process
sees."""


def read(ctx):
    peak = ctx["after"].get("host_rss_peak_bytes")
    return None if peak is None else float(peak) / 1e9
