"""device: the program's gauge `kv_cache_bytes{kind}` summed over its kinds:
what the contiguous cache holds, from the cache's own arrays.  A windowed
model's rings are bounded by the window plus one prefill chunk, so
SmallThinker's cache for 16384 positions reads 0.80 GB where 52 full layers
would hold 1.74."""


def read(ctx):
    by_kind = ctx["after"].get("kv_cache_bytes")
    if not isinstance(by_kind, dict) or not by_kind:
        return None
    return sum(float(v) for v in by_kind.values()) / 1e9 or None
