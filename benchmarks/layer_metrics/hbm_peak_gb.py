"""device: ``memory_stats()["peak_bytes_in_use"]``, the fullest chip, in GB.
Memory bounds the page pool and so the batch."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
