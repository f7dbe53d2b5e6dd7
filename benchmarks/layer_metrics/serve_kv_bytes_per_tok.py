"""device: the program's gauge `kv_bytes_per_token`: what one cached token
occupies in the paged pool over all layers, computed by the engine from the
pool's own arrays.  5760 for DeepSeek-V2's 5 layers (576 values x 2 B), where
per-head K and V would be 409600.  It bounds the pool, and so the contexts and
the batch a chip can hold."""


def read(ctx):
    return ctx["after"].get("kv_bytes_per_token") or None
