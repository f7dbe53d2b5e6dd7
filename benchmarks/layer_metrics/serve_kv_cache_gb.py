"""`kv_cache_gb` in a served cell: the same reader (the gauge
`kv_cache_bytes{kind}` summed) under a name that moves `serve_itl_p50_ms`.  On
the paged engine `full` is the pool `--kv-pages` counts and `window` a windowed
model's slot rings: K-EXAONE's cell holds 2.44 + 0.19 GB where one pool for all
24 layers would need 9.75."""

from kv_cache_gb import read  # noqa: F401
