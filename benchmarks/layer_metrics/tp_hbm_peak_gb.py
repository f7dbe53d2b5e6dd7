"""`hbm_peak_gb` in a one-stream tp cell: the same reader (the fullest chip's
``peak_bytes_in_use``, GB) under a name that moves `out_tok_s` (a per-layer
metric is reported where the metric it moves is)."""

from hbm_peak_gb import read  # noqa: F401
