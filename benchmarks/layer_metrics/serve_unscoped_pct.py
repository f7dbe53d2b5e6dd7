"""`unscoped_pct` in a served cell: the same reader under a name that moves
`serve_itl_p50_ms` (a per-layer metric is reported where the metric it moves is)."""

from unscoped_pct import read  # noqa: F401
