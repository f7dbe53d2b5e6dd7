"""`pallas_path_pct` in a served cell: the same reader under a name that moves
`serve_tok_s` (a per-layer metric is reported where the metric it moves is)."""

from pallas_path_pct import read  # noqa: F401
