"""`loadgen_late_p95_ms` in a served cell: the same reader under a name that moves
`serve_tok_s` (a per-layer metric is reported where the metric it moves is)."""

from loadgen_late_p95_ms import read  # noqa: F401
