"""engine: share of the device's idle time (every gap between ops in the
traced window) that lies inside some program span (`engine.*`, `api.*`,
`sched.*`): what the host was doing is then known.  The split by innermost
span is `idle_in_span_s` in `benchmarks/out/by-scope.json`."""

from _scopes import idle_in_span_pct as read  # noqa: F401
