"""device: share of device busy time in ops that carry no program scope:
compiler-made copies and layout changes, and whatever the program left
unnamed.  Near 100 means the executables came from a compile cache written
before the scopes existed.  The ops behind it are `unscoped_ops` in
`benchmarks/out/by-scope.json`."""

from _scopes import unscoped_pct as read  # noqa: F401
