"""Device time by sub-name: the program splits some scopes further
(``dllama_tpu/ops/scopes.py PARTS``: ``attn/latent``, ``attn/absorb``,
``attn/expand``, ``qkv/q_lora`` ...), plain components of an op's ``tf_op``
path after the scope's own.  ``_scopes.py`` knows scopes only, so a reader that
needs a part's time reads op paths here: an op counts for ``(scope, part)``
where ``scope`` is the last component of its path that is a scope and ``part``
is a later component.  Own time (``xplane.self_times``), averaged over the
chips of the trace, per scheduler step, like ``_scopes.ms_per_step``.  ``None``
where the trace has no device plane, the program names no ops, or none of the
ops carries the part (a program without it, as the parent of the PR that
added it)."""

from __future__ import annotations

import os

from _scopes import OUT, SCOPES, scoped, table
from harness import xmeta, xplane

_SECONDS: dict[tuple, dict] = {}


def _by_part(path: str) -> dict[tuple[str, str], float]:
    """Seconds of own time for every (scope, later path component)."""
    key = (path, os.path.getmtime(path))
    if key in _SECONDS:
        return _SECONDS[key]
    trace = xmeta.load(path, keep_host=lambda name: False)
    n = max(len(trace["devices"]), 1)
    out: dict[tuple[str, str], float] = {}
    for dev in trace["devices"].values():
        for mid, own in xmeta.own_times(dev):
            comps = (dev["meta"][mid].get("tf_op") or "").rstrip(":").split("/")
            at = max((i for i, c in enumerate(comps) if c in SCOPES), default=None)
            if at is None:
                continue
            for c in set(comps[at + 1:]):
                out[(comps[at], c)] = out.get((comps[at], c), 0.0) + own / 1e9 / n
    _SECONDS[key] = out
    return out


def part_ms_per_step(ctx: dict, scope: str, parts) -> float | None:
    tab = table(ctx)
    if not scoped(tab) or not tab["steps"]:
        return None
    try:
        by = _by_part(xplane.find_xplane(OUT))
    except FileNotFoundError:
        return None
    secs = sum(by.get((scope, p), 0.0) for p in parts)
    return secs * 1e3 / tab["steps"] if secs else None
