"""Device time of the one-stream DECODE programs alone, by scope and by
sub-name; and the host spans of a chunked prompt's calls.

A one-stream cell's traced window holds two kinds of program: the prefill
step (``Engine._step``) and the decode chunk (``runtime/decode_loop.py``),
which alone samples on the device.  So a program is a decode program where any
of its ops carries scope ``sample``, whatever the programs are called.
``seconds(ctx)`` maps ``(scope, part)`` to own seconds summed over the decode
programs' ops and averaged over the chips: ``part`` is ``""`` for the scope's
whole time, else a later component of the op's path (``ops/scopes.py PARTS``:
``attn/window``, ``moe/experts`` ...).  ``None`` where the trace has no device
plane or no program samples (a program without scopes: the parent of the PR
that added them).

``chunk_spans(ctx)`` is ``[(seconds, prompt tokens)]`` of the program's
``engine.prefill_chunk`` spans (``Engine._prefill_chunked``: one call of a
prompt longer than one prefill chunk, waited for; its ``k`` real tokens of
``rows`` padded ones) that began and ended inside the trace; empty where the
program has no such span.  Both come from one parse of the trace."""

from __future__ import annotations

import os

from _scopes import OUT, SCOPES
from harness import xmeta, xplane

CHUNK_SPAN = "engine.prefill_chunk"
_CACHE: dict[tuple, tuple] = {}


def _parsed(ctx: dict) -> tuple:
    """``(seconds, chunk_spans)`` of the run's trace, parsed once."""
    if not ctx["trace"]["chips"]:
        return None, []
    try:
        path = xplane.find_xplane(OUT)
    except FileNotFoundError:
        return None, []
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        trace = xmeta.load(path, keep_host=lambda name: name == CHUNK_SPAN)
        n = max(len(trace["devices"]), 1)
        secs: dict[tuple[str, str], float] = {}
        for dev in trace["devices"].values():
            rows = []
            for mid, own in xmeta.own_times(dev):
                meta = dev["meta"][mid]
                comps = (meta.get("tf_op") or "").rstrip(":").split("/")
                at = max((i for i, c in enumerate(comps) if c in SCOPES),
                         default=None)
                if at is not None:
                    rows.append((meta.get("program_id"), comps[at],
                                 set(comps[at + 1:]), own))
            decode = {pid for pid, scope, _, _ in rows if scope == "sample"}
            for pid, scope, parts, own in rows:
                if pid in decode:
                    for p in parts | {""}:
                        secs[(scope, p)] = secs.get((scope, p), 0.0) + own / 1e9 / n
        chunks = [(dur / 1e9, int(stats["k"]))
                  for _, _, _, dur, stats in trace["host"]
                  if int(stats.get("k") or 0) > 0]
        _CACHE[key] = (secs or None, chunks)
    return _CACHE[key]


def seconds(ctx: dict) -> dict | None:
    return _parsed(ctx)[0]


def chunk_spans(ctx: dict) -> list[tuple[float, int]]:
    return _parsed(ctx)[1]
