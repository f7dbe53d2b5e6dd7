"""The prefill calls of the traced window: what each took and what it fed.

``calls(ctx)`` is ``[(seconds, first position, prompt tokens)]``, one a call
of the one-stream engine's prefill program that began and ended inside the
trace.  A prompt longer than one prefill chunk is several calls, each a host
span ``engine.prefill_chunk`` whose ``k`` is the tokens it fed and ``pos``
where they start; where the trace holds such spans they are the calls (the
whole prompt's ``engine.prefill`` span is seconds long and has mostly begun
before the trace did).  A shorter prompt is one ``engine.prefill`` span, the
span `prefill_span_p50_ms` reads, whose ``k`` is the rows of its bucket,
padding included: its tokens are the load generator's ``n_prompt`` of the
request it served (the requests sent and first answered inside the traced
window, in order; their mean where the two counts differ at the window's
edges), less the ``pos`` positions the engine kept from the turn before.
Empty where the trace has no device plane or the program no such span."""

from __future__ import annotations

import os

from _scopes import OUT
from harness import xmeta, xplane

WHOLE, CHUNK = "engine.prefill", "engine.prefill_chunk"
_CACHE: dict[tuple, list] = {}


def _spans(ctx: dict) -> list[tuple[str, int, int, dict]]:
    """``(name, start_ns, dur_ns, stats)`` of both kinds of span, by start."""
    if not ctx["trace"]["chips"]:
        return []
    try:
        path = xplane.find_xplane(OUT)
    except FileNotFoundError:
        return []
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        trace = xmeta.load(path, keep_host=lambda name: name in (WHOLE, CHUNK))
        _CACHE[key] = sorted(((name, t0, dur, stats) for _, name, t0, dur, stats
                              in trace["host"]), key=lambda s: s[1])
    return _CACHE[key]


def calls(ctx: dict) -> list[tuple[float, int, int]]:
    spans = _spans(ctx)
    chunks = [(dur / 1e9, int(st.get("pos") or 0), int(st["k"]))
              for name, _, dur, st in spans
              if name == CHUNK and int(st.get("k") or 0) > 0]
    if chunks:
        return chunks
    whole = [(dur / 1e9, int(st.get("pos") or 0), int(st.get("k") or 0))
             for name, _, dur, st in spans if name == WHOLE]
    lo, hi = ctx["traced_window"]
    sent = [r["n_prompt"] for r in sorted(
        (r for r in ctx["records"] if r["times"] and r.get("sent", 0) >= lo
         and r["times"][0] < hi), key=lambda r: r["sent"])]
    if not whole or not sent:
        return []
    if len(sent) != len(whole):
        sent = [sum(sent) / len(sent)] * len(whole)
    return [(s, pos, max(1, min(rows, round(n - pos))))
            for (s, pos, rows), n in zip(whole, sent)]
