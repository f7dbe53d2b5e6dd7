"""What the readers of the program's own names share: device time by scope,
idle time by program span, steps, and the whole table for PERF.md.

The program wraps each stage of its forward pass in a named scope
(``dllama_tpu/ops/scopes.py SCOPES``; the copy below is the yardstick's, and
``benchmarks/tests/test_xmeta.py`` compares the two) and its host work in
spans that a ``jax.profiler`` trace shows (``sched.*``, ``engine.*``,
``api.*``; docs/OBSERVABILITY.md).  ``table(ctx)`` reads the run's trace
through ``harness/xmeta.py`` once, whichever reader asks first, and writes
``benchmarks/out/by-scope.json``.  It is ``None`` on a trace with no TPU
plane (a rehearsal).  On a program without scopes or spans (the parent of the
PR that added them, or a stale compile cache) every op is ``unscoped`` and
there are no steps: the readers that need them return ``None``.

Definitions.  An op's scope is the last component of its ``tf_op`` path
that is in ``SCOPES``, else ``unscoped``; its time is its own time
(``xplane.self_times``), averaged over the chips of the trace like
``xplane.reduce``.  A step is one ``sched.enqueue`` span that starts inside
the devices' span.  Idle time is the complement of the union of a chip's op
intervals inside the devices' span, averaged over chips; an idle instant is
"inside a span" if any program span on any thread covers it, and is charged
to the innermost one: the covering span of the shortest duration, a span of
the threads that launch device work (``sched.*``, ``engine.*``) before one of
a handler thread that only writes tokens out beside them (``api.*``).
"""

from __future__ import annotations

import bisect
import json
import os

from harness import xmeta, xplane

SCOPES = ("embed", "norm", "qkv", "rope", "kv_write", "page_idx", "attn",
          "wo", "w13", "w1", "w3", "w2", "moe", "head", "sample")
MATMUL_SCOPES = ("qkv", "wo", "w13", "w1", "w3", "w2", "head")
SPAN_PREFIXES = ("sched.", "engine.", "api.")
STEP_SPAN = "sched.enqueue"
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "out")


def is_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES)


def innermost_segments(spans: list[tuple[float, float, str]]
                       ) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the union of ``spans``,
    each named after the shortest span that covers it, ``api.*`` spans
    last."""
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    starts = sorted(spans)
    out, live, k = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= lo:
            live.append(starts[k])
            k += 1
        live = [sp for sp in live if sp[1] > lo]
        if live:
            s, e, name = min(live, key=lambda sp: (sp[2].startswith("api."),
                                                   sp[1] - sp[0]))
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_by_span(gaps: list[tuple[float, float]],
                 segments: list[tuple[float, float, str]]) -> dict[str, float]:
    """Nanoseconds of ``gaps`` inside each named segment."""
    out: dict[str, float] = {}
    seg_starts = [s for s, _, _ in segments]
    for gs, ge in gaps:
        i = max(bisect.bisect_right(seg_starts, gs) - 1, 0)
        while i < len(segments) and segments[i][0] < ge:
            s, e, name = segments[i]
            cover = min(ge, e) - max(gs, s)
            if cover > 0:
                out[name] = out.get(name, 0.0) + cover
            i += 1
    return out


def build(trace: dict) -> dict | None:
    """The table of one ``xmeta.load``; ``None`` without a device plane."""
    devs = trace["devices"]
    n = len(devs)
    if not n:
        return None
    lo = min(s for d in devs.values() for _, s, _ in d["ops"])
    hi = max(s + dur for d in devs.values() for _, s, dur in d["ops"])
    scopes: dict[str, dict] = {}
    programs: dict[str, dict[str, float]] = {}
    ops: dict[tuple, dict] = {}
    busy_ns = idle_ns = 0.0
    idle_in: dict[str, float] = {}
    spans = [(s, s + d, name) for _, name, s, d, _ in trace["host"]
             if s < hi and s + d > lo]
    segments = innermost_segments(spans)
    for dev in devs.values():
        prog_names = dict((pid, name) for name, pid in
                          (xmeta.module_name(m[0]) for m in dev["modules"]))
        for mid, own in xmeta.own_times(dev):
            meta = dev["meta"][mid]
            scope = xmeta.scope_of(meta.get("tf_op"), SCOPES)
            row = scopes.setdefault(scope, {"s": 0.0, "bytes_accessed": 0.0,
                                            "runs": 0})
            row["s"] += own / 1e9 / n
            row["bytes_accessed"] += float(meta.get("bytes_accessed") or 0) / n
            row["runs"] += 1
            prog = prog_names.get(meta.get("program_id"),
                                  str(meta.get("program_id")))
            prog = f"{prog}({meta.get('program_id')})"
            by = programs.setdefault(prog, {})
            by[scope] = by.get(scope, 0.0) + own / 1e9 / n
            op = ops.setdefault((meta["display"] or meta["name"][:60], prog), {
                "scope": scope, "s": 0.0, "runs": 0,
                "hlo_category": meta.get("hlo_category"),
                "tf_op": meta.get("tf_op"), "source": meta.get("source"),
                "bytes_accessed": meta.get("bytes_accessed")})
            op["s"] += own / 1e9 / n
            op["runs"] += 1
        busy = xplane.union([(s, s + d) for _, s, d in dev["ops"]])
        busy_ns += sum(e - s for s, e in busy) / n
        edges = [(lo, lo)] + busy + [(hi, hi)]
        gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
        idle_ns += sum(e - s for s, e in gaps) / n
        for name, ns in idle_by_span(gaps, segments).items():
            idle_in[name] = idle_in.get(name, 0.0) + ns / n
    for row in scopes.values():
        row["share_pct"] = 100.0 * row["s"] * 1e9 / busy_ns if busy_ns else 0.0
    top = sorted(ops.items(), key=lambda kv: -kv[1]["s"])
    return {
        "chips": n, "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
        "idle_s": idle_ns / 1e9,
        "steps": sum(1 for s, _, name in spans
                     if name == STEP_SPAN and lo <= s < hi),
        "spans": {name: sorted(e - s for s, e, nm in spans if nm == name)
                  for name in {nm for _, _, nm in spans}},
        "scopes": scopes, "programs": programs,
        "idle_in_span_s": {k: v / 1e9 for k, v in idle_in.items()},
        "ops": [dict(v, name=k[0], program=k[1]) for k, v in top[:80]],
        "unscoped_ops": [dict(v, name=k[0], program=k[1]) for k, v in top
                         if v["scope"] == "unscoped"][:20],
    }


_TABLES: dict[tuple[str, float], dict | None] = {}


def _kinds(ctx: dict, key: str) -> dict:
    after, before = ctx["after"].get(key) or {}, ctx["before"].get(key) or {}
    return {k: v - before.get(k, 0) for k, v in after.items()}


def table(ctx: dict) -> dict | None:
    """The run's table, or ``None`` (no TPU plane in the trace: a
    rehearsal).  The first reader to ask parses the trace and writes
    ``by-scope.json`` (the table, each span's count and median, and the
    window's step counters by kind); the others get the same object."""
    if not ctx["trace"]["chips"]:
        return None
    try:
        path = xplane.find_xplane(OUT)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key in _TABLES:
        return _TABLES[key]
    tab = _TABLES[key] = build(xmeta.load(path, keep_host=is_span))
    if tab is not None:
        spans = {k: {"n": len(v), "p50_ms": v[len(v) // 2] / 1e6,
                     "total_s": sum(v) / 1e9} for k, v in tab["spans"].items()}
        cell = ctx["cell"]
        with open(os.path.join(OUT, "by-scope.json"), "w") as f:
            json.dump(dict(
                tab, spans=spans, trace=os.path.relpath(path, OUT),
                workload=f"{cell.get('config')}.{cell.get('traffic')}",
                window_counters={k: _kinds(ctx, k) for k in (
                    "sched_steps", "sched_step_wall_ms", "sched_step_time_ms")}),
                f, indent=1)
    return tab


def scope_s(tab: dict, names) -> float:
    return sum(tab["scopes"].get(n, {}).get("s", 0.0) for n in names)


def scoped(tab: dict | None) -> bool:
    """Whether the program named any of its ops (its parent did not)."""
    return bool(tab) and any(k != "unscoped" for k in tab["scopes"])


def ms_per_step(ctx: dict, names) -> float | None:
    tab = table(ctx)
    if not scoped(tab) or not tab["steps"]:
        return None
    return scope_s(tab, names) * 1e3 / tab["steps"]


def unscoped_pct(ctx: dict) -> float | None:
    tab = table(ctx)
    if not tab or not tab["busy_s"]:
        return None
    return 100.0 * scope_s(tab, ["unscoped"]) / tab["busy_s"]


def idle_in_span_pct(ctx: dict) -> float | None:
    tab = table(ctx)
    if not tab or not tab["idle_s"] or not tab["spans"]:
        return None
    return 100.0 * sum(tab["idle_in_span_s"].values()) / tab["idle_s"]


def weight_roof_pct(ctx: dict, scope: str, values_per_layer: float) -> float | None:
    """A layer-stacked Q40 weight's share of the HBM roof: its packed bytes
    a token (18 bytes per 32 values, every layer) over the peak bandwidth,
    over the device time under its scope per token received in the traced
    window (prefill's time under the same scope included, so prefill-heavy
    traffic reads lower)."""
    from _common import traced_tokens
    tab, toks = table(ctx), traced_tokens(ctx)
    secs = scope_s(tab, [scope]) if scoped(tab) else 0.0
    if not secs or not toks or ctx["peaks"] is None:
        return None
    need = values_per_layer * ctx["config"]["num_hidden_layers"] * 18 / 32 \
        / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need * toks / secs
