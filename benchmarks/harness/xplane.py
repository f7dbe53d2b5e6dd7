"""The reducer: one ``.xplane.pb`` written by ``jax.profiler`` to the numbers
the per-layer metrics and the ``breakdown`` read.  Needs nothing but JAX
(``jax.profiler.ProfileData``).

What a TPU trace looks like (checked by hand on the v5e, PERF.md): one plane
``/device:TPU:<n>`` per chip; its line ``XLA Ops`` holds one event per executed
HLO instruction, named by the instruction text (``%fusion.3 = bf16[...]
fusion(...)``); control-flow instructions (``while``, ``call``, ``conditional``)
span their bodies' events on the same line.  So an op's own time is its
duration minus its children's (``self_ns``), and busy time is the union of all
intervals.  Host threads are the lines of ``/host:CPU``, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re

_INSTR = re.compile(r"^%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]}, "host":
    [(line, name, start_ns, dur_ns), ...], "span_ns": (lo, hi)}``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, seen = {}, [], []
    lo, hi = float("inf"), 0.0
    for plane in data.planes:
        seen.append([plane.name, [ln.name for ln in plane.lines][:40]])
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        is_host = plane.name == "/host:CPU"
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            if is_dev and line.name != OP_LINE:
                continue
            rows = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events]
            if not rows:
                continue
            if is_dev:
                # the window is the devices' own span: host events go on after
                # the device stops recording (stop_trace), and in steady
                # traffic a device is never idle for long at either edge
                lo = min(lo, min(r[1] for r in rows))
                hi = max(hi, max(r[1] + r[2] for r in rows))
                devices.setdefault(plane.name, []).extend(rows)
            else:
                host.extend((line.name, *r) for r in rows if r[2] > 0)
    return {"devices": devices, "host": host, "planes": seen,
            "span_ns": (lo if lo != float("inf") else 0.0, hi)}


def op_name(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an event's text."""
    m = _INSTR.match(text)
    if m:
        return m.group(1), m.group(2)
    name = text.lstrip("%").split(" ")[0]
    return name, name.split(".")[0]


def kind_of(text: str) -> str:
    """``custom_call`` (a Pallas/Mosaic kernel), ``collective`` or ``xla``."""
    name, opcode = op_name(text)
    if opcode == "custom-call" or name.startswith("custom-call") \
            or "tpu_custom_call" in text:
        return "custom_call"
    if _COLLECTIVE.search(opcode) or _COLLECTIVE.search(name):
        return "collective"
    return "xla"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(rows: list[tuple[str, float, float]]) -> list[tuple[str, float]]:
    """(name, own ns) per event of one line: duration minus the events nested
    inside it."""
    order = sorted(rows, key=lambda r: (r[1], -r[2]))
    own = [r[2] for r in order]
    stack: list[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(order[i][0], max(own[i], 0.0)) for i in range(len(order))]


def reduce(trace: dict) -> dict:
    """Busy/idle, time by kind and by op, and the longest idle gaps, all in
    seconds and averaged over the chips in the trace."""
    devs = trace["devices"]
    n = len(devs)
    lo, hi = trace["span_ns"]
    out = {"chips": n, "window_s": (hi - lo) / 1e9, "busy_s": 0.0,
           "custom_call_s": 0.0, "collective_s": 0.0, "xla_s": 0.0,
           "ops": {}, "idle_gaps": []}
    if not n:
        return out
    by_op: dict[str, float] = {}
    first = sorted(devs)[0]
    for plane, rows in devs.items():
        busy = union([(s, s + d) for _, s, d in rows])
        out["busy_s"] += sum(e - s for s, e in busy) / 1e9 / n
        for text, own in self_times(rows):
            out[kind_of(text) + "_s"] += own / 1e9 / n
            name = op_name(text)[0]
            by_op[name] = by_op.get(name, 0.0) + own / 1e9 / n
        if plane == first:
            edges = [(lo, lo)] + busy + [(hi, hi)]
            gaps = sorted(((b[0] - a[1], a[1], b[0])
                           for a, b in zip(edges, edges[1:])), reverse=True)[:10]
            out["idle_gaps"] = [(g, s, e) for g, s, e in gaps if g > 0]
    out["ops"] = by_op
    return out


def attribute_gaps(trace: dict, gaps: list[tuple[float, float, float]],
                   k: int = 5) -> list[list]:
    """``[[what the host was doing, seconds], ...]`` for the ``k`` longest idle
    gaps: the shortest host event that covers at least half of the gap (the
    innermost frame that explains it), or ``unattributed``."""
    out = []
    for gap, s, e in gaps[:k]:
        best = None
        for line, name, hs, hd in trace["host"]:
            cover = min(e, hs + hd) - max(s, hs)
            if cover >= 0.5 * gap and (best is None or hd < best[0]):
                best = (hd, f"{line.split('/')[0] or 'host'}:{name}")
        out.append([best[1][:120] if best else "unattributed", gap / 1e9])
    return out


def top_ops(reduced: dict, k: int = 10) -> list[list]:
    return [[name, secs] for name, secs in
            sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:k]]
