"""Profiler-derived compute-vs-collective attribution.

The reference's headline benchmark splits per-token time into I (inference)
and T (transfer) using task-type wall-clock accounting in its scheduler
(utils.cpp:189-192, printed at dllama.cpp:77-93).  On a TPU mesh the
inter-chip hops are XLA collectives *inside* the compiled program, so the
equivalent split needs the XLA profiler: this module traces a few engine
steps with ``jax.profiler`` and classifies device-op time into collective
vs compute from the xplane proto (SURVEY §5-tracing prescribes exactly
this profiler-derived attribution).

The heavy imports (tensorflow's xplane proto) happen lazily — profiling is
an opt-in diagnostic (`dllama inference --profile-split`), not a hot-path
dependency; without the proto available the caller gets ``None``.
"""

from __future__ import annotations

import glob
import re
import tempfile
from typing import Callable

# XLA HLO collective primitives (the ICI traffic the reference counts as T)
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
# HLO op names are lowercase dotted/dashed identifiers (fusion.3, dot.1,
# dynamic-update-slice); runtime/host events (Rendezvous, PjitFunction(...),
# "Wait: ...") are not op time and are excluded.
_HLO_NAME = re.compile(r"^[a-z][a-z0-9_.\-]*$")
# TPU device planes record full HLO instruction strings
# ('%fusion.3 = bf16[...]{...} fusion(...)'); the op name is the lhs.
_HLO_INSTR = re.compile(r"^%([A-Za-z0-9_.\-]+) =")


def _iter_op_events(path: str):
    """Yield (hlo_op_name, duration_ps) from every device plane of one
    xplane file — the shared walk under both the compute/collective split
    and per-op attribution."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # lazy, heavy

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    for plane in xs.planes:
        # TPU op time lives in '/device:TPU:N' planes; the CPU backend logs
        # ops into '/host:CPU'.  Skip pure-metadata planes.
        if not (plane.name.startswith("/device:") or plane.name == "/host:CPU"):
            continue
        md = {m.id: m.name for m in plane.event_metadata.values()}
        lines = plane.lines
        # TPU planes split events into 'XLA Modules' (whole program),
        # 'XLA Ops' (per-op), and 'Async XLA Ops' (a subset); only the
        # per-op line counts, the others would double-book the same time.
        op_lines = [ln for ln in lines if ln.name == "XLA Ops"]
        if op_lines:
            lines = op_lines
        elif plane.name == "/host:CPU":
            # the CPU backend records executed ops on the PjRt client
            # thread line; the 'python' and codegen-pass lines carry
            # host/compiler events whose names (simplification,
            # backend_compile_and_load, …) would otherwise pass the HLO
            # name filter and book compile time as op time
            # match any client-thread naming generation (TfrtCpuClient,
            # XLAPjRtCpuClient, ...)
            lines = [ln for ln in lines if "CpuClient" in ln.name]
        for line in lines:
            for ev in line.events:
                name = md.get(ev.metadata_id, "")
                m = _HLO_INSTR.match(name)
                if m:
                    name = m.group(1)
                elif not _HLO_NAME.match(name):
                    continue
                # control-flow wrappers nest their body ops' events inside
                # their own span on the same line — counting both would
                # double-book every loop body
                if name.split(".")[0] in ("while", "conditional", "call"):
                    continue
                yield name, ev.duration_ps


def op_times(trace_dir: str) -> dict[str, float]:
    """Sum device-plane op durations (ms) by op name over a trace dir."""
    times: dict[str, float] = {}
    for path in glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True):
        for name, ps in _iter_op_events(path):
            times[name] = times.get(name, 0.0) + ps / 1e9
    return times


def _parse_xspace(path: str) -> tuple[float, float]:
    """Returns (compute_ms, collective_ms) summed over all device planes."""
    compute_ps = 0
    collective_ps = 0
    for name, ps in _iter_op_events(path):
        if _COLLECTIVE.search(name):
            collective_ps += ps
        else:
            compute_ps += ps
    return compute_ps / 1e9, collective_ps / 1e9


def traced_op_times(step: Callable[[], None], steps: int = 1) -> dict[str, float] | None:
    """Trace ``steps`` calls of ``step()`` and return per-op device time
    (ms, summed over the calls and over every device in the mesh), or
    ``None`` when the xplane proto tooling is unavailable or the backend
    produced no trace files."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: F401
    except Exception:
        return None
    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            for _ in range(steps):
                step()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(d + "/**/*.xplane.pb", recursive=True)
        if not files:
            return None
        # an empty dict means the plane/line naming assumptions missed —
        # report unavailable rather than a plausible-looking zero split
        return op_times(d) or None


def split_op_times(times: dict[str, float]) -> tuple[float, float]:
    """Classify per-op times into (compute_ms, collective_ms) — the single
    home of the I/T classification behind the CLI's --profile-split and the
    server's /debug/profile."""
    compute = sum(ms for op, ms in times.items() if not _COLLECTIVE.search(op))
    collective = sum(ms for op, ms in times.items() if _COLLECTIVE.search(op))
    return compute, collective


def summarize_split(times: dict[str, float], steps: int = 1) -> dict:
    """Per-step compute/collective summary of a per-op times dict — the
    single home of the averaging and percentage math (used by
    :func:`profiled_split`, the CLI's --profile-split and /debug/profile)."""
    compute_ms, collective_ms = split_op_times(times)
    compute_ms /= steps
    collective_ms /= steps
    total = compute_ms + collective_ms
    return {
        "compute_ms": compute_ms,
        "collective_ms": collective_ms,
        "collective_pct": 100.0 * collective_ms / total if total > 0 else 0.0,
    }


def top_ops(times: dict[str, float], k: int = 10,
            steps: int = 1) -> list[tuple[str, float]]:
    """The top-``k`` ops by device time as ``(name, per-step ms)`` — the
    one sort shared by the CLI's ``--profile-ops`` report and the
    server's ``POST /debug/profile``."""
    ranked = sorted(times.items(), key=lambda kv: -kv[1])[:k]
    return [(op, ms / steps) for op, ms in ranked]


def profiled_split(step: Callable[[], None], steps: int = 3) -> dict | None:
    """Trace ``steps`` calls of ``step()`` and attribute device-op time.

    Returns ``{"compute_ms", "collective_ms", "collective_pct"}`` with the
    ms values per step summed across every device in the mesh (divide by
    the device count for a per-chip figure), or ``None`` when the xplane
    proto tooling is unavailable.
    """
    times = traced_op_times(step, steps)
    if times is None:
        return None
    return summarize_split(times, steps)
