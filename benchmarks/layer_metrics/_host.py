"""What the readers of the host's phases and of the start-up share.

The program splits the host's part of a served step by phase and step kind
(``sched_host_ms{phase,kind}``, JSON keys ``h2d/decode``; each cell receives
the duration of the span of the same name: ``sched.admit`` less the
``sched.evict`` inside it, ``sched.evict``, ``sched.build``, ``engine.h2d``,
``engine.launch``, ``sched.fanout``, ``sched.verdict`` are work;
``sched.land_wait`` is the host waiting for the device; ``compile`` is an
``engine.launch`` during which JAX traced or compiled, which no reader
counts) and counts what JAX says of
its compiles (``compile_cache_requests``, ``_hits``, ``_writes``,
``backend_compile_seconds``, ``compile_cache_retrieval_seconds``,
``jaxpr_trace_seconds``) and of its load (``engine_load_seconds{phase}``).
Counter readers take the whole window (``after`` - ``before``) over the steps
landed in it; ``benchmarks/tools/host_phases.py`` prints the same split by
kind from a run's ``out/<cell>.metrics-after.json``.  The start-up readers
take ``before``, the window's first instant.

Span readers charge the device's idle time to the innermost span
(``_scopes.table``) per ``sched.enqueue`` of the traced window.  **They say
where the chip waits, not for how long**: a traced window runs under the
profiler's Python tracer, which multiplies the host's time (PERF.md section 5:
``engine.h2d`` by 1.8, the pure-Python walk of ``sched.evict`` by 18), so
their values are inflated and step with any change to how many Python calls a
span costs.  The figure a ``perf_opt`` must move is the whole-window counter
of the same phase (``serve_host_evict_ms_per_step``,
``serve_host_h2d_ms_per_step``, ``serve_host_launch_ms_per_step``).

Every reader is ``None`` on a program without the counter or the spans (the
parent of the PR that added them)."""

from __future__ import annotations

from _common import delta
from _scopes import _kinds, table

WORK = ("admit", "evict", "build", "h2d", "launch", "fanout", "verdict")
WAIT = "land_wait"


def phase_ms_per_step(ctx: dict, *phases: str) -> float | None:
    """Milliseconds a landed step of ``phases``, all kinds together."""
    ms, steps = _kinds(ctx, "sched_host_ms"), delta(ctx, "sched_steps")
    if not ms or not steps:
        return None
    return sum(v for k, v in ms.items() if k.split("/")[0] in phases) / steps


def idle_ms_per_step(ctx: dict, names) -> float | None:
    """Device idle milliseconds a step whose innermost program span is one of
    ``names``; ``None`` on a program that has no ``engine.launch`` span (its
    ``engine.slot_enqueue`` is one opaque block)."""
    tab = table(ctx)
    if not tab or not tab["steps"] or "engine.launch" not in tab["spans"]:
        return None
    return 1e3 * sum(tab["idle_in_span_s"].get(n, 0.0) for n in names) \
        / tab["steps"]


def at_start(ctx: dict, key: str):
    """A counter of the program as the window's first instant had it."""
    return ctx["before"].get(key)
