"""Unified observability for the serving stack (docs/OBSERVABILITY.md).

Stdlib-only building blocks, threaded through every layer:

* :mod:`.metrics` — THE process-global registry of counters, gauges and
  fixed-bucket histograms, with two exposition paths from the one
  registry: the backward-compatible ``/metrics`` JSON dict and
  Prometheus text format 0.0.4.
* :mod:`.log` — structured logging (JSON lines or human format) with a
  contextvar-carried request ID stamped on every record, so one grep of
  the server log reconstructs a request's full lifecycle across server,
  engine, fault and snapshot code.
* :mod:`.trace` — lightweight always-on in-process spans in a bounded
  ring buffer, dumpable as Chrome ``trace_event`` JSON (``/debug/trace``
  + ``tools/trace_dump.py``); the cheap first-line latency attribution
  next to the heavyweight XLA tracer (``runtime/profiling.py``).
* :mod:`.dispatch` — the kernel-dispatch ledger: which matmul path every
  weight actually took (pallas-fused / xla-dequant / dense), labeled degrade counters replacing the old warn-once prints,
  and the process-wide ``degraded`` flag that ``/health`` and the
  end-of-run CLI summary surface.
* :mod:`.cost` — the analytic roofline cost model: FLOPs/bytes-moved
  per dispatch family computed from the model config and dispatch shape
  (no device counters), the per-backend peak table behind the
  ``dllama_mfu`` / ``dllama_mbu`` gauges, and per-request chip-time
  attribution feeding the flight recorder's cost block.
* :mod:`.memory` — the memory account: the chip's memory by owner, the
  program that set its peak, the host's resident set by phase, read at
  edges only, and the ``hbm_exhausted`` line of a failing allocation.
* :mod:`.flight` — the request flight recorder (per-request lifecycle
  records keyed by ``X-Request-Id``, served at ``/debug/requests``) and
  the per-dispatch slot timeline behind ``/debug/timeline`` and the
  scheduler goodput decomposition.
* :mod:`.slo` — declarative latency/error objectives with rolling
  multi-window burn rates (``--slo`` / ``DLLAMA_SLO``), feeding
  ``slo_burn_rate`` gauges and the ``/health`` verdict.
* :mod:`.events` — the pod event journal: bounded, monotonically-
  sequenced structured lifecycle events (spawn/respawn/quarantine/
  scale/reshape/hand-off/preempt…), served at ``/debug/events`` with a
  ``?since=<seq>`` cursor and optionally persisted as JSONL
  (``--event-log``).

Nothing here imports jax (or anything beyond the stdlib): the engine,
loaders, and server all import ``obs`` freely with no cycle risk, and a
metric bump on the decode hot path costs one small lock.
"""

from __future__ import annotations

from . import cost, dispatch, events, flight, log, memory, metrics, slo, \
    trace  # noqa: F401
