"""Always-on in-process spans in a bounded ring buffer.

The cheap first line of latency attribution: every request records a
handful of spans (request → queue_wait → prefill → decode_chunk → emit →
snapshot) into a fixed-capacity deque — no flags, no files, roughly one
``perf_counter`` pair and a dict per span — and ``GET /debug/trace`` (or
``tools/trace_dump.py``) dumps the recent ones as Chrome ``trace_event``
JSON for ``chrome://tracing`` / Perfetto.  When a span points at a phase
worth dissecting, ``--profile-split`` (runtime/profiling.py) remains the
heavyweight XLA-level tool.

Every timestamp in the ring is ``time.perf_counter()`` seconds (converted
to µs in the export); they order and measure correctly within one process
but are not wall-clock.  The ``raw()`` export therefore samples
``(perf_now, wall_now)`` at serve time so a cross-process stitcher (the
router's ``/debug/trace?scope=fleet``) can compute a per-replica offset
and shift every ring onto one wall-clock axis.  Capacity comes from
``--trace-buffer`` / ``DLLAMA_TRACE_BUFFER`` (default 8192 spans ≈ a few
hundred requests); a malformed value warns once and falls back.

``span(name, **args)`` is the one entry point for a block of code: besides
the ring record it enters ``jax.profiler.TraceAnnotation(name, **args)``,
so a ``jax.profiler`` trace shows the same spans, with the same arguments,
on the profiler's clock beside the device's ops (a flag test when no
profiler session is active; skipped in a process that never loaded JAX).
A call site may also name a counter cell (``total=``, e.g.
``obs.metrics.host_ms("h2d", "decode")``): it receives the same
``t1 - t0`` the ring record holds, less what a second cell (``less=``)
received meanwhile, so a parent's counter can keep its self time.  One
measurement feeds ring, profiler and counter; they cannot disagree.
``record(name, t0, t1)`` stays for spans that are not one block of code
(a pipelined dispatch is enqueued in one scheduler round and lands in the
next); those are in the ring only.  The names are listed in
docs/OBSERVABILITY.md ("Host spans").

Fleet trace context: ``X-Dllama-Trace`` carries one id for a request's
whole life across router hops and DLREQ01 migrations.  The id rides a
contextvar for the accepting thread plus a bounded rid→trace map
(``set_trace``/``trace_of``) for threads that work on behalf of another
request (the scheduler loop stamps spans with an explicit ``rid``, and
the map resolves those to the trace id without touching call sites).
"""

from __future__ import annotations

import contextvars
import os
import re
import sys
import threading
import time
import uuid
from collections import OrderedDict, deque

from .log import get_logger, request_id_var

_log = get_logger("obs.trace")

DEFAULT_CAPACITY = 8192

_warned_specs: set = set()

# ---------------------------------------------------------------------------
# Fleet trace context (X-Dllama-Trace)
# ---------------------------------------------------------------------------

#: header value charset — same shape as request ids so proxies/log greps
#: treat them alike; anything else is stripped at the trust boundary.
_TRACE_RE = re.compile(r"[^A-Za-z0-9._-]")
_TRACE_MAX = 64

#: ambient trace id for the thread/task that accepted the request.
trace_id_var: contextvars.ContextVar = contextvars.ContextVar(
    "dllama_trace_id", default=None)

#: rid → trace id, bounded LRU so abandoned requests can't grow it.
_RID_TRACE_CAP = 4096
_rid_trace: OrderedDict = OrderedDict()
_rid_trace_lock = threading.Lock()


def new_trace_id() -> str:
    """A fresh 32-hex trace id (uuid4, no dashes) — traceparent-sized."""
    return uuid.uuid4().hex


def sanitize_trace_id(raw: str | None) -> str | None:
    """Clamp an untrusted header value to the id charset; None if empty."""
    if not raw:
        return None
    return _TRACE_RE.sub("", raw)[:_TRACE_MAX] or None


def set_trace(rid: str | None, trace_id: str | None) -> None:
    """Associate a request id with a trace id (LRU-bounded)."""
    if not rid or not trace_id:
        return
    with _rid_trace_lock:
        _rid_trace[rid] = trace_id
        _rid_trace.move_to_end(rid)
        while len(_rid_trace) > _RID_TRACE_CAP:
            _rid_trace.popitem(last=False)


def trace_of(rid: str | None) -> str | None:
    """The trace id associated with ``rid`` (or None)."""
    if not rid:
        return None
    with _rid_trace_lock:
        return _rid_trace.get(rid)


def parse_buffer_env(var: str, default: int) -> int:
    """Ring capacity from ``var``; a value that is not a positive integer
    logs one warning per distinct spec and falls back to ``default`` —
    never raises (the buffer size must not be able to take the server
    down)."""
    spec = os.environ.get(var)
    if spec is None or spec == "":
        return default
    try:
        cap = int(spec)
        if cap < 1:
            raise ValueError(spec)
        return cap
    except ValueError:
        key = (var, spec)
        if key not in _warned_specs:
            _warned_specs.add(key)
            _log.warning("%s=%r is not a positive integer; using default %d",
                         var, spec, default)
        return default


def _capacity() -> int:
    return parse_buffer_env("DLLAMA_TRACE_BUFFER", DEFAULT_CAPACITY)


def _profiler_value(v):
    """A span argument as the profiler keeps it: numbers and strings as
    they are, a list joined with ``;`` (the annotation's encoding ends a
    value at a comma)."""
    if isinstance(v, (list, tuple, set)):
        return ";".join(str(x) for x in v)
    return v if isinstance(v, (int, float, str)) else str(v)


def _profiler_args(args: dict, skip=()) -> dict:
    return {k: _profiler_value(v) for k, v in args.items()
            if v is not None and k not in skip}


def _annotation(name: str, rid, args: dict):
    """An entered ``jax.profiler.TraceAnnotation`` for a span, or None
    when no profiler session is active (one flag test) or the process has
    not loaded JAX (the router, the smoke test's parent): a span never
    imports it."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return None
    kw = _profiler_args(args)
    if rid is not None:
        kw["rid"] = str(rid)
    ann = jax.profiler.TraceAnnotation(name, **kw)
    ann.__enter__()
    return ann


class SpanArgs(dict):
    """A span's arguments as its block sees them.  What the block adds (a
    shape decided half-way) reaches the ring and the profiler; ``total``
    is the counter cell the duration goes to, which the block may also
    name late (a step's kind is decided while it is built).  ``late(**kw)``
    adds arguments AFTER the block has closed, to the ring's record alone
    (the profiler's annotation has ended): what a span started and only a
    later edge can read, e.g. the allocator's peak once a launch has landed
    (``engine.compile``'s ``hbm_peak_after``)."""

    __slots__ = ("total", "_ring")

    def late(self, **kw) -> None:
        self.update(kw)
        ring = getattr(self, "_ring", None)
        if ring is not None:
            ring.update(kw)


class Span:
    """One timed block: ``with span(name, **args) as args``.  On exit the
    one ``t1 - t0`` goes to the ring, to the profiler's annotation (entered
    first, so it encloses the same block) and, where the call site named
    one, to a counter cell: anything with ``add(seconds)``.  ``less`` is a
    cell with ``received`` (seconds so far): what it took in while the
    block ran is left out of ``total``, which then holds the block's self
    time (``sched.admit`` less the ``sched.evict`` inside it)."""

    __slots__ = ("_tracer", "_name", "_rid", "_args", "_less", "_less0",
                 "_ann", "_known", "_t0")

    def __init__(self, tracer, name: str, rid, total, less, args: dict):
        self._tracer, self._name, self._rid = tracer, name, rid
        self._args = SpanArgs(args)
        self._args.total = total
        self._less = less

    def __enter__(self) -> SpanArgs:
        args = self._args
        self._ann = _annotation(self._name, self._rid, args)
        self._known = tuple(args) if self._ann is not None else ()
        if self._less is not None:
            self._less0 = self._less.received
        self._t0 = time.perf_counter()
        return args

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        args, ann = self._args, self._ann
        if ann is not None:
            late = _profiler_args(args, skip=self._known)
            if late:
                ann.set_metadata(**late)
            ann.__exit__(None, None, None)
        rec = self._tracer.put(self._name, self._t0, t1, self._rid, dict(args))
        args._ring, dur = rec["args"], rec["dur"]
        if args.total is not None:
            if self._less is not None:
                dur = max(dur - (self._less.received - self._less0), 0.0)
            args.total.add(dur)
        return False


class Tracer:
    """Lock + ring buffer of completed spans (dicts)."""

    def __init__(self, capacity: int | None = None):
        self._lock = threading.Lock()
        self._spans = deque(maxlen=capacity or _capacity())
        self._seq = 0

    def record(self, name: str, t0: float, t1: float, rid=None,
               **args) -> float:
        """Record a completed span; ``t0``/``t1`` are perf_counter secs.
        Returns the duration it stored.
        ``rid`` overrides the ambient contextvar request ID — threads that
        work on behalf of another request (the scheduler loop) stamp the
        ticket's ID explicitly.  The span's fleet trace id resolves from
        the rid→trace map first, then the ambient contextvar."""
        return self.put(name, t0, t1, rid, args)["dur"]

    def put(self, name: str, t0: float, t1: float, rid, args: dict) -> dict:
        """:meth:`record` with the arguments as a dict the ring keeps, and
        the ring's record returned (``Span`` adds late arguments to it)."""
        th = threading.current_thread()
        rid = rid if rid is not None else request_id_var.get()
        trace = trace_of(rid) or trace_id_var.get()
        dur = max(t1 - t0, 0.0)
        span = {"name": name, "ts": t0, "dur": dur,
                "tid": th.ident or 0, "thread": th.name,
                "rid": rid, "trace": trace, "args": args}
        with self._lock:
            self._seq += 1
            span["seq"] = self._seq
            self._spans.append(span)
        return span

    def resize(self, capacity: int) -> None:
        """Re-bound the ring, keeping the most recent spans that fit."""
        with self._lock:
            self._spans = deque(self._spans, maxlen=max(1, int(capacity)))

    @property
    def capacity(self) -> int:
        return self._spans.maxlen or 0

    def span(self, name: str, rid=None, total=None, less=None, **args):
        """Time the enclosed block into the ring and, under an active
        ``jax.profiler`` session, into the profiler's host plane; see
        :class:`Span`."""
        return Span(self, name, rid, total, less, args)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def raw(self, since: int | None = None) -> dict:
        """Machine-oriented export for incremental polling and fleet
        stitching: spans with their ring sequence numbers (only those
        after ``since`` when given), the cursor to pass next time, and a
        paired ``(perf_now, wall_now)`` clock sample so a cross-process
        consumer can map perf_counter timestamps to wall-clock."""
        with self._lock:
            spans = [dict(s) for s in self._spans
                     if since is None or s.get("seq", 0) > since]
            next_seq = self._seq
        return {"spans": spans, "next_seq": next_seq,
                "capacity": self.capacity,
                "perf_now": time.perf_counter(), "wall_now": time.time()}

    def trace_events(self, last_requests: int | None = None) -> list[dict]:
        """Chrome ``trace_event`` array; optionally only the spans of the
        last N distinct request IDs (id-less spans always kept)."""
        spans = self.snapshot()
        if last_requests is not None:
            keep, order = set(), 0
            for s in reversed(spans):
                rid = s["rid"]
                if rid is not None and rid not in keep:
                    if order >= last_requests:
                        continue
                    keep.add(rid)
                    order += 1
            spans = [s for s in spans if s["rid"] is None or s["rid"] in keep]

        tids, names = {}, {}
        for s in spans:
            if s["tid"] not in tids:
                tids[s["tid"]] = len(tids) + 1
                names[s["tid"]] = s["thread"]

        events = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": t,
                   "args": {"name": f"{names[raw]} ({raw})"}}
                  for raw, t in tids.items()]
        for s in spans:
            args = dict(s["args"])
            if s["rid"]:
                args["request_id"] = s["rid"]
            if s.get("trace"):
                args["trace_id"] = s["trace"]
            events.append({"name": s["name"], "cat": "dllama", "ph": "X",
                           "ts": round(s["ts"] * 1e6, 3),
                           "dur": round(s["dur"] * 1e6, 3),
                           "pid": 1, "tid": tids[s["tid"]], "args": args})
        return events

    def trace_json(self, last_requests: int | None = None) -> dict:
        return {"traceEvents": self.trace_events(last_requests),
                "displayTimeUnit": "ms"}


#: THE process-global tracer.
TRACER = Tracer()


def record(name: str, t0: float, t1: float, rid=None, **args) -> None:
    TRACER.record(name, t0, t1, rid=rid, **args)


def record_ending_now(name: str, dur_s: float, rid=None, **args) -> None:
    """Record a span of ``dur_s`` seconds that ends now, on the ring's
    clock: for a caller whose start instant is on another clock (a
    ticket's ``time.monotonic()`` deadline arithmetic)."""
    t1 = time.perf_counter()
    TRACER.record(name, t1 - max(dur_s, 0.0), t1, rid=rid, **args)


def configure(capacity: int | None = None) -> None:
    """Apply a CLI-chosen capacity (``--trace-buffer``) after import."""
    if capacity is not None:
        TRACER.resize(capacity)


def span(name: str, rid=None, total=None, less=None, **args):
    return TRACER.span(name, rid=rid, total=total, less=less, **args)


def trace_json(last_requests: int | None = None) -> dict:
    return TRACER.trace_json(last_requests)


def raw(since: int | None = None) -> dict:
    return TRACER.raw(since)


def clear() -> None:
    TRACER.clear()
