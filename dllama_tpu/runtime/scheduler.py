"""Continuous-batching slot scheduler over the slot-addressable batch
engine.

The server's engine mutex serializes whole *requests*: while one stream
decodes, every other admitted request waits, even though a lockstep batch
step prices B rows at roughly one weight read (runtime/engine.py
``generate_batch_stream``).  Iteration-level scheduling (Orca, OSDI'22;
vLLM's slot form, SOSP'23) moves the admission boundary from the request
to the *decode step*: this scheduler owns the ``--batch-slots`` engine and
drives :meth:`Engine.slot_step` from one daemon thread, admitting a new
request into any free slot between steps and retiring finished ones
without disturbing their neighbors.

Mechanics per dispatch:

* every active slot is either **prefilling** (its prompt feeds in chunks
  of ``--sched-prefill-chunk`` tokens, interleaved with its neighbors'
  decode tokens in the same mixed forward — bounding the inter-token
  latency a join adds to running streams) or **decoding** (feeds its
  previous sample);
* when *no* slot is mid-prefill, decode runs in on-device bursts
  (``steps > 1`` inside one XLA program, decode_chunk's amortization);
  with work waiting in the queue the burst is clamped so a finishing
  stream frees its slot within ``--sched-max-wait-ms``;
* a freed slot is reused by handing its row position 0 again — the
  previous occupant's stale KV sits above the newcomer's causal ceiling
  (ops/attention.py ``slot_gqa_attention_at``), so per-slot reset is
  free and the cache is never zeroed;
* with ``overlap`` (default on) steady-state decode runs as a two-deep
  pipeline: while dispatch N's tokens land and fan out host-side,
  dispatch N+1 is already enqueued on device, fed by N's on-device
  last-token row (``Engine.slot_step_async``'s ``feed_dev`` — no
  device→host→device round trip).  A queued ticket stops the pipeline
  only when it can be served at the next boundary (a free slot, a row
  whose budget runs out in the dispatch in flight, an eviction it may
  ask for, or its own cancel or deadline): behind a full house it
  waits for a slot either way, and the steps it waits through stay
  pipelined.  Every *flush point* — slot retire, cancel/deadline,
  ``exclusive()`` parking, hand-off export/import, drain — falls back
  to synchronous dispatch: the pipelined dispatch is landed and
  discarded, its KV writes sit above every surviving row's position
  (masked by the causal ceiling exactly like slot reuse), and greedy
  output stays byte-identical with overlap on or off;
* with a ``spec`` proposer armed (runtime/spec.py, ``--spec``), each
  greedy decode slot drafts up to ``spec_k`` tokens after a burst
  lands, and the next dispatch is a ragged VERIFY burst
  (``Engine.slot_verify_async``): proposing rows feed their drafts,
  no-proposal rows ride as plain decode steps, and each row emits its
  accepted leading drafts plus one bonus token — all re-derived from
  the target model's own argmax, so greedy output is byte-identical
  with speculation on or off.  Rejection truncates that row only
  (stale KV above its accepted ceiling is slot-reuse garbage), and
  every flush point above drops pending drafts the same way it drops a
  pipelined dispatch: drafts never survive a retire, park, or export.
  Speculation supersedes burst pipelining while armed (a verify
  window's content depends on the previous dispatch's landed tokens,
  so there is nothing token-independent to pipeline); the verify
  burst's multi-token yield is what amortizes the host gap instead.

Each submitted request gets a :class:`Ticket` — a thread-safe token
stream the HTTP handler consumes.  Cancellation (client disconnect, stop
string, deadline) flips a flag the loop honors at the next step
boundary, freeing the slot mid-generation.  A dispatch failure
(StepTimeout, device fault) retires every active slot with the error on
its ticket and the loop keeps serving — the write-before-visible
invariant makes any cache garbage from the failed step unobservable.

Greedy determinism contract: a temperature-0 request produces the same
tokens whichever slot it lands in and whatever its neighbors are doing
(tests/test_scheduler.py pins this).  Sampled requests draw from the
engine's shared counter-based RNG stream, so their draws depend on
co-scheduling — per-request seeds are not reproducible here (use the
mutex path for that); this is the standard continuous-batching trade.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from collections import deque

import numpy as np

from ..models import packing
from ..obs import cost as obs_cost, dispatch as obs_dispatch
from ..obs import events as obs_events, flight as obs_flight
from ..obs import metrics as obs_metrics, trace as obs_trace
from ..obs.log import get_logger, new_request_id, request_id_var
from .faults import FAULTS
from .pagepool import (PagePool, PagePoolExhausted, RadixTree,
                       ring_pages_recycled)

_log = get_logger("runtime.scheduler")

_DONE = object()  # ticket stream terminator

# multi-tenant QoS classes (lower level = more important).  The wire
# names ride the OpenAI surface (body ``priority`` / X-Dllama-Priority);
# the scheduler orders admission by level and preempts strictly
# lower-priority slots for a higher-priority arrival.
PRIORITY_LEVELS = {"interactive": 0, "standard": 1, "batch": 2}
PRIORITY_NAMES = {v: k for k, v in PRIORITY_LEVELS.items()}


class SchedulerClosed(RuntimeError):
    """submit() after begin_drain()/close(): no new work is admitted."""


class SchedulerSaturated(RuntimeError):
    """submit() with the wait queue at its bound (the server maps this to
    429, same as mutex-path admission)."""


class Ticket:
    """One request's handle: a bounded-latency token stream plus the
    finish verdict.  Produced by the scheduler thread, consumed by the
    HTTP handler thread; ``cancel`` may be called from either side."""

    def __init__(self, prompt, max_new, temperature, top_p, eos_ids,
                 deadline, priority: int = 1, top_k: int = 0):
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        self.eos_ids = tuple(eos_ids)
        self.deadline = deadline  # time.monotonic() or None
        # finish: stop/length/timeout/aborted/error/handoff/preempted
        self.finish: str | None = None
        self.error: BaseException | None = None
        self.slot: int | None = None
        self.submitted_at = time.monotonic()
        # QoS: priority level (PRIORITY_LEVELS), how many times this
        # request has been evicted to the parked area, and the total time
        # it spent parked (ms) — all three ride DLREQ01 hand-offs
        self.priority = int(priority)
        self.preempt_count = 0
        self.parked_ms = 0.0
        # KV tiering: total ms this request's pages sat in the host spill
        # pool (the stall the flight record surfaces as ``spill_ms``)
        self.spill_ms = 0.0
        # hand-off state (runtime/snapshot.py DLREQ01): the server parks
        # its stop strings here so a drain-time export can ship them, and
        # every emitted completion token is kept so the importing replica
        # can rebuild the full decode/stop-scan state
        self.stop: list[str] = []
        self.emitted: list[int] = []
        # the submitting thread's X-Request-Id rides the ticket onto the
        # scheduler thread, where the contextvar is not set — spans, logs
        # and the flight record all stamp this one grep-able ID
        self.rid: str = request_id_var.get() or new_request_id()
        # speculative decoding: draft tokens proposed for / accepted by
        # this request's verify bursts (flight record + /debug/requests)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._cancel: str | None = None
        self._on_cancel = None  # scheduler wakeup, bound at submit

    def cancel(self, reason: str = "aborted") -> None:
        """Ask the scheduler to retire this request at the next step
        boundary (idempotent).  Safe before admission: a queued ticket is
        dropped without ever occupying a slot."""
        if self._cancel is None and self.finish is None:
            self._cancel = reason
            if self._on_cancel is not None:
                self._on_cancel()

    def tokens(self):
        """Yield completion token ids until the request retires.  After
        the generator ends, ``finish`` holds the verdict; a scheduler-side
        failure re-raises here on the consumer's thread."""
        while True:
            item = self._q.get()
            if item is _DONE:
                break
            yield item
        if self.error is not None:
            raise self.error


class _Slot:
    __slots__ = ("ticket", "pos", "fed", "produced", "last", "pages",
                 "prefix_tokens", "inserted", "budget", "spilled",
                 "active_at")

    def __init__(self):
        self.ticket: Ticket | None = None
        self.pos = 0        # this row's cache clock
        self.fed = 0        # prompt tokens consumed so far
        self.produced = 0   # completion tokens emitted
        self.last = 0       # previous sample (decode feedback)
        self.pages: list[int] = []   # paged mode: owned pool pages
        self.prefix_tokens = 0       # prompt tokens bound from the radix tree
        self.inserted = False        # prompt pages handed to the tree yet?
        # KV tiering (--kv-reserve optimistic): the page ceiling this
        # request can ever need, the non-resident flag (pages spilled to
        # the host pool; the slot sits out dispatches until they page
        # back in), and the victim-ranking clock (monotonic of the last
        # token this slot advanced — idle-longest spills first)
        self.budget = 0
        self.spilled = False
        self.active_at = 0.0


class _Parked:
    """One preempted request: its live Ticket (the consumer is still
    blocked on the stream — parking is invisible beyond a stall) plus the
    DLREQ01 record that resumes it, held in RAM or spilled to
    ``--preempt-spill-dir``."""

    __slots__ = ("ticket", "blob", "path", "parked_at")

    def __init__(self, ticket, blob, path, parked_at):
        self.ticket = ticket
        self.blob = blob          # bytes, or None when spilled to disk
        self.path = path          # spill file, or None when in RAM
        self.parked_at = parked_at


class _Pending:
    """One in-flight dispatch: the engine's completion handle plus the
    host-side view frozen at enqueue time — who rode it, at what clocks,
    with which sampling params.  The pipeline in
    :meth:`SlotScheduler._dispatch` keeps at most one of these beyond
    the dispatch it is currently landing (depth 2)."""

    __slots__ = ("handle", "error", "active", "tickets", "steps",
                 "t_width", "n_valid", "temps", "topps", "topks", "prefset",
                 "rid_by_slot", "fed_by_slot", "pos_rows", "enq_tp",
                 "seq", "host_gap_ms", "idle_ms", "overlapped",
                 "queued", "verify", "proposed_by_slot")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


def _count_rows(kind: str, valid: int, run: int) -> None:
    """``sched_step_rows``: the token rows of one enqueued dispatch."""
    obs_metrics.SCHED_STEP_ROWS.inc("valid", kind, n=valid)
    obs_metrics.SCHED_STEP_ROWS.inc("run", kind, n=run)


def _kind(cur: _Pending) -> str:
    """A dispatch's kind as ``sched_steps`` counts it."""
    return "verify" if cur.verify else "mixed" if cur.prefset else "decode"


# cells of ``sched_host_ms`` whose kind is known beforehand (``obs_trace.span``
# ``total=`` / ``less=``)
_ADMIT = obs_metrics.host_ms("admit", "round")
_EVICT = obs_metrics.host_ms("evict", "round")
_BUILD_DECLINED = obs_metrics.host_ms("build", "round")
_VERDICT = obs_metrics.host_ms("verdict", "decode")  # of a pipelined step


class SlotScheduler:
    """Owns the batch engine; see the module docstring.  ``max_queue``
    bounds requests waiting for a slot (beyond it submit() raises
    :class:`SchedulerSaturated`)."""

    def __init__(self, engine, *, prefill_chunk: int = 16,
                 max_wait_ms: float = 50.0, decode_burst: int = 16,
                 max_queue: int = 32, prefix_reuse: bool = True,
                 overlap: bool = True, preempt: bool = True,
                 preempt_age_ms: float = 5000.0, preempt_cap: int = 3,
                 parked_max: int | None = None,
                 spill_dir: str | None = None,
                 spec=None, spec_k: int = 4,
                 kv_reserve: str = "full", spill_headroom: int = 16,
                 host_pool_mb: float = 64.0):
        if engine.sp > 1:
            raise ValueError("slot scheduling is not supported on sp meshes")
        if engine.cache.quantized and not getattr(engine, "paged", False):
            raise ValueError("slot scheduling needs a dense or paged-int8 "
                             "KV cache")
        if kv_reserve not in ("full", "optimistic"):
            raise ValueError(f"kv_reserve must be 'full' or 'optimistic', "
                             f"got {kv_reserve!r}")
        self.engine = engine
        self.slots = [_Slot() for _ in range(engine.batch)]
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.max_wait_ms = float(max_wait_ms)
        self.decode_burst = max(1, int(decode_burst))
        self.max_queue = max(1, int(max_queue))
        # paged engine (engine.kv_pages > 0): the scheduler owns the page
        # bookkeeping — a refcounted PagePool plus (prefix_reuse) a radix
        # tree that turns repeated prompt prefixes into shared pages
        # (runtime/pagepool.py).  Pages are reserved at admission for the
        # whole request (prompt + budget), so a dispatch can never fail on
        # allocation and exhaustion surfaces as queueing → 429.
        self.paged = bool(getattr(engine, "paged", False))
        # some models' slots own a state that no page id addresses
        # (``Engine.slot_state``, from the kind's row in
        # ``models/cache_kinds.py``).  So nothing may start a slot past position
        # 0 without having written those positions: the radix tree stays off (a
        # hit would bind the attention layers' prefix pages and leave the
        # slot's own state empty; ROADMAP M(a)(3), M(b)), and so does
        # preemption, whose park and resume move a request page by page; and a
        # step fits what the slot's planes were sized for
        self.ring_pages = int(getattr(engine, "ring_pages", 0))
        self.slot_state = str(getattr(engine, "slot_state", ""))
        if self.slot_state:
            prefix_reuse = preempt = False
            if kv_reserve == "optimistic":
                engine._refuse_slot_state("--kv-reserve optimistic (the spill tier)")
            if max(int(prefill_chunk), int(spec_k) + 1 if spec else 0) > engine.slot_rows:
                raise ValueError(
                    f"a step of more than {engine.slot_rows} rows "
                    f"(--sched-prefill-chunk {prefill_chunk}, --spec-k {spec_k}) "
                    f"does not fit a slot's {self.slot_state}")
        self.pool: PagePool | None = None
        self.prefix_cache: RadixTree | None = None
        # KV tiering (runtime/kvtier.py): under ``optimistic`` reservation
        # admission binds only ceil((prompt + spill_headroom)/page) pages
        # and slots grow page-by-page between dispatch rounds; a grow that
        # finds the pool empty spills the idle-longest neighbor's pages to
        # the bytes-bounded host pool and pages them back in on demand.
        # ``full`` keeps today's whole-request reservation (spill never
        # engages — every slot is always resident).
        self.kv_reserve = kv_reserve
        self.optimistic = self.paged and kv_reserve == "optimistic"
        self.spill_headroom = max(0, int(spill_headroom))
        self.host_pool = None
        self._spilled: dict[int, dict] = {}   # slot -> spill bookkeeping
        self._page_nbytes = 0
        if self.paged:
            self.pool = PagePool(engine.kv_pages, engine.kv_page_size)
            if prefix_reuse:
                self.prefix_cache = RadixTree(self.pool)
            self._page_tables = np.zeros(
                (engine.batch, engine.max_pages_per_slot), np.int32)
            from .kvtier import HostPagePool
            self.host_pool = HostPagePool(
                int(float(host_pool_mb) * (1 << 20)))
            cache = engine.cache
            self._page_nbytes = sum(
                int(np.prod(a.shape[:1] + a.shape[2:])) * a.dtype.itemsize
                for a in cache.pool_planes().values())
            obs_metrics.KV_PAGES_TOTAL.set(self.pool.capacity)
            obs_metrics.KV_PAGES_IN_USE.set(0)
        self._queue: deque[Ticket] = deque()
        # QoS preemption (paged mode only — the DLREQ01 export path is
        # the eviction mechanism).  Aging bounds starvation: a queued
        # ticket's effective level drops one class per preempt_age_ms
        # waited.  preempt_cap bounds per-request churn; parked_max
        # bounds the spill area — beyond either, the victim retires with
        # honest finish "preempted" instead of parking.
        self.preempt = bool(preempt)
        self.preempt_age_ms = float(preempt_age_ms)
        self.preempt_cap = max(0, int(preempt_cap))
        self.parked_max = self.max_queue if parked_max is None \
            else max(0, int(parked_max))
        self.spill_dir = spill_dir
        self._parked: list[_Parked] = []
        self._cond = threading.Condition()
        # serializes engine cache access between the dispatch loop (whose
        # jit step donates the cache buffer) and the hand-off export/
        # import paths, which read/write pool pages from other threads.
        # Scoped strictly around the device calls — never held while
        # taking self._cond, so the two locks cannot deadlock.
        self._engine_lock = threading.Lock()
        self._draining = False
        self._stop = False
        self._idle = threading.Event()  # set while paused with empty slots
        self._paused = 0
        self._step_ms_ema: float | None = None
        # overlapped-dispatch pipeline (see module docstring).  All
        # fields are mutated on the scheduler thread only; _inflight_n
        # is additionally read under _cond by _flushed() waiters, and
        # _flush_req is written by them.
        self.overlap = bool(overlap)
        self._inflight_n = 0     # pipelined dispatches on device
        self._flush_req = 0      # >0: flush requested, pipelining blocked
        self._depth = 0          # dispatches enqueued but not yet landed
        # speculative decoding (runtime/spec.py): proposer instance (or
        # None = off) and per-slot pending drafts collected at land time,
        # each tagged with the ticket it was drafted for so a re-bound
        # slot can never consume a predecessor's drafts.  All spec state
        # is host-side and scheduler-thread-only; flush points clear it.
        self.spec = spec
        self.spec_k = max(1, int(spec_k))
        self._proposals: dict[int, tuple[Ticket, list[int]]] = {}
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._n_dispatched = 0
        self._n_overlapped = 0
        # dispatches enqueued so far: the ``seq`` that ties one dispatch's
        # sched.enqueue, sched.land_wait and sched.fanout spans together
        self._n_enqueued = 0
        # an idle scheduler wakes twice a second; only the first wait
        # after work is recorded as a span (see _span)
        self._quiet = False
        self._park_wakeups = 0   # parked-wait iterations (idle test hook)
        # goodput accounting: every ms between the first and the latest
        # dispatch lands in exactly one component (see obs/metrics.py)
        self._first_dispatch_at: float | None = None   # perf_counter
        self._last_dispatch_end: float | None = None   # perf_counter
        self._idle_accum = 0.0     # seconds slept in _cond.wait since last dispatch
        self._comp = {"prefill": 0.0, "decode": 0.0, "pad": 0.0,
                      "host_gap": 0.0, "idle": 0.0}
        # roofline cost attribution (obs/cost.py): analytic FLOPs/bytes
        # per landed dispatch, pro-rated across occupied rows.  None when
        # the engine shape could not be modeled — serving never depends
        # on the accounting.
        self.cost_model = obs_cost.model_from_engine(engine)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dllama-slot-scheduler")
        self._thread.start()

    # -- submission-side API -------------------------------------------
    def submit(self, prompt: list[int], max_new: int, *,
               temperature: float = 0.0, top_p: float = 0.9,
               top_k: int = 0, eos_ids: tuple[int, ...] = (),
               deadline: float | None = None,
               priority: int = 1) -> Ticket:
        """Queue one request; returns its :class:`Ticket` immediately.
        ``deadline`` is a ``time.monotonic()`` instant (the server's
        per-request deadline); an expired request retires with finish
        ``timeout`` and whatever tokens it produced.  ``priority`` is a
        :data:`PRIORITY_LEVELS` level: admission is priority-ordered and
        a higher-priority arrival may preempt lower-priority slots."""
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be positive")
        if self.pool is not None:
            # a request whose full reservation exceeds the pool would wait
            # forever — that is a sizing error, not transient saturation
            need = min(len(prompt) + max_new, self.engine.seq_len)
            n_pages = -(-need // self.pool.page_size)
            if n_pages > self.pool.capacity:
                from .engine import ContextOverflow
                raise ContextOverflow(
                    f"request needs {n_pages} KV pages but the pool has "
                    f"{self.pool.capacity}; raise --kv-pages or shorten "
                    "the request")
        t = Ticket(prompt, max_new, temperature, top_p, eos_ids, deadline,
                   priority=max(0, min(max(PRIORITY_NAMES), int(priority))),
                   top_k=top_k)
        with self._cond:
            if self._stop or self._draining:
                raise SchedulerClosed("scheduler is draining")
            # admission runs on the scheduler thread, so just-submitted
            # tickets sit in the queue for one beat even when slots are
            # free — the bound is on work beyond what free slots will
            # immediately absorb, not on that scheduling gap
            free = sum(1 for s in self.slots if s.ticket is None)
            if len(self._queue) >= self.max_queue + (0 if self._paused
                                                     else free):
                raise SchedulerSaturated(
                    f"{len(self._queue)} requests already waiting")
            t._on_cancel = self._wake
            self._queue.append(t)
            self._cond.notify_all()
        obs_flight.submit(t.rid, n_prompt=len(t.prompt), max_new=t.max_new,
                          temperature=t.temperature, source="scheduler",
                          priority=PRIORITY_NAMES.get(t.priority, "standard"))
        return t

    def occupancy(self) -> dict:
        """Live state for /health and the over-n error body."""
        with self._cond:
            active = sum(1 for s in self.slots if s.ticket is not None)
            out = {"slots": len(self.slots), "active": active,
                   "queued": len(self._queue),
                   "parked": len(self._parked)}
            if self.pool is not None:
                out["kv_pages_total"] = self.pool.capacity
                out["kv_pages_free"] = self.pool.available
                if self.prefix_cache is not None:
                    out["prefix_nodes"] = len(self.prefix_cache)
                # tiering pressure for the fleet router: resident free
                # pages plus what one spill pass could free into the host
                # pool — the capacity a new request can actually claim
                owned = sum(len(s.pages) for s in self.slots
                            if s.ticket is not None and not s.spilled)
                headroom = 0
                if self.host_pool is not None and self._page_nbytes:
                    headroom = max(0, self.host_pool.capacity_bytes
                                   - self.host_pool.bytes_used) \
                        // self._page_nbytes
                spillable = min(owned, headroom) if self.optimistic else 0
                eng = self.engine
                out["kv_pressure"] = {
                    "reserve": self.kv_reserve,
                    "resident_free": self.pool.available,
                    "spillable": spillable,
                    "effective_free": self.pool.available + spillable,
                    "host_pool_bytes": self.host_pool.bytes_used
                    if self.host_pool is not None else 0,
                    "spilled_slots": len(self._spilled),
                    "codec": "int8" if eng.cache.quantized
                    else str(eng.cache.k.dtype),
                }
            return out

    def begin_drain(self, deadline: float | None) -> None:
        """Stop admitting new submissions and clamp every in-flight and
        queued request's deadline — drain then *waits* for the slots via
        the handlers consuming their tickets."""
        with self._cond:
            self._draining = True
            for t in list(self._queue):
                t.deadline = min(t.deadline, deadline) \
                    if (t.deadline and deadline) else (t.deadline or deadline)
            for s in self.slots:
                if s.ticket is not None:
                    t = s.ticket
                    t.deadline = min(t.deadline, deadline) \
                        if (t.deadline and deadline) else (t.deadline or deadline)
            for e in self._parked:
                t = e.ticket
                t.deadline = min(t.deadline, deadline) \
                    if (t.deadline and deadline) else (t.deadline or deadline)
            self._cond.notify_all()

    def drain_with_export(self, deadline: float | None) -> dict[str, bytes]:
        """Bulk drain entry point: stop admissions, clamp every ticket's
        deadline, and export every live slot as a DLREQ01 record in one
        call — the shape a fleet-level drain (SIGTERM, elastic
        scale-down, live reshape) actually wants, so callers cannot
        forget one half.  Returns the records keyed by request id;
        ``{}`` when the scheduler has no paged KV pool (nothing
        exportable — the drain still runs)."""
        self.begin_drain(deadline)
        return self.handoff_export_all()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the loop; any still-live tickets retire as ``aborted`` so
        no consumer blocks forever."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)

    @contextlib.contextmanager
    def exclusive(self):
        """Park the scheduler and wait until every slot has retired, so
        the caller may run one-shot batch-engine work (list-prompt
        lockstep, n>1 fan-out, logprobs scoring) that resets the shared
        cache.  Admission pauses; queued requests keep their place."""
        with self._cond:
            self._paused += 1
            self._cond.notify_all()
        self._idle.wait()
        try:
            yield
        finally:
            with self._cond:
                self._paused -= 1
                if self._paused == 0:
                    self._idle.clear()
                self._cond.notify_all()

    def _wake(self):
        with self._cond:
            self._cond.notify_all()

    # -- pipeline flush ------------------------------------------------
    @contextlib.contextmanager
    def _flushed(self):
        """Hold the dispatch pipeline empty: block new pipelining, wait
        for any in-flight pipelined dispatch to land (it is discarded
        at the flush point), then yield with ``self._cond`` held and
        zero dispatches in flight.  The DLREQ01 exporter runs inside
        this window so its snapshots never observe a half-landed
        burst."""
        with self._cond:
            self._flush_req += 1
            self._cond.notify_all()
            try:
                if not self._cond.wait_for(
                        lambda: self._inflight_n == 0, timeout=60.0):
                    _log.error("pipeline flush timed out", extra={
                        "inflight": self._inflight_n})
                yield
            finally:
                self._flush_req -= 1
                self._cond.notify_all()

    def flush(self) -> None:
        """Synchronize the dispatch pipeline: returns only once zero
        dispatches are in flight.  Speculation resumes immediately
        after."""
        with self._flushed():
            pass

    # -- paged state snapshot/restore (runtime/snapshot.py DLSNAP02) ----
    def snapshot_paged(self, path, extra: dict | None = None) -> str:
        """Persist the paged serving state: the pool KV arrays ride the
        engine snapshot, the page tables go as an extra array, and the
        radix tree's token keys + page ids go in the JSON meta.  Call
        with no live slots (drain or ``exclusive()`` first) — snapshots
        of mid-flight requests are not meaningful."""
        if self.pool is None:
            raise ValueError("snapshot_paged on a non-paged scheduler")
        with self._cond:
            if self._active():
                raise RuntimeError("snapshot_paged with live slots; "
                                   "drain first")
            meta = dict(extra or {})
            meta["radix"] = (self.prefix_cache.export()
                             if self.prefix_cache is not None else [])
            return self.engine.snapshot(
                path, extra=meta,
                extra_arrays={"page_tables": self._page_tables.copy()})

    def restore_paged(self, path) -> dict:
        """Restore :meth:`snapshot_paged` state.  The engine validates
        format/fingerprint (pool geometry is part of the fingerprint, so
        a mismatched geometry raises SnapshotMismatch and the caller
        cold-starts); the pool and radix tree are rebuilt from the
        snapshot's tree keys, re-claiming their pages."""
        if self.pool is None:
            raise ValueError("restore_paged on a non-paged scheduler")
        with self._cond:
            if self._active():
                raise RuntimeError("restore_paged with live slots")
            extra = self.engine.restore(path)
            arrs = getattr(self.engine, "restored_arrays", {})
            pt = arrs.get("page_tables")
            if pt is not None and pt.shape == self._page_tables.shape:
                self._page_tables[:] = pt
            self.pool = PagePool(self.engine.kv_pages,
                                 self.engine.kv_page_size)
            if self.prefix_cache is not None:
                self.prefix_cache = RadixTree(self.pool)
                self.prefix_cache.restore(extra.get("radix") or [])
            # spill records describe the pre-restore pool; drop them
            if self.host_pool is not None:
                self.host_pool.clear()
            self._spilled.clear()
            obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
            return extra

    # -- per-request KV hand-off (runtime/snapshot.py DLREQ01) ----------
    def _export_slot_locked(self, slot_idx: int) -> bytes:
        """Serialize one live slot to a DLREQ01 record (caller holds
        ``self._cond``).  The record carries the slot's written KV pages
        (positions ``[0, pos)``), the full prompt + completion token ids,
        sampling params, remaining deadline, and the engine's sampler RNG
        stream — everything a geometry-compatible peer needs to resume
        decode without re-prefilling."""
        import math

        s = self.slots[slot_idx]
        t = s.ticket
        ps = self.pool.page_size
        n_data = math.ceil(s.pos / ps)
        deadline_left = None
        if t.deadline is not None:
            deadline_left = max(t.deadline - time.monotonic(), 0.0)
        # pages may contain stale values above pos (an in-flight dispatch
        # whose fanout never ran) — harmless, the importer's causal
        # ceiling masks them exactly like slot reuse does.  A spilled
        # slot's pages are not resident: its record is built from the
        # host-pool copy (page order there is the slot's logical order).
        if s.spilled:
            rec = self.host_pool.get(self._spill_key(slot_idx))
            if rec is None:
                raise RuntimeError(
                    f"slot {slot_idx} marked spilled but its host-pool "
                    "record is missing")
            arrays = {name: np.asarray(a[:, :n_data])
                      for name, a in rec[0].items()}
            with self._engine_lock:
                arrays["rng_key"] = np.asarray(self.engine._key)
                if self.engine._dev_key is not None:
                    arrays["rng_dev_key"] = np.asarray(self.engine._dev_key)
                chunk_counter = self.engine._chunk_counter
        else:
            with self._engine_lock:
                arrays = self.engine.read_pool_pages(s.pages[:n_data])
                arrays["rng_key"] = np.asarray(self.engine._key)
                if self.engine._dev_key is not None:
                    arrays["rng_dev_key"] = np.asarray(self.engine._dev_key)
                chunk_counter = self.engine._chunk_counter
        from . import snapshot as snapfmt
        return snapfmt.dumps_request(
            fingerprint=self.engine.handoff_fingerprint(),
            pos=s.pos, chunk_counter=chunk_counter, arrays=arrays,
            extra={
                "rid": t.rid, "prompt": list(t.prompt),
                "completion": list(t.emitted), "max_new": t.max_new,
                "temperature": t.temperature, "top_p": t.top_p,
                "top_k": t.top_k,
                "sampling_path": self.engine.sampling_path,
                "eos_ids": list(t.eos_ids), "stop": list(t.stop),
                "deadline_left": deadline_left,
                "fed": s.fed, "produced": s.produced, "last": s.last,
                "priority": t.priority, "preempt_count": t.preempt_count,
                "parked_ms": t.parked_ms, "spill_ms": t.spill_ms,
                "trace_id": obs_trace.trace_of(t.rid),
            })

    def handoff_export_all(self) -> dict[str, bytes]:
        """Drain-time hand-off: export every live slot to a DLREQ01
        record keyed by request id and retire it with finish
        ``handoff``; queued (never-admitted) tickets retire ``handoff``
        with no record — the router re-submits those from scratch, which
        is idempotent because nothing was ever streamed."""
        if self.pool is None:
            return {}
        records: dict[str, bytes] = {}
        # _flushed() lands-and-discards any in-flight pipelined
        # dispatch before yielding, so every snapshot below observes
        # step-boundary state only (acceptance: zero in-flight here).
        # Token speculation flushes too: the export path runs _retire
        # (via handoff) which drops the slot's pending drafts, so a
        # DLREQ01 record never carries speculative state
        with self._flushed():
            for i in self._active():
                t = self.slots[i].ticket
                try:
                    records[t.rid] = self._export_slot_locked(i)
                except Exception as e:
                    # an unexportable slot degrades to a plain drain
                    # abort for that request; the fleet must not lose
                    # the other slots over it
                    _log.error("handoff export failed", extra={
                        "rid": t.rid, "error": repr(e)})
                self._retire(i, "handoff")
            # parked (preempted) requests already ARE their own DLREQ01
            # records — ship them as-is so a peer resumes them too
            for e in list(self._parked):
                t = e.ticket
                try:
                    blob = e.blob
                    if blob is None:
                        with open(e.path, "rb") as f:
                            blob = f.read()
                    records[t.rid] = blob
                except Exception as exc:
                    _log.error("handoff export of parked record failed",
                               extra={"rid": t.rid, "error": repr(exc)})
                self._drop_parked_locked(e)
                self._fail_ticket(t, "handoff")
            while self._queue:
                self._fail_ticket(self._queue.popleft(), "handoff")
            self._cond.notify_all()
        if records:
            _log.info("handoff export", extra={"requests": len(records)})
            for rid in records:
                obs_events.emit("handoff", direction="export", rid=rid,
                                trace=obs_trace.trace_of(rid))
        return records

    def checkpoint_export(self, rid: str) -> bytes | None:
        """Non-destructive DLREQ01 snapshot of ONE live slot, keyed by
        request id — the proactive-checkpoint twin of
        :meth:`handoff_export_all`.  The slot keeps decoding afterwards;
        the record is a point-in-time copy the router caches so a
        replica that later dies *ungracefully* can be resumed from the
        checkpoint instead of paying a full re-prefill.

        Runs inside :meth:`_flushed` so the snapshot only ever observes
        step-boundary state (same invariant as the drain exporter).  A
        resumed checkpoint is allowed to be stale: the importer's
        ``emitted_chars`` cursor re-decodes the tokens between the
        checkpoint and what the client already saw and emits nothing
        until the cursor is passed, so greedy byte-parity holds for any
        checkpoint age.  Returns ``None`` when the request is not in a
        live slot (queued, parked, or already retired)."""
        if self.pool is None:
            return None
        with self._flushed():
            for i in self._active():
                t = self.slots[i].ticket
                if t is not None and t.rid == rid:
                    return self._export_slot_locked(i)
        return None

    def import_request(self, blob: bytes) -> tuple[Ticket, dict]:
        """Re-bind an exported request (DLREQ01 bytes) into a free slot:
        allocate this pool's own physical pages, write the exported page
        slices into them, and resume the slot's clocks exactly where the
        exporter stopped — continued greedy decode is byte-identical to
        never having moved (tests/test_handoff.py pins this).

        Raises :class:`~dllama_tpu.io.integrity.ArtifactError` on a
        corrupt record, :class:`SnapshotMismatch` on incompatible
        geometry, :class:`SchedulerSaturated` when no slot/pages are
        free, :class:`SchedulerClosed` when this replica is itself
        draining.  Returns ``(ticket, record_extra)``.

        The exporter's sampler RNG stream is restored only when this
        scheduler has no other live work — the engine RNG is shared
        across slots, so rebasing it under co-scheduled requests would
        perturb their draws.  Greedy (temperature-0) requests do not
        consume the stream and hand off byte-identically regardless.
        """
        from . import snapshot as snapfmt

        if self.pool is None:
            raise ValueError("hand-off import needs a paged scheduler "
                             "(--kv-pages)")
        meta, arrays = snapfmt.loads_request(blob)
        eng = self.engine
        want = eng.handoff_fingerprint()
        if meta["fingerprint"] != want:
            raise snapfmt.SnapshotMismatch(
                "<handoff record>", "fingerprint",
                "record is from a replica with incompatible geometry",
                expected=want, got=meta["fingerprint"])
        extra = dict(meta.get("extra", {}))
        rec_sp = extra.get("sampling_path")
        if rec_sp is not None and rec_sp != eng.sampling_path:
            # the record's sampled stream was drawn by a different
            # sampling implementation — resuming here would silently
            # change the distribution (absent flag = legacy record,
            # accepted for compatibility)
            raise snapfmt.SnapshotMismatch(
                "<handoff record>", "sampling_path",
                "record sampled on a different sampling path",
                expected=eng.sampling_path, got=str(rec_sp))
        prompt = [int(x) for x in extra.get("prompt") or []]
        completion = [int(x) for x in extra.get("completion") or []]
        pos = int(meta["pos"])
        max_new = int(extra.get("max_new", 1))
        fed = int(extra.get("fed", 0))
        produced = int(extra.get("produced", len(completion)))
        if not prompt or max_new < 1 or not (0 <= pos <= eng.seq_len) \
                or not (0 <= fed <= len(prompt)) or produced < 0:
            raise snapfmt.SnapshotMismatch(
                "<handoff record>", "extra",
                "inconsistent request state in hand-off record")
        ps = self.pool.page_size
        n_data = -(-pos // ps)
        # the record must carry exactly this pool's page planes: values
        # always (a latent pool's are ``pages.k`` alone), per-position scale
        # planes iff the pool is int8 — an
        # int8 record into a dense pool (or vice versa) already failed
        # the fingerprint above, this validates shape against position
        page_names = [f"pages.{n}" for n in eng.cache.pool_planes()]
        page_arrays: dict = {}
        for name in page_names:
            ref = getattr(eng.cache, name.split(".", 1)[1])
            arr = arrays.get(name)
            want_shape = (ref.shape[0], n_data) + tuple(ref.shape[2:])
            if arr is None or tuple(arr.shape) != want_shape:
                raise snapfmt.SnapshotMismatch(
                    "<handoff record>", f"array {name!r}",
                    "page payload does not match the record position",
                    expected=str(want_shape),
                    got="missing" if arr is None else str(arr.shape))
            page_arrays[name] = arr
        need = min(len(prompt) + max_new, eng.seq_len)
        n_total = -(-need // ps)
        if n_total > self.pool.capacity:
            from .engine import ContextOverflow
            raise ContextOverflow(
                f"request needs {n_total} KV pages but the pool has "
                f"{self.pool.capacity}")
        deadline = None
        if extra.get("deadline_left") is not None:
            deadline = time.monotonic() + float(extra["deadline_left"])
        with self._cond:
            if self._stop or self._draining:
                raise SchedulerClosed("scheduler is draining")
            slot_idx = next((i for i, s in enumerate(self.slots)
                             if s.ticket is None), None)
            if slot_idx is None:
                raise SchedulerSaturated("no free slot for hand-off import")
            try:
                pages = self.pool.alloc(n_total)
            except PagePoolExhausted:
                pages = None
                if self.prefix_cache is not None:
                    self.prefix_cache.evict(n_total - self.pool.available)
                    try:
                        pages = self.pool.alloc(n_total)
                    except PagePoolExhausted:
                        pass
            if pages is None:
                raise SchedulerSaturated(
                    "no free KV pages for hand-off import")
            others = any(s.ticket is not None for s in self.slots)
            with self._engine_lock:
                if n_data:
                    eng.write_pool_pages(pages[:n_data], page_arrays)
                if not others and not self._queue and "rng_key" in arrays:
                    eng.set_rng(arrays["rng_key"],
                                int(meta["chunk_counter"]),
                                dev_key_np=arrays.get("rng_dev_key"))
            t = Ticket(prompt, max_new,
                       float(extra.get("temperature", 0.0)),
                       float(extra.get("top_p", 0.9)),
                       tuple(int(e) for e in extra.get("eos_ids") or ()),
                       deadline, top_k=int(extra.get("top_k", 0)))
            t.rid = str(extra.get("rid") or t.rid)
            # re-establish the fleet trace context on the importing
            # replica: every span this scheduler records for the resumed
            # request (rid-stamped) joins the exporter's trace id, so a
            # migrated request is ONE trace across both rings
            if extra.get("trace_id"):
                obs_trace.set_trace(t.rid, str(extra["trace_id"]))
            t.stop = [str(x) for x in extra.get("stop") or []]
            t.emitted = list(completion)
            t.priority = int(extra.get("priority", 1))
            t.preempt_count = int(extra.get("preempt_count", 0))
            t.parked_ms = float(extra.get("parked_ms", 0.0))
            t.spill_ms = float(extra.get("spill_ms", 0.0))
            t._on_cancel = self._wake
            s = self.slots[slot_idx]
            s.ticket = t
            s.pages = pages
            s.budget = n_total
            s.spilled = False
            s.active_at = time.monotonic()
            s.prefix_tokens = 0
            # prompt pages become radix-shareable once prefill completes;
            # a decode-phase import never re-inserts (alignment with the
            # exporter's shared prefixes is unknowable here)
            s.inserted = fed >= len(prompt)
            s.pos = pos
            s.fed = fed
            s.produced = produced
            s.last = int(extra.get("last", 0))
            t.slot = slot_idx
            row = self._page_tables[slot_idx]
            row[:] = 0
            row[:len(pages)] = pages
            obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
            obs_metrics.SCHED_SLOT_JOINS.inc(slot_idx)
            self._cond.notify_all()
        obs_flight.submit(t.rid, n_prompt=len(prompt), max_new=max_new,
                          temperature=t.temperature, source="handoff",
                          priority=PRIORITY_NAMES.get(t.priority, "standard"))
        obs_flight.admit(t.rid, slot=slot_idx, queued_ms=0.0,
                         prefix_reused=0)
        ctx = request_id_var.set(t.rid)
        try:
            _log.info("handoff import", extra={
                "slot": slot_idx, "pos": pos, "produced": produced,
                "pages": len(pages)})
        finally:
            request_id_var.reset(ctx)
        obs_events.emit("handoff", direction="import", rid=t.rid,
                        slot=slot_idx, pos=pos, produced=produced,
                        trace=obs_trace.trace_of(t.rid))
        return t, extra

    # -- scheduler thread ----------------------------------------------
    def _retire(self, slot_idx: int, reason: str,
                error: BaseException | None = None) -> None:
        s = self.slots[slot_idx]
        t = s.ticket
        if t is None:
            return
        t.finish = reason
        t.error = error
        if self.pool is not None:
            # a spilled slot owns no pages; its host-pool record dies
            # with the request (dropped while the ticket is still bound
            # so the spilled interval lands on its spill_ms clock)
            self._drop_spilled_locked(slot_idx)
        s.ticket = None
        # flush point for speculation: pending drafts die with the slot
        # and the proposer forgets its per-slot state (a later occupant
        # rebuilds from its own prompt)
        self._proposals.pop(slot_idx, None)
        if self.spec is not None:
            self.spec.reset(slot_idx)
        if self.pool is not None and s.pages:
            # drop this slot's references; pages the radix tree retained
            # stay live (and reusable by the next matching prompt)
            self.pool.decref(s.pages)
            s.pages = []
            self._page_tables[slot_idx][:] = 0
            obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
        obs_metrics.SCHED_SLOT_RETIRES.inc(slot_idx, reason)
        obs_trace.record_ending_now("sched_retire", 0.0, rid=t.rid,
                                    slot=slot_idx, reason=reason,
                                    produced=s.produced)
        # the log record factory stamps the contextvar, so bind the
        # ticket's ID around the call (this thread serves many requests)
        ctx = request_id_var.set(t.rid)
        try:
            _log.info("slot retire", extra={
                "slot": slot_idx, "reason": reason, "produced": s.produced})
        finally:
            request_id_var.reset(ctx)
        obs_flight.retire(t.rid, reason, produced=s.produced, pos=s.pos,
                          error=repr(error) if error is not None else None,
                          preempt_count=t.preempt_count or None,
                          parked_ms=round(t.parked_ms, 3)
                          if t.parked_ms else None,
                          spill_ms=round(t.spill_ms, 3)
                          if t.spill_ms else None,
                          spec_proposed=t.spec_proposed or None,
                          spec_accepted=t.spec_accepted
                          if t.spec_proposed else None)
        t._q.put(_DONE)

    def _fail_ticket(self, t: Ticket, reason: str,
                     error: BaseException | None = None) -> None:
        t.finish = reason
        t.error = error
        obs_flight.retire(t.rid, reason, produced=0,
                          error=repr(error) if error is not None else None)
        t._q.put(_DONE)

    def _bind_pages(self, slot_idx: int, t: Ticket) -> bool:
        """Paged admission: match the prompt against the radix tree, then
        reserve pages.  Under ``full`` reservation that is every page the
        request can ever touch (matched prefix + fresh pages through
        ``min(len(prompt) + max_new, seq_len)``) — exhaustion stays out
        of the dispatch path because a request that cannot get its pages
        stays queued (False), it never fails mid-decode.  Under
        ``optimistic`` only ``ceil((prompt + spill_headroom)/page)`` is
        bound here; the slot grows page-by-page between dispatch rounds
        (:meth:`_tier_round_locked`'s ladder: alloc → radix evict →
        spill → park), so over-commit degrades to queueing either way.
        Caller holds the lock."""
        pool = self.pool
        ps = pool.page_size
        prompt = t.prompt
        matched, shared = 0, []
        if self.prefix_cache is not None:
            matched, shared = self.prefix_cache.match(prompt)
            # always leave ≥1 prompt token to feed: the forward over the
            # suffix is what produces the first sampled token.  The dropped
            # block is re-prefilled into a fresh page; the tree keeps its
            # copy (first writer wins on a later insert).
            while matched >= len(prompt):
                matched -= ps
                shared = shared[:-1]
        # shared pages are referenced BEFORE any allocation/eviction so the
        # evictor (which only frees tree-only pages) cannot free a page
        # this admission just matched
        pool.incref(shared)
        need_len = min(len(prompt) + t.max_new, self.engine.seq_len)
        if self.optimistic:
            reserve_len = min(len(prompt) + self.spill_headroom, need_len)
        else:
            reserve_len = need_len
        fresh = -(-reserve_len // ps) - len(shared)
        try:
            new_pages = pool.alloc(fresh)
        except PagePoolExhausted:
            new_pages = None
            if self.prefix_cache is not None:
                self.prefix_cache.evict(fresh - pool.available)
                try:
                    new_pages = pool.alloc(fresh)
                except PagePoolExhausted:
                    pass
        if new_pages is None:
            pool.decref(shared)
            if not getattr(t, "_page_deferred", False):
                t._page_deferred = True
                obs_metrics.KV_POOL_EXHAUSTED.inc()
                ctx = request_id_var.set(t.rid)
                try:
                    _log.info("kv pool exhausted", extra={
                        "need_pages": fresh, "free": pool.available})
                finally:
                    request_id_var.reset(ctx)
            return False
        s = self.slots[slot_idx]
        s.pages = list(shared) + new_pages
        s.prefix_tokens = matched
        s.inserted = False
        # full-reservation page count: the growth ceiling under
        # optimistic mode (and trivially == len(s.pages) under full)
        s.budget = -(-need_len // ps)
        s.spilled = False
        s.active_at = time.monotonic()
        # the slot's page-table row: reserved pages first, scratch page 0
        # everywhere else (unreserved entries absorb overshoot writes)
        row = self._page_tables[slot_idx]
        row[:] = 0
        row[:len(s.pages)] = s.pages
        if matched:
            obs_metrics.PREFIX_HITS.inc()
            obs_metrics.PREFIX_TOKENS_REUSED.inc(matched)
            obs_flight.phase(t.rid, "prefix_reuse", tokens=matched,
                             pages=len(shared))
        obs_metrics.KV_PAGES_IN_USE.set(pool.in_use)
        return True

    def _eff_level(self, t: Ticket, now: float) -> int:
        """Effective priority level after aging: a waiting ticket climbs
        one class per ``preempt_age_ms`` waited, bounding starvation of
        batch traffic behind a steady interactive stream.  ``<= 0``
        disables aging."""
        lvl = t.priority
        if self.preempt_age_ms > 0:
            lvl -= int((now - t.submitted_at) * 1e3 / self.preempt_age_ms)
        return lvl

    def _admit_locked(self, now: float) -> None:
        """Move waiting work into free slots in priority order (caller
        holds the lock).  Candidates come from two places — the submit
        queue and the parked (preempted) area; the best effective level
        wins, parked beating queued on ties (they were admitted once
        already).  A candidate that cannot get a slot or pages may
        preempt a strictly lower-priority victim; otherwise admission
        stops for the round (head-of-line keeps its place)."""
        while True:
            best = None  # (sort key, kind, ticket, parked entry)
            for t in self._queue:
                k = (self._eff_level(t, now), 1, t.submitted_at)
                if best is None or k < best[0]:
                    best = (k, "queued", t, None)
            for e in self._parked:
                k = (self._eff_level(e.ticket, now), 0,
                     e.ticket.submitted_at)
                if best is None or k < best[0]:
                    best = (k, "parked", e.ticket, e)
            if best is None:
                return
            _, kind, t, entry = best
            if t._cancel is not None or (t.deadline is not None
                                         and now >= t.deadline):
                if kind == "queued":
                    self._queue.remove(t)
                else:
                    self._drop_parked_locked(entry)
                self._fail_ticket(t, t._cancel or "timeout")
                continue
            free = next((i for i, s in enumerate(self.slots)
                         if s.ticket is None), None)
            if free is None:
                if self._preempt_for_locked(t, now, "no_free_slot"):
                    continue
                return
            if kind == "parked":
                if self._unpark_locked(free, entry, now):
                    continue
                if self._preempt_for_locked(t, now, "pool_exhausted"):
                    continue
                return
            if self.pool is not None and not self._bind_pages(free, t):
                # pool exhausted: evict a lower-priority slot if one
                # exists, else the ticket keeps its place at the head of
                # the order and admission stops for this round —
                # retirements free pages and the next pass retries
                if self._preempt_for_locked(t, now, "pool_exhausted"):
                    continue
                return
            self._queue.remove(t)
            s = self.slots[free]
            s.ticket = t
            # paged with a prefix hit: the matched tokens are already in
            # the cache (shared pages), so the clock starts past them and
            # prefill covers only the suffix.  Otherwise both start at 0
            # (_bind_pages sets prefix_tokens; it stays 0 when contiguous).
            s.pos = s.fed = s.prefix_tokens
            s.produced = 0
            s.last = 0
            t.slot = free
            queued_ms = round((now - t.submitted_at) * 1e3, 3)
            obs_metrics.SCHED_SLOT_JOINS.inc(free)
            obs_trace.record_ending_now(
                "sched_admit", now - t.submitted_at, rid=t.rid, slot=free,
                queued_ms=queued_ms, n_prompt=len(t.prompt),
                prefix_reused=s.prefix_tokens,
                priority=PRIORITY_NAMES.get(t.priority, t.priority))
            ctx = request_id_var.set(t.rid)
            try:
                _log.info("slot join", extra={
                    "slot": free, "n_prompt": len(t.prompt),
                    "queued_ms": queued_ms,
                    "prefix_reused": s.prefix_tokens,
                    "priority": PRIORITY_NAMES.get(t.priority, t.priority)})
            finally:
                request_id_var.reset(ctx)
            obs_flight.admit(t.rid, slot=free, queued_ms=queued_ms,
                             prefix_reused=s.prefix_tokens)
            obs_metrics.QUEUE_WAIT.observe(max(now - t.submitted_at, 0.0))

    # -- QoS preemption (export → park → re-admit) ---------------------
    def _preempt_for_locked(self, t: Ticket, now: float,
                            reason: str) -> bool:
        """Evict the lowest-priority longest-remaining slot so ``t`` can
        admit.  Raw (un-aged) priorities gate eviction — an aged batch
        ticket outranks newer batch arrivals for admission but never
        evicts standard work.  Admission runs only between dispatch
        rounds (``_dispatch``'s zero-in-flight invariant), so the export
        below observes step-boundary state only; ``_inflight_n`` is
        checked anyway as a belt-and-braces guard.  Returns False when
        preemption is off, the scheduler is unpaged, or no strictly
        lower-priority victim exists."""
        if not self.preempt or self.pool is None or self._inflight_n:
            return False
        victims = [i for i, s in enumerate(self.slots)
                   if s.ticket is not None and s.ticket.priority > t.priority]
        if not victims:
            return False
        victim = max(victims, key=lambda i: (
            self.slots[i].ticket.priority,
            self.slots[i].ticket.max_new - self.slots[i].produced))
        self._preempt_locked(victim, reason, now)
        return True

    def _preempt_locked(self, slot_idx: int, reason: str,
                        now: float) -> None:
        """Evict one slot through the DLREQ01 export path: snapshot it,
        park the record (RAM, or ``spill_dir``), free its pages, and
        leave the ticket live — the streaming consumer sees only a
        stall.  Over the per-request cap or with the parked area full,
        the victim retires instead with honest finish ``preempted`` and
        whatever tokens it produced."""
        s = self.slots[slot_idx]
        t = s.ticket
        # flush point: pending drafts are discarded BEFORE the export so
        # a DLREQ01 record never carries speculative state — the resumed
        # slot re-drafts from its own (exact) accepted stream
        self._proposals.pop(slot_idx, None)
        if self.spec is not None:
            self.spec.reset(slot_idx)
        obs_metrics.SCHED_PREEMPTIONS.inc(reason)
        obs_trace.record_ending_now(
            "sched_preempt", time.monotonic() - now, rid=t.rid,
            slot=slot_idx, reason=reason, produced=s.produced,
            priority=PRIORITY_NAMES.get(t.priority, t.priority))
        if t.preempt_count >= self.preempt_cap \
                or len(self._parked) >= self.parked_max:
            self._retire(slot_idx, "preempted")
            return
        try:
            blob = self._export_slot_locked(slot_idx)
        except Exception as e:
            # an unexportable slot cannot be parked — honest truncation
            _log.error("preempt export failed", extra={
                "rid": t.rid, "error": repr(e)})
            self._retire(slot_idx, "preempted")
            return
        path = None
        if self.spill_dir is not None:
            import os
            try:
                os.makedirs(self.spill_dir, exist_ok=True)
                path = os.path.join(self.spill_dir, f"{t.rid}.dlreq")
                with open(path, "wb") as f:
                    f.write(blob)
                blob = None
            except OSError as e:
                path = None  # spill failed: keep the record in RAM
                _log.error("preempt spill failed; keeping record in RAM",
                           extra={"rid": t.rid, "error": repr(e)})
        t.preempt_count += 1
        self._parked.append(_Parked(t, blob, path, now))
        # a spilled victim parks from its host-pool copy (the export
        # above read it); the record is now redundant with the DLREQ01
        # blob — drop it while the ticket is still bound
        self._drop_spilled_locked(slot_idx)
        s.ticket = None
        t.slot = None
        if s.pages:
            self.pool.decref(s.pages)
            s.pages = []
            self._page_tables[slot_idx][:] = 0
            obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
        obs_metrics.SCHED_PREEMPT_PARKED.set(len(self._parked))
        ctx = request_id_var.set(t.rid)
        try:
            _log.info("slot preempt", extra={
                "slot": slot_idx, "reason": reason, "produced": s.produced,
                "preempt_count": t.preempt_count,
                "spilled": path is not None})
        finally:
            request_id_var.reset(ctx)
        obs_flight.phase(t.rid, "preempted", slot=slot_idx, reason=reason,
                         produced=s.produced,
                         preempt_count=t.preempt_count)
        obs_events.emit("preempt", rid=t.rid, slot=slot_idx, reason=reason,
                        produced=s.produced, spilled=path is not None,
                        trace=obs_trace.trace_of(t.rid))

    def _unpark_locked(self, slot_idx: int, entry: _Parked,
                       now: float) -> bool:
        """Re-admit a parked request into ``slot_idx``, re-binding its
        ORIGINAL ticket — the consumer is still blocked on the stream,
        so resumption is invisible beyond the stall.  Continued greedy
        decode is byte-identical to never having been preempted
        (tests/test_qos.py pins this against a solo oracle).  Returns
        True when the entry was consumed (resumed, or failed on an
        unreadable record), False when pages are unavailable and it must
        stay parked."""
        from . import snapshot as snapfmt

        eng = self.engine
        t = entry.ticket
        try:
            blob = entry.blob
            if blob is None:
                with open(entry.path, "rb") as f:
                    blob = f.read()
            meta, arrays = snapfmt.loads_request(blob)
        except Exception as e:
            _log.error("parked record unreadable; request cannot resume",
                       extra={"rid": t.rid, "error": repr(e)})
            self._drop_parked_locked(entry)
            self._fail_ticket(t, "preempted")
            return True
        ps = self.pool.page_size
        pos = int(meta["pos"])
        n_data = -(-pos // ps)
        need = min(len(t.prompt) + t.max_new, eng.seq_len)
        n_total = -(-need // ps)
        if self.optimistic:
            # resume with the written pages plus headroom (same shape as
            # optimistic admission); growth resumes page-by-page
            n_alloc = max(n_data,
                          -(-min(pos + self.spill_headroom, need) // ps))
        else:
            n_alloc = n_total
        # the full ladder applies: resuming a parked request may spill
        # an idle neighbor to make room (round boundary — safe)
        pages = self._alloc_ladder_locked(n_alloc)
        if pages is None:
            return False
        extra = dict(meta.get("extra", {}))
        others = any(s.ticket is not None for s in self.slots)
        with self._engine_lock:
            if n_data:
                eng.write_pool_pages(
                    pages[:n_data],
                    {n: arrays[n] for n in arrays if n.startswith("pages.")})
            if not others and not self._queue and "rng_key" in arrays:
                eng.set_rng(arrays["rng_key"], int(meta["chunk_counter"]),
                            dev_key_np=arrays.get("rng_dev_key"))
        s = self.slots[slot_idx]
        s.ticket = t
        s.pages = pages
        s.prefix_tokens = 0
        s.inserted = int(extra.get("fed", 0)) >= len(t.prompt)
        s.budget = n_total
        s.spilled = False
        s.active_at = now
        s.pos = pos
        s.fed = int(extra.get("fed", 0))
        s.produced = int(extra.get("produced", len(t.emitted)))
        s.last = int(extra.get("last", 0))
        t.slot = slot_idx
        row = self._page_tables[slot_idx]
        row[:] = 0
        row[:len(pages)] = pages
        parked_ms = round((now - entry.parked_at) * 1e3, 3)
        t.parked_ms += parked_ms
        self._drop_parked_locked(entry)
        obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
        obs_metrics.SCHED_SLOT_JOINS.inc(slot_idx)
        obs_trace.record_ending_now(
            "sched_resume", now - entry.parked_at, rid=t.rid, slot=slot_idx,
            parked_ms=parked_ms, pos=pos,
            priority=PRIORITY_NAMES.get(t.priority, t.priority))
        ctx = request_id_var.set(t.rid)
        try:
            _log.info("slot resume", extra={
                "slot": slot_idx, "pos": pos, "produced": s.produced,
                "parked_ms": parked_ms})
        finally:
            request_id_var.reset(ctx)
        obs_flight.phase(t.rid, "resumed", slot=slot_idx,
                         parked_ms=parked_ms, pos=pos)
        obs_events.emit("resume", rid=t.rid, slot=slot_idx,
                        parked_ms=parked_ms, pos=pos,
                        trace=obs_trace.trace_of(t.rid))
        return True

    def _drop_parked_locked(self, entry: _Parked) -> None:
        with contextlib.suppress(ValueError):
            self._parked.remove(entry)
        if entry.path is not None:
            import os
            with contextlib.suppress(OSError):
                os.remove(entry.path)
        obs_metrics.SCHED_PREEMPT_PARKED.set(len(self._parked))

    def _sweep_parked_locked(self, now: float) -> None:
        for e in list(self._parked):
            t = e.ticket
            if t._cancel is not None:
                self._drop_parked_locked(e)
                self._fail_ticket(t, t._cancel)
            elif t.deadline is not None and now >= t.deadline:
                self._drop_parked_locked(e)
                self._fail_ticket(t, "timeout")

    # -- KV tiering (optimistic growth → spill → page-in) --------------
    def _spill_key(self, slot_idx: int):
        """Host-pool key for one slot's spill record: the (slot, rid)
        pair, so a slot re-bound to a new ticket can never collide with
        a stale record of its previous occupant."""
        return (slot_idx, self.slots[slot_idx].ticket.rid)

    def _drop_spilled_locked(self, slot_idx: int) -> None:
        """Forget a slot's spill record (retire / park / page-in), and
        charge the spilled interval to the ticket's ``spill_ms`` clock.
        Idempotent — a no-op for slots with no record."""
        rec = self._spilled.pop(slot_idx, None)
        if rec is None:
            return
        s = self.slots[slot_idx]
        if s.ticket is not None:
            s.ticket.spill_ms += (time.monotonic() - rec["since"]) * 1e3
        if self.host_pool is not None:
            self.host_pool.drop(rec["key"])
        s.spilled = False

    def _spill_slot_locked(self, slot_idx: int) -> bool:
        """Move one slot's resident pages to the host pool (caller holds
        ``self._cond``; zero dispatches in flight — the round-boundary
        invariant _dispatch provides).  The page payload is read through
        the engine's async D2H path, stored whole in the host pool, and
        only THEN are the device pages released — a refused or failed
        spill leaves the slot fully resident, so the ladder can fall
        back to preemption without replaying anything."""
        from . import kvtier

        s = self.slots[slot_idx]
        t = s.ticket
        n = len(s.pages)
        if (self.host_pool is None or not n
                or not self.host_pool.would_fit(n * self._page_nbytes)):
            return False
        FAULTS.fire("kv.spill")
        with self._engine_lock:
            handles = self.engine.read_pool_pages_async(s.pages)
        arrays = {k: h.wait() for k, h in handles.items()}
        key = self._spill_key(slot_idx)
        if not self.host_pool.put(key, arrays, {"pos": s.pos}):
            return False
        self.pool.decref(s.pages)
        s.pages = []
        s.spilled = True
        self._page_tables[slot_idx][:] = 0
        now = time.monotonic()
        self._spilled[slot_idx] = {"key": key, "since": now, "n_pages": n}
        obs_metrics.KV_PAGES_SPILLED.inc(n)
        obs_metrics.KV_SPILL_BYTES.inc(kvtier.arrays_nbytes(arrays))
        obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
        ctx = request_id_var.set(t.rid)
        try:
            _log.info("kv spill", extra={"slot": slot_idx, "pages": n,
                                         "pos": s.pos})
        finally:
            request_id_var.reset(ctx)
        obs_flight.phase(t.rid, "kv_spill", slot=slot_idx, pages=n)
        return True

    def _spill_one_locked(self, exclude: int | None = None) -> bool:
        """Pick the best spill victim (idle-longest, index tie-break —
        kvtier.rank_victims) among active resident slots and spill it.
        ``exclude`` protects the slot the ladder is growing — spilling
        the grower to feed the grower would livelock."""
        from . import kvtier

        cands = [(i, self.slots[i].active_at) for i in self._active()
                 if i != exclude and not self.slots[i].spilled
                 and self.slots[i].pages]
        for idx in kvtier.rank_victims(cands):
            if self._spill_slot_locked(idx):
                return True
        return False

    def _alloc_ladder_locked(self, n: int, exclude: int | None = None,
                             allow_spill: bool = True):
        """Allocate ``n`` pages, escalating through the reclaim ladder:
        free list → radix-tree eviction (cold shared prefixes) → host
        spill of idle slots.  Returns the page list or None — the caller
        decides the fallback (queue the admission, park the slot).  Each
        rung only frees pages no slot row references, so recycled pages
        are safe even under an in-flight pipelined dispatch; the spill
        rung additionally reads device state and is round-boundary only
        (callers pass ``allow_spill=False`` mid-flight)."""
        if n <= 0:
            return []
        pool = self.pool
        try:
            return pool.alloc(n)
        except PagePoolExhausted:
            pass
        if self.prefix_cache is not None:
            self.prefix_cache.evict(n - pool.available)
            try:
                return pool.alloc(n)
            except PagePoolExhausted:
                pass
        if allow_spill and self.host_pool is not None:
            while pool.available < n:
                if not self._spill_one_locked(exclude):
                    return None
            try:
                return pool.alloc(n)
            except PagePoolExhausted:  # pragma: no cover - defensive
                return None
        return None

    def _grow_slot_locked(self, slot_idx: int, target_pos: int,
                          allow_spill: bool = True) -> bool:
        """Ensure ``slot_idx`` owns every page the write of token
        positions ``[0, target_pos)`` touches, growing through the
        reclaim ladder.  Growth MUST land before the dispatch that
        writes past the reserved prefix — unreserved page-table entries
        hold scratch page 0, which absorbs (and silently discards)
        overshoot writes.  Clamped to the slot's full-reservation budget
        so optimistic never holds more than full mode would."""
        s = self.slots[slot_idx]
        ps = self.pool.page_size
        need = min(-(-int(target_pos) // ps), s.budget)
        extra = need - len(s.pages)
        if extra <= 0:
            return True
        pages = self._alloc_ladder_locked(extra, exclude=slot_idx,
                                          allow_spill=allow_spill)
        if pages is None:
            return False
        s.pages.extend(pages)
        self._page_tables[slot_idx][:len(s.pages)] = s.pages
        obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
        return True

    def _try_page_in_locked(self) -> None:
        """Bring spilled slots back to residency, oldest spill first
        (FIFO — the longest-stalled consumer un-stalls first).  Runs
        before admission so freed pages prefer slots that already hold
        tickets over fresh admissions.  The ladder runs WITHOUT the
        spill rung here: paging one slot in by spilling another would
        ping-pong."""
        order = sorted(self._spilled.items(),
                       key=lambda kv: (kv[1]["since"], kv[0]))
        for slot_idx, rec in order:
            s = self.slots[slot_idx]
            pages = self._alloc_ladder_locked(rec["n_pages"],
                                              allow_spill=False)
            if pages is None:
                return
            got = self.host_pool.pop(rec["key"])
            if got is None:  # pragma: no cover - defensive
                self._spilled.pop(slot_idx, None)
                s.spilled = False
                self.pool.decref(pages)
                continue
            arrays, _meta = got
            with self._engine_lock:
                self.engine.write_pool_pages(pages, arrays)
            s.pages = list(pages)
            s.spilled = False
            row = self._page_tables[slot_idx]
            row[:] = 0
            row[:len(pages)] = pages
            t = s.ticket
            stalled_ms = (time.monotonic() - rec["since"]) * 1e3
            t.spill_ms += stalled_ms
            self._spilled.pop(slot_idx, None)
            obs_metrics.KV_PAGES_PAGED_IN.inc(len(pages))
            obs_metrics.KV_PAGES_IN_USE.set(self.pool.in_use)
            ctx = request_id_var.set(t.rid)
            try:
                _log.info("kv page-in", extra={
                    "slot": slot_idx, "pages": len(pages),
                    "stalled_ms": round(stalled_ms, 3)})
            finally:
                request_id_var.reset(ctx)
            obs_flight.phase(t.rid, "kv_pagein", slot=slot_idx,
                             pages=len(pages),
                             stalled_ms=round(stalled_ms, 3))

    def _tier_round_locked(self, now: float) -> None:
        """Between-rounds tiering pass (caller holds ``self._cond``,
        zero dispatches in flight): grow every active resident slot to
        cover the widest write the next dispatch can issue.  A slot the
        ladder cannot make room for parks (``kv_pressure``) — the same
        honest-queueing degradation as admission-time exhaustion."""
        if not self.optimistic:
            return
        reach = max(self.prefill_chunk, self.decode_burst,
                    (self.spec_k + 1) if self.spec is not None else 1)
        for i in self._active():
            s = self.slots[i]
            if s.spilled:
                continue
            target = min(s.pos + reach, int(self.engine.seq_len))
            if not self._grow_slot_locked(i, target, allow_spill=True):
                self._preempt_locked(i, "kv_pressure", now)

    def _active(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.ticket is not None]

    def _account(self, component: str, ms: float) -> None:
        self._comp[component] += ms
        obs_metrics.SCHED_STEP_TIME_MS.inc(component, n=ms)

    def _slot_entries(self, active, prefset, rid_by_slot, emitted) -> list:
        out = []
        for i in range(len(self.slots)):
            if i in rid_by_slot:
                out.append({"slot": i,
                            "phase": "prefill" if i in prefset else "decode",
                            "tokens": emitted.get(i, 0),
                            "request_id": rid_by_slot[i]})
            else:
                out.append({"slot": i, "phase": "pad", "tokens": 0})
        return out

    def wall_window(self) -> tuple[float, float] | None:
        """``perf_counter`` bounds of the accounted span (first dispatch
        start → latest dispatch end); the goodput components sum to this
        interval by construction.  None before the first dispatch."""
        if self._first_dispatch_at is None or self._last_dispatch_end is None:
            return None
        return self._first_dispatch_at, self._last_dispatch_end

    def _span(self, name: str, **args):
        """``obs_trace.span`` for the loop's own rounds, silent while the
        scheduler idles with an empty queue: the ring keeps the requests'
        spans instead of two records a second of nothing."""
        if self._quiet:
            return contextlib.nullcontext()
        return obs_trace.span(name, **args)

    def _round_head_locked(self, now: float) -> list[int]:
        """The head of a round under ``_cond``: cancels and deadlines,
        parked sweep, page-in, admission, the tier round.  Returns the
        slots that hold a ticket."""
        # honor cancels/deadlines first so their slots free up
        for i in self._active():
            t = self.slots[i].ticket
            if t._cancel is not None:
                self._retire(i, t._cancel)
            elif t.deadline is not None and now >= t.deadline:
                self._retire(i, "timeout")
        for t in [q for q in self._queue
                  if q._cancel is not None
                  or (q.deadline is not None and now >= q.deadline)]:
            self._queue.remove(t)
            self._fail_ticket(t, t._cancel or "timeout")
        self._sweep_parked_locked(now)
        if self.paged and self._spilled:
            # spilled slots rejoin before fresh admissions: they hold
            # live tickets whose consumers are stalled, so freed pages go
            # to them first
            self._try_page_in_locked()
        if not self._paused:
            self._admit_locked(now)
        if self.paged and self.optimistic:
            self._tier_round_locked(now)
        return self._active()

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    with self._span("sched.admit", seq=self._n_enqueued + 1,
                                    total=_ADMIT, less=_EVICT):
                        now = time.monotonic()
                        real_active = self._round_head_locked(now)
                        # a spilled slot holds a ticket but no pages — it
                        # must sit out the dispatch (its page-table row is
                        # all scratch) until _try_page_in_locked restores
                        # it
                        active = [i for i in real_active
                                  if not self.slots[i].spilled]
                        queued = len(self._queue)
                        obs_metrics.SCHED_SLOTS_OCCUPIED.set(len(active))
                        obs_metrics.SCHED_QUEUE_DEPTH.set(queued)
                    if self._stop:
                        return
                    if not active:
                        if self._paused and not real_active:
                            self._idle.set()
                        # parked: submissions/cancels/close notify_all
                        # immediately, so the timeout only has to cover
                        # the earliest *queued* deadline (a paused
                        # scheduler holds its queue), capped at 0.5s —
                        # the old fixed 0.1s poll burned ~10 wakeups/s
                        # doing nothing.  The slept time is "idle" in
                        # the goodput decomposition (the remainder of an
                        # inter-dispatch gap is host_gap — true
                        # scheduling overhead)
                        timeout = 0.5
                        dls = [t.deadline for t in self._queue
                               if t.deadline is not None]
                        dls += [e.ticket.deadline for e in self._parked
                                if e.ticket.deadline is not None]
                        if dls:
                            timeout = min(timeout,
                                          max(min(dls) - now, 0.0))
                        # no step is in flight (_dispatch's invariant):
                        # the memory account's idle edge, which reads only
                        # after a compile or a build (obs/memory.py)
                        self.engine.settled()
                        w0 = time.perf_counter()
                        with self._span("sched.idle", timeout=timeout):
                            self._cond.wait(timeout)
                        self._park_wakeups += 1
                        self._idle_accum += time.perf_counter() - w0
                        self._quiet = not self._queue
                        continue
                self._quiet = False
                self._dispatch(active, queued)
        except BaseException as e:  # loop must not die silently
            _log.error("scheduler loop failed", extra={"error": repr(e)})
            raise
        finally:
            with self._cond:
                for i in self._active():
                    self._retire(i, "aborted")
                while self._queue:
                    self._fail_ticket(self._queue.popleft(), "aborted")
                for e in list(self._parked):
                    self._drop_parked_locked(e)
                    self._fail_ticket(e.ticket, "aborted")
                self._idle.set()

    def _dispatch(self, active: list[int], queued: int) -> None:
        """Run one dispatch round — and, with ``overlap`` on, keep a
        second dispatch enqueued on device while the first one's tokens
        land and fan out (a two-deep pipeline).  INVARIANT: zero
        dispatches are in flight when this returns, so admission,
        ``exclusive()``, drain and hand-off export all still happen at a
        plain step boundary."""
        cur = self._enqueue_first(active, queued)
        while True:
            nxt = None
            if cur.error is None and self.overlap:
                nxt = self._maybe_pipeline(cur)
            ok = self._land_and_fanout(cur)
            if not ok or nxt is None:
                if nxt is not None:
                    self._abandon(nxt)
                return
            with obs_trace.span("sched.verdict", seq=nxt.seq, total=_VERDICT):
                survivors = self._pipeline_verdict(nxt)
            if survivors is None:
                self._abandon(nxt)
                return
            cur = nxt

    def _enqueue_first(self, active: list[int], queued: int) -> _Pending:
        """Build and enqueue the round's first (host-fed) dispatch.
        Does not block on the device — the returned handle's tokens are
        still in flight."""
        self._n_enqueued += 1
        with obs_trace.span("sched.enqueue", seq=self._n_enqueued,
                            overlapped=False) as sp:
            return self._build_and_enqueue(active, queued, sp)

    def _build_and_enqueue(self, active: list[int], queued: int,
                           sp: dict) -> _Pending:
        """:meth:`_enqueue_first` inside its span; ``sp`` takes the
        dispatch's shape once it is decided.  The host's part before the
        engine call (shape choice, numpy operands, gap accounting) is the
        span ``sched.build`` and ``sched_host_ms{phase="build"}``, under the
        kind the shape turns out to have; the engine's ``engine.h2d`` and
        ``engine.launch`` follow inside ``engine.slot_enqueue``."""
        with obs_trace.span("sched.build", seq=self._n_enqueued,
                            overlapped=False) as bp:
            eng = self.engine
            b = eng.batch
            slots = self.slots
            prefilling = [i for i in active
                          if slots[i].fed < len(slots[i].ticket.prompt)]
            room = min(eng.seq_len - slots[i].pos for i in active)
            # consume the slots' pending draft proposals (runtime/spec.py).
            # Proposals are valid for exactly the next dispatch after the
            # burst that produced them — decode rows advance every
            # dispatch — so they are popped unconditionally and re-validated:
            # identity-checked against the slot's *current* ticket (retire /
            # park / import all rebind), dropped whole when a prefilling row
            # joins (the mixed step has no verify shape) or the context edge
            # is closer than a full verify window (flush, not truncate: the
            # proposer re-drafts next round from exact state either way)
            props: dict[int, list[int]] = {}
            if self.spec is not None:
                with self._cond:
                    pend, self._proposals = self._proposals, {}
                if not prefilling and room >= self.spec_k + 1:
                    for i, (tk, d) in pend.items():
                        if i in active and slots[i].ticket is tk and d:
                            props[i] = d
            # both dispatch dimensions ride the compile key (engine.slot_step
            # caches per (T, steps, greedy)), so each is rounded down to a
            # power of two: transient values — a neighbor 3 tokens from its
            # prompt end, a row 2 tokens from its budget — would otherwise
            # mint one-off executables (PR-4 compile telemetry made that
            # visible).  O(log chunk × log burst) shapes total, each reusable.
            if props:
                # ragged verify burst: a fixed T = spec_k + 1 window (one
                # compile key per spec_k), rows with proposals feed
                # [last, d_1..d_k] and rows without ride along as plain
                # single-token decode (n_valid 1) — one slot speculating
                # never stalls a neighbor that has nothing to propose
                t_width = self.spec_k + 1
                steps = 1
            elif prefilling:
                # mixed step: prefill chunks ride along with the decode rows'
                # single tokens; steps=1 keeps every row's clock advancing by
                # its own n_valid
                t_width = min(self.prefill_chunk, room,
                              max(len(slots[i].ticket.prompt) - slots[i].fed
                                  for i in prefilling))
                t_width = 1 << (t_width.bit_length() - 1)
                steps = 1
            else:
                # pure decode: burst on device, clamped so (a) no row outruns
                # the context edge and (b) queued work waits at most
                # ~max_wait_ms for the next admission boundary.  A row that
                # hits its token budget mid-burst retires and the fanout
                # discards its overrun — cheaper than letting per-row budget
                # minima pick the burst size (lockstep rows share the cost of
                # the longest-running neighbor either way)
                t_width = 1
                steps = min(self.decode_burst, room)
                if queued and self._step_ms_ema:
                    steps = min(steps, max(
                        1, int(self.max_wait_ms / self._step_ms_ema)))
                steps = max(1, steps)
                steps = 1 << (steps.bit_length() - 1)

            tokens = np.zeros((b, t_width), np.int32)
            n_valid = np.ones((b,), np.int32)
            pos_rows = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            topps = np.full((b,), 0.9, np.float32)
            topks = np.zeros((b,), np.int32)
            for i in active:
                s = slots[i]
                pos_rows[i] = s.pos
                temps[i] = s.ticket.temperature
                topps[i] = s.ticket.top_p
                topks[i] = s.ticket.top_k
                if s.fed < len(s.ticket.prompt):
                    c = min(t_width, len(s.ticket.prompt) - s.fed)
                    tokens[i, :c] = s.ticket.prompt[s.fed:s.fed + c]
                    n_valid[i] = c
                else:
                    tokens[i, 0] = s.last
                    d = props.get(i)
                    if d is not None:
                        tokens[i, 1:1 + len(d)] = d
                        n_valid[i] = 1 + len(d)

            obs_metrics.SCHED_BATCH_EFFICIENCY.set(len(active) / b)
            prefset = set(prefilling)
            rid_by_slot = {i: slots[i].ticket.rid for i in active}
            kind = "verify" if props else "mixed" if prefilling else "decode"
            valid_rows = int(n_valid.sum())  # each at most t_width
            run_rows = packing.run_rows(valid_rows, b, t_width,
                                        eng.mesh) * steps
            valid_rows *= steps
            _count_rows(kind, valid_rows, run_rows)
            sp.update(t=t_width, steps=steps, rows=len(active),
                      prefill_rows=len(prefilling), verify=bool(props),
                      valid_rows=valid_rows, run_rows=run_rows,
                      rids=sorted(rid_by_slot.values()))
            fed_by_slot = {i: int(n_valid[i]) for i in prefilling}
            tickets = {i: slots[i].ticket for i in active}

            # inter-dispatch gap: idle (slept waiting for work) vs host_gap
            # (token fanout, admission, array prep — the overhead the
            # overlapped pipeline exists to hide)
            tp0 = time.perf_counter()
            host_gap_ms = idle_ms = 0.0
            if self._last_dispatch_end is None:
                self._first_dispatch_at = tp0
            else:
                gap_ms = max(tp0 - self._last_dispatch_end, 0.0) * 1e3
                idle_ms = min(self._idle_accum * 1e3, gap_ms)
                host_gap_ms = gap_ms - idle_ms
                self._account("idle", idle_ms)
                self._account("host_gap", host_gap_ms)
                obs_metrics.SCHED_HOST_GAP_MS.observe(host_gap_ms)
            self._idle_accum = 0.0
            bp.update(t=t_width, steps=steps, rows=len(active))
            bp.total = obs_metrics.host_ms("build", kind)

        handle, error = None, None
        try:
            with self._engine_lock:
                if props:
                    handle = eng.slot_verify_async(
                        tokens, pos_rows, n_valid, temps_np=temps,
                        topps_np=topps, topks_np=topks,
                        page_tables_np=self._page_tables
                        if self.paged else None)
                else:
                    handle = eng.slot_step_async(
                        tokens, pos_rows, n_valid, temps_np=temps,
                        topps_np=topps, topks_np=topks, steps=steps,
                        page_tables_np=self._page_tables
                        if self.paged else None)
        except Exception as e:
            error = e
        if handle is not None:
            self._depth += 1
            obs_metrics.SCHED_INFLIGHT_DEPTH.set(self._depth)
        return _Pending(handle=handle, error=error, active=list(active),
                        tickets=tickets, steps=steps, t_width=t_width,
                        n_valid=n_valid, temps=temps, topps=topps,
                        topks=topks,
                        prefset=prefset, rid_by_slot=rid_by_slot,
                        fed_by_slot=fed_by_slot, pos_rows=pos_rows,
                        enq_tp=tp0, seq=self._n_enqueued,
                        host_gap_ms=host_gap_ms, idle_ms=idle_ms,
                        overlapped=False, queued=queued,
                        verify=bool(props),
                        proposed_by_slot={i: len(d)
                                          for i, d in props.items()})

    def _queue_must_wait_locked(self, cur: _Pending, now: float) -> bool:
        """True when nothing queued can be served at ``cur``'s boundary,
        so the queue is no reason to stop pipelining: every slot holds a
        ticket of ``cur`` (a budget that runs out in ``cur`` is the
        caller's check; an EOS frees its slot one dispatch late, and the
        in-flight dispatch is kept, not discarded), no queued ticket is
        cancelled or past its deadline (the round head owes it its
        error), and none may evict a running one (``_preempt_for_locked``'s
        rule).  Caller holds ``_cond``."""
        running = [s.ticket for s in self.slots]
        if any(t is None or t is not cur.tickets.get(j)
               for j, t in enumerate(running)):
            return False
        for q in self._queue:
            if q._cancel is not None or (q.deadline is not None
                                         and now >= q.deadline):
                return False
            if self.preempt and self.pool is not None and any(
                    t.priority > q.priority for t in running):
                return False
        return True

    def _maybe_pipeline(self, cur: _Pending) -> _Pending | None:
        """While ``cur`` is still in flight, speculate on the next burst:
        enqueue the next pure-decode dispatch fed by ``cur``'s on-device
        last-token row.  ("Speculate" here is dispatch pipelining — a
        guess that no flush point interrupts the round — not token
        speculation; that is the ``spec`` proposer's job.)  Returns None
        at any pipeline flush point — a queued ticket that the next
        boundary can serve (:meth:`_queue_must_wait_locked`), drain /
        pause / flush request, cancel or expired deadline, a row still
        mid-prefill after ``cur``, a hand-off import, no context room —
        and the round then completes synchronously."""
        if self.spec is not None:
            # token speculation supersedes burst pipelining: a verify
            # window's *content* (the draft tokens) depends on the
            # previous dispatch's landed tokens, so the next dispatch
            # cannot be built while ``cur`` is in flight.  The verify
            # burst's multi-token yield amortizes the host gap instead.
            return None
        # a plan that declines keeps no ``seq`` and counts as ``build/round``
        with obs_trace.span("sched.build", overlapped=True,
                            total=_BUILD_DECLINED, less=_EVICT) as bp:
            eng = self.engine
            slots = self.slots
            b = eng.batch
            with self._cond:
                if (self._stop or self._draining or self._paused
                        or self._flush_req or self._parked):
                    return None
                now = time.monotonic()
                queued = len(self._queue)
                if queued and not self._queue_must_wait_locked(cur, now):
                    return None
                pos2 = np.zeros((b,), np.int32)
                budget = 0
                for j in range(b):
                    s = slots[j]
                    t = s.ticket
                    if j not in cur.tickets:
                        if t is not None:
                            return None   # hand-off import mid-round
                        continue
                    if t is None or t is not cur.tickets[j]:
                        return None       # slot re-bound under us
                    if t._cancel is not None or (t.deadline is not None
                                                 and now >= t.deadline):
                        return None
                    nv = int(cur.n_valid[j])
                    if s.fed < len(t.prompt) and s.fed + nv < len(t.prompt):
                        return None       # still mid-prefill after cur
                    pos2[j] = s.pos + nv + (cur.steps - 1)
                    made = 1 if j in cur.prefset else cur.steps
                    left = t.max_new - (s.produced + made)
                    if queued and left < 1:
                        return None       # its slot frees when cur lands
                    budget = max(budget, left)
                if budget < 1:
                    # every row hits its token budget during ``cur``: unlike
                    # the sync path (which only learns a row retired after
                    # the burst lands), the pipelined dispatch knows its
                    # predecessor's yield up front, so the all-overrun burst
                    # is avoidable waste, not a shape-count trade
                    return None
                room = min(int(eng.seq_len) - int(pos2[i])
                           for i in cur.active)
                if room < 1:
                    return None
                # sized exactly like the sync burst (mid-burst retirement
                # overrun stays cheaper than minting tail shapes), so the
                # overlap on/off A/B compares dispatch pipelining alone
                steps2 = max(1, min(self.decode_burst, room))
                steps2 = 1 << (steps2.bit_length() - 1)
                if queued:
                    # a burst amortizes the host gap, and a pipelined
                    # dispatch has none: single steps keep a stream's tokens
                    # evenly spaced and the first slot to free one step from
                    # its admission boundary
                    steps2 = 1
                if self.paged and self.optimistic:
                    # pipelined chains are unbounded per round (cur = nxt
                    # loops), so the round-start grow cannot cover them:
                    # each burst grows its rows here.  No spill rung — a
                    # D2H page read would order behind the in-flight
                    # dispatch; radix eviction stays safe mid-flight (it
                    # only frees pages no slot row references)
                    for j in cur.active:
                        if not self._grow_slot_locked(
                                j, int(pos2[j]) + steps2, allow_spill=False):
                            return None
                # the import path rewrites _page_tables under _cond; freeze
                # a copy so the enqueue below (outside the lock) cannot
                # observe a half-written row
                ptab = self._page_tables.copy() if self.paged else None
                # reserve the in-flight count before releasing the lock so a
                # concurrent _flushed() waiter sees this dispatch coming
                self._inflight_n += 1
            bp.update(seq=self._n_enqueued + 1, t=1, steps=steps2,
                      rows=len(cur.active))
            bp.total = obs_metrics.host_ms("build", "decode")
        handle, err = None, None
        self._n_enqueued += 1
        _count_rows("decode", b * steps2, b * steps2)
        with obs_trace.span("sched.enqueue", seq=self._n_enqueued,
                            overlapped=True, t=1, steps=steps2,
                            rows=len(cur.active), prefill_rows=0,
                            verify=False, valid_rows=b * steps2,
                            run_rows=b * steps2,
                            rids=sorted(cur.rid_by_slot.values())):
            try:
                with self._engine_lock:
                    handle = eng.slot_step_async(
                        None, pos2, np.ones((b,), np.int32),
                        temps_np=cur.temps, topps_np=cur.topps,
                        topks_np=cur.topks, steps=steps2,
                        page_tables_np=ptab, feed_dev=cur.handle.last_dev)
            except Exception as e:
                err = e
        if err is not None:
            with self._cond:
                self._inflight_n -= 1
                self._cond.notify_all()
            _log.error("pipelined enqueue failed; round completes "
                       "synchronously", extra={"error": repr(err)})
            return None
        self._depth += 1
        obs_metrics.SCHED_INFLIGHT_DEPTH.set(self._depth)
        return _Pending(handle=handle, error=None,
                        active=list(cur.active), tickets=dict(cur.tickets),
                        steps=steps2, t_width=1,
                        n_valid=np.ones((b,), np.int32),
                        temps=cur.temps, topps=cur.topps, topks=cur.topks,
                        prefset=set(),
                        rid_by_slot=dict(cur.rid_by_slot), fed_by_slot={},
                        pos_rows=pos2, enq_tp=time.perf_counter(),
                        seq=self._n_enqueued, host_gap_ms=0.0,
                        idle_ms=0.0, overlapped=True, queued=queued)

    def _attribute_cost(self, cur: _Pending, wall_ms: float) -> None:
        """Analytic roofline attribution for one landed dispatch
        (obs/cost.py): ledger FLOPs/bytes counters by (codec, path,
        phase), a cost block on every riding request's flight record,
        per-class chip-time, and the MFU/MBU gauges.

        A row's chip-time share is ``wall_ms / batch`` — summed over the
        occupied rows of every dispatch that is exactly the busy
        (prefill + decode) goodput component, so per-request chip time
        telescopes the same way the goodput clock does (pad rows' share
        is capacity waste, attributed to nobody).  FLOPs/bytes use each
        row's own useful tokens; the per-pass weight read is split
        evenly across occupied rows (that IS the batching
        amortization)."""
        cm = self.cost_model
        if cm is None or not cur.active:
            return
        rows = []
        for i in cur.active:
            if i in cur.prefset:
                rows.append(("prefill", int(cur.pos_rows[i]),
                             int(cur.n_valid[i])))
            elif cur.verify and (cur.proposed_by_slot or {}).get(i):
                rows.append(("verify", int(cur.pos_rows[i]),
                             int(cur.n_valid[i])))
            else:
                # plain decode rows advance cur.steps tokens (1 inside a
                # mixed or verify dispatch)
                rows.append(("decode", int(cur.pos_rows[i]),
                             int(cur.steps)))
        out = cm.dispatch_cost(rows, steps=cur.steps)
        obs_dispatch.record_cost(out["entries"])
        obs_cost.TRACKER.note(out["flops"], out["hbm_bytes"], wall_ms)
        mfu, mbu = obs_cost.TRACKER.mfu(), obs_cost.TRACKER.mbu()
        if mfu is not None:
            obs_metrics.MFU.set(mfu)
        if mbu is not None:
            obs_metrics.MBU.set(mbu)
        chip_ms = wall_ms / self.engine.batch
        for i, rc in zip(cur.active, out["per_row"]):
            pages = len(self.slots[i].pages) if self.paged else 0
            obs_flight.cost(cur.rid_by_slot.get(i), chip_ms=chip_ms,
                            flops=rc["flops"], hbm_bytes=rc["hbm_bytes"],
                            kv_page_ms=pages * wall_ms)
            t = (cur.tickets or {}).get(i)
            cls = PRIORITY_NAMES.get(getattr(t, "priority", 1), "standard")
            obs_metrics.CLASS_CHIP_MS.inc(cls, n=chip_ms)

    def _land_and_fanout(self, cur: _Pending) -> bool:
        """Block until ``cur``'s tokens land, charge the goodput clock,
        and fan the tokens out to their tickets.  Returns False when the
        dispatch errored (every active slot retires with the error and
        the pipeline round ends)."""
        tw = time.perf_counter()
        error, out, kind = cur.error, None, _kind(cur)
        if error is None:
            with obs_trace.span("sched.land_wait", seq=cur.seq,
                                total=obs_metrics.host_ms("land_wait", kind)):
                try:
                    out = cur.handle.wait()
                except Exception as e:
                    error = e
        tp1 = time.perf_counter()
        with obs_trace.span("sched.fanout", seq=cur.seq,
                            rids=sorted(cur.rid_by_slot.values()),
                            total=obs_metrics.host_ms("fanout", kind)):
            return self._account_and_fanout(cur, out, error, tw, tp1)

    def _account_and_fanout(self, cur: _Pending, out, error, tw: float,
                            tp1: float) -> bool:
        """:meth:`_land_and_fanout` after the wait (``tw`` to ``tp1``):
        the goodput clock, the step counters, the tokens' fan-out."""
        eng = self.engine
        b = eng.batch
        prev_end = self._last_dispatch_end
        self._last_dispatch_end = tp1
        if cur.handle is not None:
            self._depth -= 1
            obs_metrics.SCHED_INFLIGHT_DEPTH.set(self._depth)
        self._n_dispatched += 1
        if cur.overlapped:
            self._n_overlapped += 1
            with self._cond:
                self._inflight_n -= 1
                self._cond.notify_all()
        obs_metrics.SCHED_OVERLAP_RATIO.set(
            self._n_overlapped / self._n_dispatched)

        n_pref, n_act = len(cur.prefset), len(cur.active)
        hidden_ms = 0.0
        if cur.overlapped:
            # this dispatch was enqueued while its predecessor was still
            # in flight, so the span [previous land end, this land end]
            # is the wall it owns.  The host-side share (predecessor
            # fanout + bookkeeping before wait() was called) is *hidden*
            # when the land actually had to wait — the device was still
            # computing underneath it — and *exposed* when the land
            # returned immediately (the host was the bottleneck after
            # all).  Either way every ms lands in exactly one goodput
            # component, preserving the telescoping-sum contract.
            host_ms = max(tw - prev_end, 0.0) * 1e3
            wait_ms = max(tp1 - tw, 0.0) * 1e3
            if wait_ms >= 0.1:
                hidden_ms = host_ms
                exposed_ms = 0.0
                wall_ms = host_ms + wait_ms
            else:
                exposed_ms = host_ms
                wall_ms = wait_ms
            if exposed_ms:
                self._account("host_gap", exposed_ms)
                obs_metrics.SCHED_HOST_GAP_MS.observe(exposed_ms)
            if hidden_ms:
                obs_metrics.SCHED_HOST_GAP_HIDDEN_MS.inc(hidden_ms)
            ts0 = prev_end
            gap_exposed, gap_idle = exposed_ms, 0.0
        else:
            wall_ms = (tp1 - cur.enq_tp) * 1e3
            ts0 = cur.enq_tp
            gap_exposed, gap_idle = cur.host_gap_ms, cur.idle_ms
        # split the dispatch wall by row occupancy: every row rode the
        # same lockstep step, so a row's share IS wall * rows/b
        self._account("prefill", wall_ms * n_pref / b)
        self._account("decode", wall_ms * (n_act - n_pref) / b)
        self._account("pad", wall_ms * (b - n_act) / b)
        kind = _kind(cur)
        obs_metrics.SCHED_STEPS.inc(kind)
        obs_metrics.SCHED_STEP_WALL_MS.inc(kind, n=wall_ms)
        busy = self._comp["prefill"] + self._comp["decode"]
        total = sum(self._comp.values())
        if total > 0:
            obs_metrics.SCHED_GOODPUT_RATIO.set(busy / total)

        if error is not None:
            # a failed dispatch poisons at most this round: retire every
            # active slot with the error and keep serving — stale cache
            # garbage sits above future occupants' causal ceilings
            _log.error("slot dispatch failed", extra={"error": repr(error)})
            obs_flight.TIMELINE.record_step(
                ts=ts0, wall_ms=wall_ms, host_gap_ms=gap_exposed,
                idle_ms=gap_idle, steps=cur.steps, t_width=cur.t_width,
                error=True, overlapped=cur.overlapped,
                hidden_host_ms=hidden_ms,
                slots=self._slot_entries(cur.active, cur.prefset,
                                         cur.rid_by_slot, {}))
            with self._cond:
                for i in self._active():
                    self._retire(i, "error", error=error)
            return False
        self._note_step_time(wall_ms, cur.steps, cur.handle.fresh)
        # enqueue to fan-out is not one block of code (a pipelined
        # dispatch is enqueued a round before it lands), so these two are
        # ring records; a profile shows the parts, tied by ``seq``
        if cur.verify:
            preds, accepted = out
            n_prop = sum(cur.proposed_by_slot.values())
            n_acc = sum(int(accepted[i]) for i in cur.proposed_by_slot)
            obs_trace.record("sched_verify", cur.enq_tp, time.perf_counter(),
                             seq=cur.seq, active=n_act, queued=cur.queued,
                             t=cur.t_width, proposed=n_prop, accepted=n_acc,
                             rids=sorted(cur.rid_by_slot.values()))
        else:
            obs_trace.record("sched_step", cur.enq_tp, time.perf_counter(),
                             seq=cur.seq, active=n_act, queued=cur.queued,
                             t=cur.t_width, steps=cur.steps,
                             overlapped=cur.overlapped,
                             rids=sorted(cur.rid_by_slot.values()))

        FAULTS.fire("sched.host_fanout")
        emitted = dict.fromkeys(cur.active, 0)
        # the whole fanout holds _cond (re-entrant with the _retire calls
        # below): slot clocks (pos/fed/produced/last) and the ticket's
        # emitted list must never be observable half-advanced by the
        # hand-off exporter, which snapshots them from another thread
        with self._cond:
            if cur.verify:
                self._fanout_verify(cur.active, preds, accepted,
                                    cur.proposed_by_slot, emitted)
            else:
                self._fanout(cur.active, cur.steps, out, cur.n_valid,
                             emitted)

        # flight phases + timeline entry for this dispatch (after the
        # fanout so the emitted-token counts are final; a row retired
        # mid-burst still gets its last burst recorded)
        step_ms = wall_ms / cur.steps
        for i in cur.active:
            rid = cur.rid_by_slot[i]
            if i in cur.prefset:
                # a completing chunk also emits the first sampled token —
                # recorded as ``emitted`` on the chunk, not a zero-wall
                # synthetic burst
                obs_flight.phase(rid, "prefill_chunk",
                                 tokens=cur.fed_by_slot[i], ms=wall_ms,
                                 pos=int(cur.pos_rows[i]),
                                 emitted=emitted[i])
            elif cur.verify:
                obs_flight.phase(rid, "verify_burst",
                                 proposed=cur.proposed_by_slot.get(i, 0),
                                 accepted=int(accepted[i]),
                                 tokens=emitted[i], wall_ms=wall_ms)
            else:
                obs_flight.phase(rid, "decode_burst", steps=cur.steps,
                                 tokens=emitted[i], wall_ms=wall_ms,
                                 step_ms=step_ms)
        self._attribute_cost(cur, wall_ms)
        obs_flight.TIMELINE.record_step(
            ts=ts0, wall_ms=wall_ms,
            device_ms=getattr(eng, "last_slot_dispatch_ms", None),
            host_gap_ms=gap_exposed, idle_ms=gap_idle, steps=cur.steps,
            t_width=cur.t_width, overlapped=cur.overlapped,
            hidden_host_ms=hidden_ms,
            slots=self._slot_entries(cur.active, cur.prefset,
                                     cur.rid_by_slot, emitted))
        if self.spec is not None:
            self._collect_proposals()
        return True

    def _pipeline_verdict(self, nxt: _Pending) -> list[int] | None:
        """After ``nxt``'s predecessor landed and fanned out with
        ``nxt`` still in flight: decide whether ``nxt``'s tokens may
        be emitted.  Returns the surviving slot list, or None for a hard
        flush (``nxt`` must be discarded).  A slot that merely retired
        in the predecessor's fanout (EOS / budget) survives row-wise
        removal — the burst computed its row for nothing, which is
        cheaper than flushing the whole pipeline."""
        slots = self.slots
        with self._cond:
            # a queued ticket is not among these: ``nxt`` is on the device
            # either way, admission waits for it to land whether its
            # tokens are kept or not, and _maybe_pipeline enqueues nothing
            # further once the queue can be served
            if (self._stop or self._draining or self._paused
                    or self._flush_req or self._parked):
                return None
            now = time.monotonic()
            survivors = []
            for j in range(len(slots)):
                s = slots[j]
                if j not in nxt.tickets:
                    if s.ticket is not None:
                        return None   # import bound a slot mid-pipeline
                    continue
                t = s.ticket
                if t is None:
                    continue          # retired by the predecessor's fanout
                if t is not nxt.tickets[j]:
                    return None       # slot re-bound (import into freed row)
                if t._cancel is not None or (t.deadline is not None
                                             and now >= t.deadline):
                    return None       # honor the step boundary, like sync
                survivors.append(j)
            if not survivors:
                return None
            nxt.active = survivors
            nxt.tickets = {j: nxt.tickets[j] for j in survivors}
            nxt.rid_by_slot = {j: nxt.rid_by_slot[j] for j in survivors}
            return survivors

    def _abandon(self, nxt: _Pending) -> None:
        """Land and discard an in-flight pipelined dispatch at a flush
        point.  No slot clock ever advanced for it and its tokens are
        never emitted, so greedy output is byte-identical to never
        having pipelined: its KV writes all sit above every surviving
        row's position — masked by the causal ceiling and rewritten
        identically by the synchronous redo dispatch, exactly like slot
        reuse.  The sampler RNG tick it consumed is not rewound: sampled
        draws are co-scheduling-dependent by contract (module
        docstring); greedy rows never touch the stream."""
        with obs_trace.span("sched.land_wait", seq=nxt.seq, discarded=True,
                            total=obs_metrics.host_ms("land_wait", "decode")):
            try:
                nxt.handle.wait()
            except Exception as e:
                # the discarded dispatch owns its own failure — nothing
                # was emitted from it; the next live dispatch re-probes
                # the device
                _log.error("discarded in-flight dispatch failed", extra={
                    "error": repr(e)})
        tp1 = time.perf_counter()
        prev_end = self._last_dispatch_end
        self._last_dispatch_end = tp1
        self._depth -= 1
        obs_metrics.SCHED_INFLIGHT_DEPTH.set(self._depth)
        self._n_dispatched += 1
        self._n_overlapped += 1
        obs_metrics.SCHED_OVERLAP_RATIO.set(
            self._n_overlapped / self._n_dispatched)
        with self._cond:
            self._inflight_n -= 1
            self._cond.notify_all()
        wall_ms = max(tp1 - prev_end, 0.0) * 1e3
        # burned device capacity, not goodput; still a landed decode
        # dispatch, so the step counters keep summing to the clock
        self._account("pad", wall_ms)
        obs_metrics.SCHED_STEPS.inc("decode")
        obs_metrics.SCHED_STEP_WALL_MS.inc("decode", n=wall_ms)
        obs_metrics.SCHED_OVERLAP_DISCARDS.inc()
        obs_flight.TIMELINE.record_step(
            ts=prev_end, wall_ms=wall_ms, steps=nxt.steps, t_width=1,
            overlapped=True, discarded=True,
            slots=self._slot_entries([], set(), {}, {}))

    def _note_step_time(self, wall_ms: float, steps: int,
                        fresh: bool) -> None:
        """Fold one dispatch's per-step wall into the EMA that clamps
        burst size under queue pressure — except fresh-compile
        dispatches, whose trace+compile seconds would poison the EMA and
        pin bursts near 1 for dozens of dispatches after every new
        compile key."""
        if fresh:
            return
        step_ms = wall_ms / max(1, steps)
        self._step_ms_ema = step_ms if self._step_ms_ema is None \
            else 0.8 * self._step_ms_ema + 0.2 * step_ms

    def _fanout(self, active: list[int], steps: int, out, n_valid,
                emitted: dict[int, int]) -> None:
        """Distribute one dispatch's sampled tokens to their tickets and
        advance the slot clocks.  Caller holds ``self._cond``."""
        eng = self.engine
        slots = self.slots
        now = time.monotonic()
        for i in active:
            # the spill victim clock: a slot that took part in this
            # dispatch was active now, whatever it emitted
            slots[i].active_at = now
        for j in range(steps):
            for i in active:
                s = slots[i]
                t = s.ticket
                if t is None:  # retired earlier this burst
                    continue
                tok = int(out[j, i])
                if j == 0 and s.fed < len(t.prompt):
                    s.fed += int(n_valid[i])
                    self._advance(s, int(n_valid[i]))
                    if s.fed < len(t.prompt):
                        continue  # mid-prefill: sample not meaningful yet
                    # prefill just completed: this sample IS the first
                    # completion token — fall through to emit it.  The
                    # prompt's full pages are now entirely written and will
                    # never be rewritten (the clock only moves forward), so
                    # this is the moment they become shareable.
                    if self.prefix_cache is not None and not s.inserted:
                        s.inserted = True
                        ps = self.pool.page_size
                        n_full = len(t.prompt) // ps
                        if n_full:
                            self.prefix_cache.insert(
                                t.prompt[:n_full * ps], s.pages[:n_full])
                else:
                    self._advance(s, 1)
                s.last = tok
                if tok in t.eos_ids:
                    with self._cond:
                        self._retire(i, "stop")
                    continue
                s.produced += 1
                emitted[i] += 1
                t.emitted.append(tok)
                t._q.put(tok)
                if s.produced >= t.max_new or s.pos >= eng.seq_len:
                    with self._cond:
                        self._retire(i, "length")

    def _advance(self, s: _Slot, n: int) -> None:
        """Move a slot's clock ``n`` positions on.  Where the engine keeps
        slot rings (a windowed model), every page the clock enters past the
        ring's first lap lands on the ring page that held the page ``ring``
        pages back, in each window layer: the release of the pages behind the
        window, counted in ``kv_window_pages_recycled``."""
        if self.ring_pages:
            recycled = ring_pages_recycled(s.pos, s.pos + n,
                                           self.pool.page_size, self.ring_pages)
            if recycled:
                obs_metrics.KV_WINDOW_PAGES_RECYCLED.inc(recycled)
        s.pos += n

    def _fanout_verify(self, active: list[int], preds, accepted,
                       proposed_by_slot: dict[int, int],
                       emitted: dict[int, int]) -> None:
        """Distribute one verify dispatch's tokens and advance the slot
        clocks.  Row ``i`` emits ``preds[i, :accepted[i]+1]`` — every
        token is the model's own (argmax) prediction, so the stream is
        byte-identical to plain decode; the drafts only chose how many
        positions one dispatch got to check.  A rejection truncates that
        row alone (its clock advances by its own accepted count; the
        rejected tail's KV sits above the new position, dead under the
        causal ceiling).  EOS or budget mid-window retires the row and
        discards the rest of its window, exactly like a decode burst.
        Caller holds ``self._cond``."""
        eng = self.engine
        slots = self.slots
        now = time.monotonic()
        for i in active:
            s = slots[i]
            s.active_at = now
            t = s.ticket
            if t is None:  # retired between enqueue and land
                continue
            a = int(accepted[i])
            k = proposed_by_slot.get(i, 0)
            if k:
                t.spec_proposed += k
                t.spec_accepted += a
                self._spec_proposed += k
                self._spec_accepted += a
                obs_metrics.SCHED_SPEC_PROPOSED.inc(k)
                if a:
                    obs_metrics.SCHED_SPEC_ACCEPTED.inc(self.spec.name, n=a)
            for tok in (int(preds[i, j]) for j in range(a + 1)):
                self._advance(s, 1)
                s.last = tok
                if tok in t.eos_ids:
                    self._retire(i, "stop")
                    break
                s.produced += 1
                emitted[i] += 1
                t.emitted.append(tok)
                t._q.put(tok)
                if s.produced >= t.max_new or s.pos >= eng.seq_len:
                    self._retire(i, "length")
                    break
        if self._spec_proposed:
            obs_metrics.SCHED_SPEC_ACCEPT_RATIO.set(
                self._spec_accepted / self._spec_proposed)

    def _collect_proposals(self) -> None:
        """After a dispatch fans out: let each live, greedy, decode-phase
        slot draft up to ``spec_k`` tokens for the *next* dispatch.  Runs
        on the scheduler thread only; slot clocks are stable here.  The
        proposer call happens outside ``_cond`` (a draft-model proposer
        dispatches its own engine), so storage re-validates ticket
        identity — a slot parked or retired mid-draft simply loses its
        proposal, which the consume-time check would also have caught."""
        spec = self.spec
        eng = self.engine
        slots = self.slots
        want: dict[int, int] = {}
        with self._cond:
            if (self._stop or self._draining or self._paused
                    or self._flush_req or self._queue or self._parked):
                # a flush point (or pending admission, which makes the
                # next dispatch a mixed prefill step) is imminent:
                # drafting now would be discarded at consume — refuse
                # speculation instead of wasting proposer work
                return
            now = time.monotonic()
            tick = {}
            for i in self._active():
                s = slots[i]
                t = s.ticket
                if (t.temperature != 0.0 or s.fed < len(t.prompt)
                        or t._cancel is not None
                        or (t.deadline is not None and now >= t.deadline)):
                    continue
                if eng.seq_len - s.pos < self.spec_k + 1:
                    # the verify window is fixed at spec_k + 1 columns no
                    # matter how few tokens this row drafts, so a row
                    # that close to the context edge cannot ride one
                    continue
                # drafting past the token budget is pure waste (the
                # fanout discards the overrun as the row retires), so k
                # is clamped to remaining-budget - 1: the window's bonus
                # token is the one that lands exactly on the budget
                k = min(self.spec_k, t.max_new - s.produced - 1)
                if k < 1:
                    continue
                spec.sync(i, t.rid, t.prompt, t.emitted)
                want[i] = k
                tick[i] = t
        if not want:
            return
        props = spec.propose(want)
        with self._cond:
            for i, d in props.items():
                t = slots[i].ticket
                if t is None or t is not tick.get(i):
                    continue
                d = d[:want[i]]
                if d:
                    self._proposals[i] = (t, d)
