"""OpenAI-compatible HTTP API server (`dllama-api` equivalent).

Re-implements `/root/reference/src/apps/dllama-api/dllama-api.cpp`:

* ``POST /v1/chat/completions`` — chat completion with optional SSE
  streaming (writeChatCompletionChunk, :168-185), per-request temperature /
  top_p / max_tokens / seed / stop (:351-380), usage counts (:336-345).
* ``POST /v1/completions`` — text completion; ``prompt`` may be a LIST of
  strings (and/or ``n > 1``), which decodes every prompt as its own
  distinct stream in ONE lockstep batch (Engine.generate_batch) — beyond
  reference (the reference is strictly batch=1, tasks.cpp:199-210) and
  the TPU serving-throughput lever: the decode matmuls amortize one
  weight read over all rows.  Enabled with ``--batch-slots N``.
* **continuous batching** (``--batch-slots`` + runtime/scheduler.py):
  single-prompt completions and spillover chat requests join the batch
  engine at *decode-step* granularity — a request admitted mid-decode
  prefills in ``--sched-prefill-chunk`` chunks interleaved with its
  neighbors' tokens, and a finished stream frees its slot within
  ``--sched-max-wait-ms`` without stopping the batch.  Seeded sampling,
  logprobs, echo, list prompts, and ``n>1`` stay on the mutex/lockstep
  paths (see ``Handler._sched_eligible``).
* ``GET /v1/models`` — stub model list (:387-393).
* **NaiveCache** (:187-232): if a new request's messages extend the cached
  conversation prefix exactly, generation resumes from the cached KV
  position instead of re-prefilling the whole history.

**Request lifecycle & fault tolerance** (beyond reference — the
reference's accept loop is single-threaded blocking I/O, :418-429, and a
stalled client wedges the whole server): requests are handled on threads
(``ThreadingHTTPServer``) with a single **engine mutex** serializing
generation — each engine owns one KV cache, so the mutex queue IS the
request queue — plus:

* **bounded admission**: at most ``--max-pending`` requests in flight or
  queued; excess get ``429`` + ``Retry-After`` instead of an unbounded
  backlog (tail latency stays diagnosable under overload).
* **per-request deadlines**: a ``timeout``/``max_time`` body field (and
  ``--request-timeout`` server default) is enforced between decode
  chunks; an expired request returns a well-formed truncated completion
  with ``finish_reason="timeout"``.
* **socket I/O timeouts** (``--io-timeout``): a stalled client reading
  the body gets ``408``; a stalled reader mid-stream is treated as a
  disconnect.  Client disconnects cancel generation at the next chunk
  and rewind ``engine.pos`` (the runtime/stream.py invariant).
* **graceful drain**: SIGTERM/SIGINT stop accepting (new requests get
  ``503``), finish in-flight requests bounded by ``--drain-grace``, then
  exit (see :func:`serve`).
* **observability**: ``/health`` reports readiness + queue depth;
  ``/metrics`` exports counters (served, 429s, timeouts, disconnects).
* every degraded path above is deterministically testable through the
  fault registry (``runtime/faults.py``; ``DLLAMA_FAULTS`` arms a live
  server, ``tools/fault_drill.py`` drives one end to end).

Uses only the standard library (the reference vendors nlohmann/json;
Python's ``json`` plays that role).  docs/ROBUSTNESS.md has the full
semantics.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import signal
import threading
import time
import uuid
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs

from ..io.integrity import ArtifactError
from ..obs import cost as obs_cost, dispatch as obs_dispatch, \
    events as obs_events, flight as obs_flight, metrics as obs_metrics, \
    trace as obs_trace
from ..obs.log import (configure as configure_logging, get_logger,
                       new_request_id, set_request_id)
from ..runtime.engine import ContextOverflow, Engine, NumericFault, StepTimeout
from ..runtime.faults import FAULTS
from ..runtime.scheduler import (PRIORITY_LEVELS, PRIORITY_NAMES,
                                 SchedulerClosed, SchedulerSaturated,
                                 SlotScheduler)
from ..runtime.snapshot import RecordStore, SnapshotMismatch
from ..runtime.stream import drain_generation
from .backoff import jittered_retry_after
from ..tokenizer.bpe import Tokenizer
from ..tokenizer.chat import ChatItem, ChatTemplate, TokenizerChatStops
from ..tokenizer.eos import EosDetector

_log = get_logger("server.api")

#: client-supplied X-Request-Id is echoed but sanitized to this alphabet
#: (it lands in logs and response headers verbatim otherwise)
_RID_RE = re.compile(r"[^A-Za-z0-9._-]")
_RID_MAX = 64


def priority_level(value) -> int | None:
    """QoS class name → scheduler level, or None for anything that is
    not a known class (callers decide between 400 and silent default)."""
    try:
        return PRIORITY_LEVELS[str(value).strip().lower()]
    except (KeyError, AttributeError):
        return None

#: request bodies above this are refused with 413 (an unbounded
#: Content-Length read is an easy memory DoS against a model server)
MAX_BODY_BYTES = 8 * 1024 * 1024

#: /admin/import bodies (DLREQ01 hand-off records) carry raw KV pages,
#: which dwarf JSON bodies — separate, much larger bound
MAX_HANDOFF_BYTES = 1 << 30


def _decode_continuation(tok: Tokenizer, prev: int, token_ids: list[int]) -> str:
    """Decode a continuation with ``prev`` = the last prompt token — NOT
    from BOS: sentencepiece-style decode-from-BOS strips the first piece's
    leading space (bpe.py decode_piece), which is wrong for text that
    continues a prompt and diverges from the incremental/streaming
    decoders.  One copy shared by every non-streaming batch path."""
    parts = []
    for t in token_ids:
        parts.append(tok.decode_piece(prev, t))
        prev = t
    return b"".join(parts).decode("utf-8", errors="replace")


@dataclass
class ChatMessage:
    role: str
    content: str


@dataclass
class CacheItem:
    end_pos: int
    message: ChatMessage


class NaiveCache:
    """Longest-prefix conversation cache (dllama-api.cpp:187-232)."""

    def __init__(self):
        self.items: list[CacheItem] = []

    def clear(self):
        self.items.clear()

    def push(self, end_pos: int, message: ChatMessage):
        self.items.append(CacheItem(end_pos, message))

    def resolve_delta_prompt(self, messages: list[ChatMessage]) -> tuple[int, list[ChatMessage]]:
        """Returns (start_pos, delta_messages). On any mismatch the cache is
        cleared and the full message list is returned with start_pos 0."""
        n = len(self.items)
        if n and len(messages) > n:
            for i in range(n):
                if (self.items[i].message.role != messages[i].role or
                        self.items[i].message.content != messages[i].content):
                    break
            else:
                start = self.items[n - 1].end_pos
                return start, messages[n:]
        self.clear()
        return 0, messages


@dataclass
class InferenceParams:
    messages: list[ChatMessage] = field(default_factory=list)
    temperature: float = 0.7
    top_p: float = 0.9
    max_tokens: int = 0
    stream: bool = False
    seed: int | None = None
    stop: list[str] = field(default_factory=list)
    n: int = 1  # choices per request; n>1 runs on the batch engine


def parse_request(body: dict, default_temp: float, default_topp: float) -> InferenceParams:
    """Request-param extraction (dllama-api.cpp:351-380).  JSON ``null``
    for an optional field means "unset" to most OpenAI clients."""
    p = InferenceParams(temperature=default_temp, top_p=default_topp)
    for m in body.get("messages", []):
        p.messages.append(ChatMessage(str(m.get("role", "")), str(m.get("content", ""))))
    if body.get("temperature") is not None:
        p.temperature = float(body["temperature"])
    if body.get("top_p") is not None:
        p.top_p = float(body["top_p"])
    if body.get("max_tokens") is not None:
        p.max_tokens = int(body["max_tokens"])
    if body.get("stream") is not None:
        p.stream = bool(body["stream"])
    if body.get("seed") is not None:
        p.seed = int(body["seed"])
    if body.get("n") is not None:
        p.n = int(body["n"])
    stop = body.get("stop")
    if isinstance(stop, str):
        p.stop = [stop]
    elif isinstance(stop, list):
        p.stop = [str(s) for s in stop]
    return p


#: serving counters this class mediates; each name is both the
#: pre-registry ``/metrics`` JSON key and the obs registry json_key
_SERVING_COUNTERS = (
    "requests_served", "requests_rejected_429", "requests_rejected_503",
    "read_timeouts_408", "deadline_timeouts", "client_disconnects",
    "server_errors")


class ServerMetrics:
    """Per-``ApiState`` *view* over the process-global obs registry.

    Bumps land in the one registry (so ``/metrics`` JSON and Prometheus
    exposition read the same numbers), while attribute reads and
    :meth:`snapshot` return deltas against a baseline captured at
    construction — several ApiStates in one test process each see only
    their own traffic, exactly like the pre-registry per-instance
    dataclass.  ``avg_request_s`` stays a per-instance EMA (it feeds this
    server's ``Retry-After`` hint); the global gauge mirrors it."""

    def __init__(self):
        self.started_at = time.time()
        self._lock = threading.Lock()
        self._counters = {n: obs_metrics.REGISTRY.counter(n)
                          for n in _SERVING_COUNTERS}
        self._base = {n: c.value for n, c in self._counters.items()}
        self._avg_request_s = 0.0  # EMA; feeds the Retry-After hint

    def bump(self, name: str, n: int = 1) -> None:
        self._counters[name].inc(n)

    def observe_duration(self, seconds: float) -> None:
        with self._lock:
            a = self._avg_request_s
            self._avg_request_s = (seconds if a == 0.0
                                   else 0.8 * a + 0.2 * seconds)
        obs_metrics.AVG_REQUEST_S.set(self._avg_request_s)
        obs_metrics.REQUEST_DURATION.observe(seconds)

    @property
    def avg_request_s(self) -> float:
        with self._lock:
            return self._avg_request_s

    def __getattr__(self, name: str) -> int:
        # counter reads (state.metrics.requests_served == 1 in tests) are
        # deltas vs the construction baseline
        try:
            counters = object.__getattribute__(self, "_counters")
            base = object.__getattribute__(self, "_base")
        except AttributeError:
            raise AttributeError(name) from None
        if name in counters:
            return counters[name].value - base[name]
        raise AttributeError(name)

    def snapshot(self) -> dict:
        out = {"uptime_s": round(time.time() - self.started_at, 3)}
        for n, c in self._counters.items():
            out[n] = c.value - self._base[n]
        out["avg_request_s"] = round(self.avg_request_s, 6)
        return out


class _StreamTimer:
    """TTFT / inter-token latency observation for one request.

    Constructed at admission (so engine-mutex queue wait counts into
    TTFT, matching what the client experiences) and ticked after each
    delta has been *flushed to the socket* — a slow emit path (e.g. an
    injected ``server.emit_delta`` delay) therefore lands in the first
    delta's TTFT bucket, not between buckets.

    The exact observed values also feed the request's flight record
    (obs/flight.py), so ``/debug/requests/<id>`` and the TTFT/ITL
    histograms agree by construction."""

    def __init__(self, rid=None):
        self.t0 = time.monotonic()
        self.rid = rid
        self._last: float | None = None

    def tick(self) -> None:
        now = time.monotonic()
        if self._last is None:
            ttft = now - self.t0
            obs_metrics.TTFT.observe(ttft)
            obs_flight.first_token(self.rid, ttft)
        else:
            gap = now - self._last
            obs_metrics.INTER_TOKEN.observe(gap)
            obs_flight.inter_token(self.rid, gap)
        self._last = now


def _bounded(stream, state: "ApiState", deadline: float | None,
             is_aborted, flag: dict, n_prompt: int = 0):
    """Wrap an engine token stream so generation stops *between tokens*
    when the request deadline (or the server's drain deadline) passes or
    the client has gone away.  The consumer (drain_generation) then runs
    its normal end-of-stream path — held-back text flushes and
    ``engine.pos`` rewinds exactly as for a budget-exhausted stream, so
    cancellation reuses the one pos-rewind invariant instead of adding a
    second.  The invariant (``runtime/engine.py``, above
    ``Engine.state_holds``): what a sequence leaves in the cache is addressed
    by position, so rows above ``pos`` are dead and get overwritten.  Keys
    and values are; a recurrent state has to be made so: a convolution
    layer's is a ring of ``ops/conv.py RING`` positions, the bound on how far
    a rewind may reach (a decode burst is capped to fit it, the stop
    string's hold-back is a few tokens more), and a resume the ring no longer
    covers (``Engine.resume_at``) prefills the conversation again from 0.
    ``flag`` reports why the stream ended early.

    The deadline arms only after ``n_prompt`` + 1 items: the engine echoes
    the prompt before the first sampled token, and a "timed out" response
    must be a TRUNCATED completion, never an empty one — a cold server
    whose prefill compile alone eats the deadline still owes one token."""
    with contextlib.closing(stream):
        for i, item in enumerate(stream):
            yield item
            if is_aborted is not None and is_aborted():
                flag["aborted"] = True
                return
            d = state.effective_deadline(deadline)
            if d is not None and i >= n_prompt and time.monotonic() >= d:
                flag["timed_out"] = True
                return


class ApiState:
    """Engine + tokenizer + conversation cache shared across requests.

    ``batch_engine`` (optional, ``--batch-slots``) is a second Engine with
    batch > 1 for /v1/completions list-prompt requests.  It shares the
    chat engine's *placed* weight buffers — Engine re-placement of an
    already-sharded array is a no-op — so the only extra HBM is its KV
    cache.

    Request-lifecycle state (threaded server): ``engine_lock`` is THE
    engine mutex — generation for both engines serializes under it (one
    KV-cache conversation state, one device queue).  Admission is counted
    in ``try_enter``/``leave``; ``begin_drain`` flips the server into
    draining (reject new work, clamp in-flight deadlines)."""

    def __init__(self, engine: Engine, tokenizer: Tokenizer,
                 default_temperature: float = 0.7, default_topp: float = 0.9,
                 chunk: int = 16, model_name: str = "dllama-tpu",
                 batch_engine: Engine | None = None,
                 max_pending: int = 8, request_timeout: float = 0.0,
                 io_timeout: float = 15.0, drain_grace: float = 30.0,
                 snapshot_dir: str | None = None,
                 scheduler: SlotScheduler | None = None,
                 slo=None, handoff: bool = False,
                 handoff_ttl: float = 0.0):
        self.engine = engine
        self.snapshot_dir = snapshot_dir
        self.batch_engine = batch_engine
        self.scheduler = scheduler
        self.slo = slo  # obs.slo.SloEngine or None (--slo / DLLAMA_SLO)
        self.tokenizer = tokenizer
        self.default_temperature = default_temperature
        self.default_topp = default_topp
        self.chunk = chunk
        self.model_name = model_name
        self.naive_cache = NaiveCache()
        stops = TokenizerChatStops(tokenizer)
        self.base_stops = stops.stops
        eos = tokenizer.vocab[tokenizer.chat_eos_id].decode("utf-8", "replace")
        self.template = ChatTemplate(tokenizer.chat_template, eos)
        # ---- robustness layer ----
        self.max_pending = max_pending
        self.request_timeout = request_timeout
        self.io_timeout = io_timeout
        self.drain_grace = drain_grace
        self.engine_lock = threading.Lock()
        self.metrics = ServerMetrics()
        self._admit_lock = threading.Lock()
        self._pending = 0   # admitted: queued on the mutex or generating
        self._active = 0    # holding the engine mutex (0 or 1)
        self.draining = False
        self.drain_deadline: float | None = None
        # ---- per-request KV hand-off (--handoff; fleet router) ----
        # opt-in: with it on, a drain EXPORTS live slot requests as
        # DLREQ01 records (finish "handoff") for the router to re-bind on
        # a peer, instead of finishing them here within the grace window
        self.handoff = bool(handoff and scheduler is not None
                            and scheduler.pool is not None)
        # unclaimed export records expire after --handoff-ttl: a router
        # that died between the drain and the GET /admin/export/<rid>
        # pickup must not park the record (and this drain) forever
        self.handoff_records = RecordStore(
            ttl=handoff_ttl, on_expire=self._handoff_expired)

    def _handoff_expired(self, rid: str) -> None:
        obs_metrics.HANDOFF_EXPIRED.inc()
        _log.warning("handoff_record_expired", extra={"rid": rid})

    # -- admission / drain ---------------------------------------------
    def try_enter(self) -> str:
        """Admit one request: ``"ok"`` (caller MUST pair with ``leave``),
        ``"full"`` (queue at capacity → 429) or ``"draining"`` (→ 503)."""
        with self._admit_lock:
            if self.draining:
                return "draining"
            if self._pending >= self.max_pending:
                return "full"
            self._pending += 1
            return "ok"

    def leave(self, duration_s: float) -> None:
        with self._admit_lock:
            self._pending -= 1
        self.metrics.observe_duration(duration_s)

    def mark_active(self, on: bool) -> None:
        with self._admit_lock:
            self._active += 1 if on else -1

    def queue_depths(self) -> tuple[int, int]:
        """(in_flight, queued) — for /health and Retry-After."""
        with self._admit_lock:
            return self._active, max(self._pending - self._active, 0)

    def begin_drain(self, grace: float | None = None) -> None:
        """Stop admitting; clamp every in-flight deadline to now+grace."""
        with self._admit_lock:
            self.draining = True
            g = self.drain_grace if grace is None else grace
            self.drain_deadline = time.monotonic() + max(g, 0.0)
        if self.scheduler is not None:
            # slot-path requests drain too: no new submissions, every
            # in-flight and queued ticket's deadline clamps to the grace
            if self.handoff:
                # drain-with-export in one scheduler call: every live
                # slot becomes a DLREQ01 record the router fetches via
                # GET /admin/export/<rid>; the requests' handlers see
                # finish "handoff" and answer immediately, so the drain
                # completes in O(export) rather than O(longest
                # in-flight decode)
                try:
                    self.handoff_records.update(
                        self.scheduler.drain_with_export(
                            self.drain_deadline))
                except Exception as e:
                    # a failed export degrades to a plain grace-bounded
                    # drain; it must never turn SIGTERM into a crash
                    _log.error("handoff_export_failed",
                               extra={"error": repr(e)})
                    self.scheduler.begin_drain(self.drain_deadline)
            else:
                self.scheduler.begin_drain(self.drain_deadline)

    # -- engine-state snapshot (warm restart; runtime/snapshot.py) ------
    @property
    def snapshot_path(self) -> str | None:
        if not self.snapshot_dir:
            return None
        return os.path.join(self.snapshot_dir, "engine.snap")

    @property
    def sched_snapshot_path(self) -> str | None:
        if not self.snapshot_dir:
            return None
        return os.path.join(self.snapshot_dir, "scheduler.snap")

    def save_snapshot(self) -> str | None:
        """Snapshot the chat engine's state + the conversation cache to
        ``--snapshot-dir`` (called after drain, when no request holds the
        engine).  Returns the path, or None when disabled/failed — a
        snapshot failure must never turn a clean drain into a crash.

        A paged scheduler gets a sibling file: its pool KV, page tables
        and radix-tree keys (SlotScheduler.snapshot_paged), so the prefix
        cache built up before the drain survives the restart warm."""
        path = self.snapshot_path
        if path is None:
            return None
        try:
            os.makedirs(self.snapshot_dir, exist_ok=True)
            with obs_trace.span("snapshot_save", path=path):
                with self.engine_lock:
                    cache_items = [[it.end_pos, it.message.role,
                                    it.message.content]
                                   for it in self.naive_cache.items]
                    self.engine.snapshot(path,
                                         extra={"naive_cache": cache_items})
            _log.info("snapshot_saved", extra={"path": path})
        except Exception as e:
            _log.warning("snapshot_save_failed", extra={
                "path": path, "error": str(e)})
            return None
        if self.scheduler is not None and self.scheduler.pool is not None:
            try:
                self.scheduler.snapshot_paged(self.sched_snapshot_path)
                _log.info("sched_snapshot_saved",
                          extra={"path": self.sched_snapshot_path})
            except Exception as e:
                # best-effort: the prefix cache is a performance artifact,
                # losing it only costs re-prefills after restart
                _log.warning("sched_snapshot_save_failed", extra={
                    "path": self.sched_snapshot_path, "error": str(e)})
        return path

    def restore_snapshot(self) -> bool:
        """Warm-boot from ``--snapshot-dir`` when a snapshot exists.

        The snapshot is one-shot: deleted after a successful restore so a
        crash loop cannot replay ever-staler state.  A corrupt snapshot,
        a config-fingerprint mismatch, or any other failure logs its
        reason and cold-starts (the file is left behind for postmortem) —
        never a crash; a stale state file must not take the server down."""
        path = self.snapshot_path
        if path is None or not os.path.exists(path):
            return False
        try:
            with obs_trace.span("snapshot_restore", path=path):
                extra = self.engine.restore(path)
        except ArtifactError as e:
            _log.warning("snapshot_rejected_cold_start", extra={
                "path": path, "error": str(e)})
            self.engine.reset()
            return False
        except Exception as e:
            _log.warning("snapshot_restore_failed_cold_start", extra={
                "path": path, "error": str(e)})
            self.engine.reset()
            return False
        for end_pos, role, content in extra.get("naive_cache", []):
            self.naive_cache.push(int(end_pos), ChatMessage(str(role),
                                                            str(content)))
        try:
            os.remove(path)
        except OSError:
            pass
        _log.info("warm_start", extra={
            "path": path, "pos": self.engine.pos,
            "cached_messages": len(self.naive_cache.items)})
        spath = self.sched_snapshot_path
        if (self.scheduler is not None and self.scheduler.pool is not None
                and spath and os.path.exists(spath)):
            try:
                self.scheduler.restore_paged(spath)
                _log.info("sched_warm_start", extra={
                    "path": spath,
                    "prefix_nodes": len(self.scheduler.prefix_cache or ())})
            except Exception as e:
                # stale/mismatched scheduler state (geometry change,
                # superseded format): cold pool, warm everything else
                _log.warning("sched_snapshot_rejected_cold_start", extra={
                    "path": spath, "error": str(e)})
            try:
                os.remove(spath)
            except OSError:
                pass
        return True

    def retry_after_hint(self) -> int:
        """Retry-After seconds: queue depth × the EMA request duration
        (floor 1s) — an honest backpressure hint, not a constant."""
        with self._admit_lock:
            depth = self._pending
        avg = self.metrics.avg_request_s or 1.0
        return max(1, min(int(depth * avg + 0.999), 60))

    def should_shed(self, level: int) -> bool:
        """SLO-driven shedding order (docs/SERVING.md QoS): interactive
        is never shed; ``batch`` sheds as soon as ANY objective's burn
        rate on the fast window reaches 1.0 (the error budget has
        started burning — drop best-effort load before the verdict
        degrades); ``standard`` sheds only once the overall verdict is
        ``violating`` (every window burning — the replica is actually
        failing its objectives, not just wobbling)."""
        if self.slo is None or level <= PRIORITY_LEVELS["interactive"]:
            return False
        try:
            verdict = self.slo.evaluate()
        except Exception:
            return False
        if level >= PRIORITY_LEVELS["batch"]:
            windows = verdict.get("windows") or []
            if not windows:
                return False
            fast = windows[0]
            return any((o.get("burn") or {}).get(fast, 0.0) >= 1.0
                       for o in (verdict.get("objectives") or {}).values())
        return verdict.get("status") == "violating"

    # -- deadlines ------------------------------------------------------
    def request_deadline(self, body: dict) -> float | None:
        """Absolute (monotonic) deadline for a request: the body's
        ``timeout``/``max_time`` seconds, clamped by the server default
        (``--request-timeout``); None when neither applies."""
        t = body.get("timeout")
        if t is None:
            t = body.get("max_time")
        try:
            t = float(t) if t is not None else None
        except (TypeError, ValueError):
            t = None
        if t is not None and t <= 0:
            t = None
        if self.request_timeout > 0:
            t = self.request_timeout if t is None else min(t, self.request_timeout)
        return time.monotonic() + t if t is not None else None

    def effective_deadline(self, deadline: float | None) -> float | None:
        """The request deadline clamped by the drain deadline (a drain
        that starts mid-request shortens every in-flight request)."""
        dd = self.drain_deadline
        if dd is None:
            return deadline
        return dd if deadline is None else min(deadline, dd)

    def health(self) -> dict:
        """Readiness + liveness detail for ``/health`` (satellite: model
        loaded, mesh shape, backend, queue depths, uptime)."""
        eng = self.engine
        try:
            backend = eng.mesh.devices.flat[0].platform
        except Exception:
            backend = "unknown"
        in_flight, queued = self.queue_depths()
        occ = self.scheduler.occupancy() if self.scheduler is not None \
            else None
        # machine-readable capacity block (fleet satellite): everything
        # the router's least-loaded scorer needs in one probe, without
        # scraping Prometheus text.  Additive — the pre-fleet fields
        # below keep their exact shapes.
        capacity = {
            "free_slots": (occ["slots"] - occ["active"]) if occ
            else max(self.max_pending - in_flight - queued, 0),
            "free_kv_pages": occ.get("kv_pages_free") if occ else None,
            "queue_depth": queued + (occ["queued"] if occ else 0),
            "batch_efficiency":
                obs_metrics.SCHED_BATCH_EFFICIENCY.json_value(),
            "handoff": self.handoff,
            # KV tiering (runtime/kvtier.py): the router's free-KV
            # tiebreak should see effective capacity — resident free
            # pages plus pages reclaimable by spilling idle slots —
            # not just the resident free list
            "kv_pressure": occ.get("kv_pressure") if occ else None,
        }
        return {
            "status": "draining" if self.draining else "ok",
            "ready": True,  # the model loads before serve() binds the port
            "model": self.model_name,
            "backend": backend,
            "mesh": {k: int(v) for k, v in dict(eng.mesh.shape).items()},
            "seq_len": eng.seq_len,
            "batch_slots": self.batch_engine.batch if self.batch_engine else 0,
            # slot-scheduler occupancy (satellite: /health must surface it
            # alongside batch_slots so an over-n client can size retries)
            "scheduler": occ,
            "capacity": capacity,
            "in_flight": in_flight,
            "queued": queued,
            "max_pending": self.max_pending,
            "uptime_s": round(time.time() - self.metrics.started_at, 3),
            "requests_served": self.metrics.requests_served,
            # kernel-dispatch ledger (obs/dispatch.py): a process that fell
            # off its fast matmul path advertises it on every health probe —
            # a degraded pod shows up in the fleet dashboard, not just in
            # one scrollback warning at load time
            "degraded": obs_dispatch.degraded(),
            "degrade_reasons": obs_dispatch.reasons(),
            # SLO verdict (obs/slo.py): ok / at_risk / violating per
            # objective plus the burn rates behind the call — evaluated
            # live, so the health probe IS the alerting primitive
            "slo": self.slo.evaluate() if self.slo is not None else None,
            # performance economics (obs/cost.py): MFU/MBU against the
            # backend peak table, cumulative modeled work, and chip-time
            # by QoS class — cost-per-tenant as a health probe
            "perf": obs_cost.summary(),
        }

    # ------------------------------------------------------------------
    def complete(self, params: InferenceParams, emit, *,
                 deadline: float | None = None, is_aborted=None):
        """Run one chat completion; calls ``emit(delta_text)`` as text
        becomes safe to stream.  Returns ``(content, n_prompt_tokens,
        n_completion_tokens, finish_reason)`` with finish_reason ``"stop"``
        (eos/stop/budget — the pre-deadline contract), ``"timeout"``
        (deadline expired between chunks) or ``"aborted"`` (client gone;
        the caller sends nothing further).

        Cancellation safety: the deadline/abort checks live in a wrapper
        *around* the engine stream (:func:`_bounded`), so every early
        exit flows through drain_generation's single end-of-stream path —
        held-back text flushes, ``engine.pos`` rewinds to the consumed
        prefix, and the conversation cache records exactly the state the
        KV cache holds.  A disconnected client therefore never poisons
        the next request's cache resume."""
        engine, tok = self.engine, self.tokenizer
        if deadline is not None and time.monotonic() >= deadline:
            # expired while queued on the engine mutex: answer without
            # burning a prefill (the 429/Retry-After path exists so
            # clients can avoid this; some will miss anyway under load)
            return "", 0, 0, "timeout"

        start_pos, delta_messages = self.naive_cache.resolve_delta_prompt(params.messages)
        if start_pos and not engine.resume_at(start_pos):
            # a recurrent state no longer holds the rows before the cached
            # turn's end (the pos-rewind invariant, runtime/engine.py): the
            # whole conversation is prefilled again
            self.naive_cache.clear()
            start_pos, delta_messages = 0, params.messages
        if start_pos == 0:
            engine.reset()
        engine.pos = start_pos

        items = [ChatItem(m.role, m.content) for m in delta_messages]
        text = self.template.generate(items, True)
        prompt_tokens = tok.encode(text, add_bos=start_pos == 0)
        prompt_end = start_pos + len(prompt_tokens)
        if prompt_end + 1 >= engine.seq_len:
            # refuse before touching the cache — a poisoned entry would make
            # every follow-up request resolve to a bogus start_pos
            raise ContextOverflow(
                f"prompt needs {prompt_end} of {engine.seq_len} context positions")

        for m in delta_messages:
            self.naive_cache.push(prompt_end, m)

        max_pos = engine.seq_len
        if params.max_tokens > 0:
            max_pos = min(prompt_end + params.max_tokens, engine.seq_len)
        budget = max_pos - start_pos

        detector = EosDetector(tok.chat_eos_id, self.base_stops + params.stop,
                               padding_left=2, padding_right=2)
        seed = params.seed if params.seed is not None else int(time.time())

        stream = engine.generate_stream(
            prompt_tokens, budget, temperature=params.temperature,
            topp=params.top_p, seed=seed, chunk=self.chunk,
            eos_ids=(tok.chat_eos_id,))
        flag: dict = {}
        if deadline is not None or is_aborted is not None \
                or self.drain_deadline is not None:
            stream = _bounded(stream, self, deadline, is_aborted, flag,
                              n_prompt=len(prompt_tokens))
        reply, n_completion, _ = drain_generation(
            engine, tok, detector, stream, len(prompt_tokens), prompt_end, emit)
        if engine.pos >= engine.seq_len:
            self.naive_cache.clear()  # context exhausted (dllama-api.cpp:330-331)
        else:
            # on timeout/disconnect this records the PARTIAL reply at the
            # rewound pos — cache and KV state stay consistent, which is
            # the whole invariant (a poisoned entry would corrupt resumes)
            self.naive_cache.push(engine.pos, ChatMessage("assistant", reply))
        finish = "aborted" if flag.get("aborted") \
            else "timeout" if flag.get("timed_out") else "stop"
        # coarse flight phases for the mutex path (the scheduler path
        # records per-dispatch detail instead); rid rides the contextvar
        obs_flight.phase(None, "prefill_chunk",
                         tokens=len(prompt_tokens), pos=start_pos)
        obs_flight.phase(None, "decode_burst", tokens=n_completion)
        obs_flight.retire(None, finish, produced=n_completion)
        return reply, len(prompt_tokens), n_completion, finish

    # ------------------------------------------------------------------
    def overflow_body(self, e: Exception) -> dict:
        """Error body for a batch-capacity 4xx: the message plus the
        server's slot count and live scheduler occupancy, so a client
        that sent too many prompts (or too large an ``n``) can split the
        work without a second probing request."""
        body: dict = {"error": str(e)}
        if self.batch_engine is not None:
            body["batch_slots"] = self.batch_engine.batch
        if self.scheduler is not None:
            body["scheduler"] = self.scheduler.occupancy()
        return body

    def _batch_exclusive(self):
        """One-shot batch-engine work (list-prompt lockstep, n>1 fan-out,
        logprobs scoring) resets the shared KV cache, which would corrupt
        any live slot rows — park the scheduler first."""
        if self.scheduler is not None:
            return self.scheduler.exclusive()
        return contextlib.nullcontext()

    def _plan_ids(self, id_lists: list[list[int]], max_tokens: int,
                  eos_id: int) -> tuple[list[list[int]], int, int, int]:
        """THE batched-serving validation/padding/budget recipe — single
        copy shared by /v1/completions (stream and not) and chat ``n>1``.
        Pads the real rows to the engine's batch by repeating row 0 and
        raises ContextOverflow for every client-side problem, so handlers
        can 400 BEFORE committing to a response kind."""
        eng = self.batch_engine
        if eng is None:
            raise ValueError("batched serving not enabled (--batch-slots)")
        if getattr(eng, "paged", False):
            # the paged pool has no whole-batch reset/lockstep mode
            # (engine.slot_step is the only entry); these requests must go
            # one at a time through the scheduler instead
            raise ContextOverflow(
                "prompt lists, n>1 and logprobs are not available with "
                "--kv-pages (slot scheduling only); send requests "
                "individually")
        n_real = len(id_lists)
        if not (0 < n_real <= eng.batch):
            raise ContextOverflow(
                f"{n_real} prompts for {eng.batch} batch slots")
        if any(not ids for ids in id_lists):
            # a BOS-less tokenizer can encode "" to zero tokens; surface it
            # as the client-error type rather than letting the engine's
            # ValueError kill the connection with no HTTP response
            raise ContextOverflow("a prompt encoded to zero tokens")
        longest = max(len(i) for i in id_lists)
        if longest + 1 >= eng.seq_len:
            raise ContextOverflow(
                f"prompt needs {longest} of {eng.seq_len} context positions")
        padded = [list(i) for i in id_lists] \
            + [list(id_lists[0])] * (eng.batch - n_real)
        budget = eng.seq_len
        if max_tokens > 0:
            budget = min(longest + max_tokens, eng.seq_len)
        return padded, n_real, budget, eos_id

    def _drain_batch(self, id_lists: list[list[int]], budget: int, *,
                     temperature: float, top_p: float, seed: int | None,
                     eos_id: int, deadline: float | None = None,
                     n_real: int | None = None
                     ) -> tuple[list[list[int]], list[bool]]:
        """Consume one lockstep batch generation (Engine.generate_batch
        semantics: per-row EOS/budget truncation) with a deadline check
        between device chunks — the batch twin of :func:`_bounded`.
        Returns ``(outs, timed_out_per_row)``; rows cut by the deadline
        keep whatever they had decoded.  The batch engine is one-shot
        (reset precedes every use), so early exit needs no pos rewind —
        only the generator close, which returns the speculative chunk's
        RNG tick (engine contract).

        ``n_real``: rows past it are ``_plan_ids`` padding — they decode
        on device (lockstep has no ragged exit) but are masked out of
        every host-side step: no detokenization, no EOS scan, and no say
        in the early-exit vote, so a short real batch finishes as soon as
        its REAL rows do.  The pad fraction is what the batch-efficiency
        gauge reports."""
        eng = self.batch_engine
        if n_real is None:
            n_real = len(id_lists)
        obs_metrics.SCHED_BATCH_EFFICIENCY.set(n_real / eng.batch)
        outs = [list(p) for p in id_lists]
        done = [len(o) >= budget or r >= n_real
                for r, o in enumerate(outs)]
        timed = [False] * len(outs)
        with self._batch_exclusive():
            eng.reset()
            stream = eng.generate_batch_stream(
                id_lists, budget, temperature=temperature, topp=top_p,
                seed=seed if seed is not None else int(time.time()),
                chunk=self.chunk)
            with contextlib.closing(stream):
                for row_tokens in stream:
                    for r, t in enumerate(row_tokens.tolist()):
                        if done[r]:
                            continue
                        outs[r].append(int(t))
                        if int(t) == eos_id or len(outs[r]) >= budget:
                            done[r] = True
                    if all(done):
                        break
                    d = self.effective_deadline(deadline)
                    if d is not None and time.monotonic() >= d:
                        timed = [not dn and r < n_real
                                 for r, dn in enumerate(done)]
                        break
        return outs, timed

    def complete_n(self, params: InferenceParams,
                   deadline: float | None = None
                   ) -> tuple[list[str], int, int]:
        """``n > 1`` chat choices: the templated prompt replicated n times
        decodes as one lockstep batch on ``batch_engine`` — n *sampled*
        alternatives per weight read (greedy rows are identical, as with
        any sampler).  Fresh conversation each time: the batch engine has
        its own cache and the NaiveCache is neither consulted nor updated
        (n distinct replies cannot extend one conversation prefix)."""
        eng, tok = self.batch_engine, self.tokenizer
        if eng is not None and params.n > eng.batch:
            # tailored message: the client sent ONE prompt with n choices,
            # not n prompts (the generic _plan_ids wording would mislead)
            raise ContextOverflow(
                f"n={params.n} exceeds the {eng.batch} batch slots; lower n "
                "or restart the server with a larger --batch-slots")
        items = [ChatItem(m.role, m.content) for m in params.messages]
        text = self.template.generate(items, True)
        prompt_tokens = tok.encode(text, add_bos=True)
        id_lists, _, budget, eos_id = self._plan_ids(
            [prompt_tokens] * params.n, params.max_tokens, tok.chat_eos_id)
        outs, timed = self._drain_batch(
            id_lists, budget, temperature=params.temperature,
            top_p=params.top_p, seed=params.seed, eos_id=eos_id,
            deadline=deadline, n_real=params.n)
        choices = []
        n_completion = 0
        for r in range(params.n):
            comp = outs[r][len(prompt_tokens):]
            finish = "timeout" if timed[r] else "length"
            if comp and comp[-1] == eos_id:
                comp = comp[:-1]
                finish = "stop"
            n_completion += len(comp)
            # continuation decode (prev = last prompt token), NOT
            # tok.decode: decode-from-BOS strips a leading space, which the
            # n=1 path's incremental drain keeps — the n choices must read
            # exactly like the single-choice reply
            reply = _decode_continuation(tok, prompt_tokens[-1], comp)
            for s in self.base_stops + params.stop:
                cut = reply.find(s)
                if cut != -1:
                    reply = reply[:cut]
                    finish = "stop"
            choices.append((reply, finish))
        return choices, len(prompt_tokens), n_completion

    # ------------------------------------------------------------------
    def plan_batch(self, prompts: list[str], max_tokens: int
                   ) -> tuple[list[list[int]], int, int, int]:
        """Tokenize a /v1/completions prompt list and run it through
        :meth:`_plan_ids` (the shared validation/budget recipe)."""
        tok = self.tokenizer
        if self.batch_engine is None:
            raise ValueError("batched serving not enabled (--batch-slots)")
        id_lists = [tok.encode(p, add_bos=self.batch_engine.cfg.add_bos)
                    for p in prompts]
        # plain-text completion stops at the base EOS (generate-mode
        # semantics), not the chat template's stop token
        eos_id = tok.eos_id if tok.eos_id >= 0 else tok.chat_eos_id
        return self._plan_ids(id_lists, max_tokens, eos_id)

    def complete_batch(self, prompts: list[str], *, temperature: float,
                       top_p: float, max_tokens: int, seed: int | None,
                       stop: list[str], echo: bool = False,
                       logprobs: int | None = None,
                       deadline: float | None = None
                       ) -> tuple[list[dict], int, int]:
        """Run B distinct prompts as one lockstep batch on ``batch_engine``.

        Returns (choices, prompt_tokens, completion_tokens).  Prompt lists
        shorter than the engine's batch are padded by repeating the first
        prompt (pad rows' outputs are dropped); longer lists are the
        caller's 400.  ``stop`` strings truncate post-hoc — batch mode is
        offline-style serving, not token streaming, so the EosDetector's
        incremental hold-back buys nothing here.

        ``logprobs`` (int ≥ 0, OpenAI semantics) scores every returned
        completion with ONE extra teacher-forced ragged forward
        (Engine.score_batch): chosen-token log-probs, plus the top-k
        alternatives per position when > 0.
        """
        eng, tok = self.batch_engine, self.tokenizer
        id_lists, n_real, budget, eos_id = self.plan_batch(prompts, max_tokens)
        outs, timed = self._drain_batch(
            id_lists, budget, temperature=temperature, top_p=top_p,
            seed=seed, eos_id=eos_id, deadline=deadline, n_real=n_real)
        choices = []
        comps = []
        n_prompt = n_completion = 0
        for r in range(n_real):
            ids, out = id_lists[r], outs[r]
            comp = out[len(ids):]
            # the lockstep budget is sized by the LONGEST prompt, so short
            # rows overshoot their own prompt+max_tokens — cap per row, so
            # a prompt served in a batch returns exactly the completion it
            # would get served alone
            if max_tokens > 0:
                comp = comp[:max_tokens]
            finish = "timeout" if timed[r] else "length"
            if comp and comp[-1] == eos_id:
                comp = comp[:-1]
                finish = "stop"
            comps.append(comp)
            n_prompt += len(ids)
            n_completion += len(comp)
            # continuation decode (see _decode_continuation); echo decodes
            # prompt+completion as ONE sequence so a UTF-8 codepoint split
            # across the prompt/completion boundary still reassembles
            text = tok.decode(ids + comp) if echo \
                else _decode_continuation(tok, ids[-1], comp)
            for s in stop:
                cut = text.find(s)
                if cut != -1:
                    text = text[:cut]
                    finish = "stop"
            choices.append({"text": text, "index": r,
                            "finish_reason": finish, "logprobs": None})
        if logprobs is not None and n_real:
            # even with every completion empty (e.g. EOS first): echo rows
            # still owe the prompt's logprobs, non-echo rows empty lists —
            # OpenAI shape either way, never a silent null.  The empty
            # non-echo shape needs no scoring forward, so skip it.
            if echo or any(comps):
                self._attach_logprobs(choices, id_lists, comps, n_real,
                                      int(logprobs), echo)
            else:
                for r in range(n_real):
                    choices[r]["logprobs"] = {
                        "tokens": [], "token_logprobs": [],
                        "top_logprobs": [] if int(logprobs) > 0 else None,
                        "text_offset": []}
        return choices, n_prompt, n_completion

    def _attach_logprobs(self, choices, id_lists, comps, n_real, top_k,
                         echo):
        """Fill each choice's ``logprobs`` object (OpenAI completions
        shape) from one teacher-forced scoring forward over the padded
        batch (Engine.score_batch).

        Alignment contract: ``"".join(tokens)`` equals the choice's
        ``text`` — piece strings come from an incremental UTF-8 decode (a
        codepoint split across byte-fallback tokens attributes to its
        final fragment), tokens past a stop-string truncation are
        dropped, and with ``echo`` the prompt's tokens lead the list with
        ``None`` as the first logprob (no conditional for position 0) —
        all OpenAI completions semantics."""
        import codecs
        eng, tok = self.batch_engine, self.tokenizer
        # pad rows never influence real rows (independent batch rows);
        # their sequences just need ≥2 tokens for the scorer
        seqs = [id_lists[r] + comps[r] if r < n_real else list(id_lists[r])
                for r in range(eng.batch)]
        seqs = [s if len(s) >= 2 else s + [0] for s in seqs]
        with self._batch_exclusive():
            tok_lp, top_ids, top_lp = eng.score_batch(seqs, top_k=top_k)
        bucket = tok_lp.shape[1]
        for r in range(n_real):
            text = choices[r]["text"]
            if echo:
                # tok.decode renders no piece for a leading BOS — skip it
                # here too; the first displayed token then has a REAL
                # conditional (on BOS), so only a truly context-free
                # position 0 gets the OpenAI null.  Walk the REAL sequence,
                # not seqs[r], which may carry the scorer's min-length pad
                # token at the end
                skip = 1 if id_lists[r] and id_lists[r][0] == tok.bos_id else 0
                seq_tokens = (id_lists[r] + comps[r])[skip:]
                base = skip
            else:
                seq_tokens = comps[r]
                base = len(id_lists[r])  # seq index of entry 0
            off = bucket - len(seqs[r])
            # piece strings via incremental decode so their join equals
            # the text (which was decoded from joined bytes)
            dec = codecs.getincrementaldecoder("utf-8")("replace")
            prev = tok.bos_id if echo else id_lists[r][-1]
            prevs, pieces = [], []
            for t in seq_tokens:
                prevs.append(prev)
                pieces.append(dec.decode(tok.decode_piece(prev, t)))
                prev = t
            tail = dec.decode(b"", True)
            if tail and pieces:
                pieces[-1] += tail
            tokens, lps, tops, offsets_txt = [], [], [], []
            text_pos = 0
            for m, piece in enumerate(pieces):
                if text_pos + len(piece) > len(text):
                    break  # stop-string truncation: align to the text
                seq_idx = base + m
                tokens.append(piece)
                offsets_txt.append(text_pos)
                text_pos += len(piece)
                if seq_idx == 0:  # echo: position 0 has no conditional
                    lps.append(None)
                    if top_k > 0:
                        tops.append(None)
                    continue
                col = off + seq_idx - 1
                lps.append(float(tok_lp[r, col]))
                if top_k > 0:
                    # distinct ids can render to the same piece string
                    # (byte-fallback → U+FFFD): top_k is sorted descending,
                    # so setdefault keeps the higher logprob on collision
                    d: dict = {}
                    for i, l in zip(top_ids[r, col], top_lp[r, col]):
                        d.setdefault(tok.decode_piece(prevs[m], int(i))
                                     .decode("utf-8", "replace"), float(l))
                    tops.append(d)
            choices[r]["logprobs"] = {
                "tokens": tokens, "token_logprobs": lps,
                "top_logprobs": tops if top_k > 0 else None,
                "text_offset": offsets_txt}

    # ------------------------------------------------------------------
    def complete_batch_stream(self, prompts: list[str], *, temperature: float,
                              top_p: float, max_tokens: int, seed: int | None,
                              stop: list[str], emit,
                              plan: tuple | None = None,
                              deadline: float | None = None,
                              is_aborted=None) -> None:
        """Streaming complement of :meth:`complete_batch`: drives the same
        lockstep batch but calls ``emit(row_index, delta_text,
        finish_reason_or_None)`` as each row's text becomes safe to send.
        A row that finishes stops emitting while the batch keeps decoding
        for the rows still live.

        Parity details that keep stream ≡ non-stream for the same seed:
        per-row *incremental* UTF-8 decoding (a codepoint split across
        byte-fallback tokens reassembles instead of becoming U+FFFD, with
        a final flush when the row closes), and a per-row hold-back
        buffer of ``max(len(stop))-1`` characters — a stop string can
        begin anywhere inside a BPE piece and span any number of pieces,
        so the buffer scan sees exactly what complete_batch's post-hoc
        ``text.find`` sees, and no prefix of a stop is ever emitted
        early.  ``plan`` lets the HTTP handler run :meth:`plan_batch`
        (and 400) before committing to SSE headers.
        """
        import codecs
        eng, tok = self.batch_engine, self.tokenizer
        id_lists, n_real, budget, eos_id = \
            plan if plan is not None else self.plan_batch(prompts, max_tokens)
        obs_metrics.SCHED_BATCH_EFFICIENCY.set(n_real / eng.batch)
        decoders = [codecs.getincrementaldecoder("utf-8")("replace")
                    for _ in range(n_real)]
        hold = max((len(s) for s in stop), default=0)
        prev = [ids[-1] for ids in id_lists[:n_real]]
        buf = [""] * n_real   # decoded but not yet emitted
        n_comp = [0] * n_real
        cap = [max_tokens if max_tokens > 0
               else eng.seq_len - len(id_lists[r]) for r in range(n_real)]
        done = [False] * n_real

        def flush(r, closing, finish="length"):
            """Scan the row's unsent buffer for stops; emit everything
            safe.  While the row is live, the last ``hold-1`` characters
            stay buffered (a stop could still complete across the
            boundary); on close the whole buffer goes out with ``finish``
            ("length" at the cap, "stop" when eos fired)."""
            cuts = [c for c in (buf[r].find(s) for s in stop) if c != -1]
            if cuts:
                emit(r, buf[r][:min(cuts)], "stop")
                buf[r] = ""
                done[r] = True
                return
            if closing:
                done[r] = True
                emit(r, buf[r], finish)
                buf[r] = ""
            elif hold and len(buf[r]) >= hold:
                emit(r, buf[r][:len(buf[r]) - (hold - 1)], None)
                buf[r] = buf[r][len(buf[r]) - (hold - 1):]
            elif not hold and buf[r]:
                emit(r, buf[r], None)
                buf[r] = ""

        with self._batch_exclusive():
            eng.reset()
            stream = eng.generate_batch_stream(
                id_lists, budget, temperature=temperature, topp=top_p,
                seed=seed if seed is not None else int(time.time()),
                chunk=self.chunk)
            with contextlib.closing(stream):
                for step_vec in stream:
                    for r in range(n_real):
                        if done[r]:
                            continue
                        t = int(step_vec[r])
                        n_comp[r] += 1
                        if t == eos_id:
                            # eos text never enters the reply; flush and close
                            # as "stop" (a stop string firing in the buffer
                            # also ends the row as "stop" — flush handles both)
                            buf[r] += decoders[r].decode(b"", True)
                            flush(r, closing=True, finish="stop")
                            continue
                        buf[r] += decoders[r].decode(
                            tok.decode_piece(prev[r], t))
                        prev[r] = t
                        if n_comp[r] >= cap[r]:
                            buf[r] += decoders[r].decode(b"", True)
                            flush(r, closing=True)
                        else:
                            flush(r, closing=False)
                    if all(done):
                        break
                    if is_aborted is not None and is_aborted():
                        return  # client gone: nothing left worth decoding
                    d = self.effective_deadline(deadline)
                    if d is not None and time.monotonic() >= d:
                        # deadline between chunks: close every live row as a
                        # well-formed truncated stream (OpenAI shape, the
                        # chat path's finish_reason="timeout" contract)
                        for r in range(n_real):
                            if not done[r]:
                                buf[r] += decoders[r].decode(b"", True)
                                flush(r, closing=True, finish="timeout")
                        return
        for r in range(n_real):
            if not done[r]:  # budget exhausted with text still buffered
                buf[r] += decoders[r].decode(b"", True)
                flush(r, closing=True)

    # -- continuous batching (runtime/scheduler.py) --------------------
    def sched_submit(self, prompt_tokens: list[int], max_tokens: int, *,
                     temperature: float, top_p: float, eos_id: int,
                     deadline: float | None, stop: list[str] | None = None,
                     priority: int = 1):
        """Validate and submit one request to the slot scheduler.  Split
        from :meth:`sched_drain` so streaming handlers can 400/429/503
        BEFORE committing to SSE headers.  Raises ContextOverflow /
        SchedulerClosed / SchedulerSaturated.  ``stop`` strings ride the
        ticket so a drain-time hand-off export can ship them (the
        importing replica owes the client the same stop-scan)."""
        eng = self.scheduler.engine
        if not prompt_tokens:
            raise ContextOverflow("a prompt encoded to zero tokens")
        if len(prompt_tokens) + 1 >= eng.seq_len:
            raise ContextOverflow(
                f"prompt needs {len(prompt_tokens)} of {eng.seq_len} "
                "context positions")
        max_new = eng.seq_len - len(prompt_tokens)
        if max_tokens > 0:
            max_new = min(max_new, max_tokens)
        ticket = self.scheduler.submit(
            prompt_tokens, max_new, temperature=temperature, top_p=top_p,
            eos_ids=(eos_id,), deadline=self.effective_deadline(deadline),
            priority=priority)
        ticket.stop = [str(s) for s in stop or []]
        return ticket

    def sched_drain(self, ticket, prev: int, *, stop: list[str], emit,
                    is_aborted=None) -> tuple[str, int, str]:
        """Consume one ticket's token stream: incremental UTF-8 decode
        plus the same ``max(len(stop))-1`` hold-back scan as
        :meth:`complete_batch_stream`, so slot-path stream ≡ non-stream
        for the same request.  Calls ``emit(delta, finish_or_None)`` as
        text becomes safe; returns ``(text, n_completion_tokens,
        finish)`` with finish stop/length/timeout/aborted.  A scheduler-
        side failure (StepTimeout, device fault) re-raises here, on this
        handler's thread."""
        import codecs
        tok = self.tokenizer
        dec = codecs.getincrementaldecoder("utf-8")("replace")
        hold = max((len(s) for s in stop), default=0)
        parts: list[str] = []
        buf = ""
        n_comp = 0

        def push(delta, finish):
            parts.append(delta)
            emit(delta, finish)

        stopped = False
        for t in ticket.tokens():
            if is_aborted is not None and is_aborted():
                ticket.cancel("aborted")
                break
            n_comp += 1
            buf += dec.decode(tok.decode_piece(prev, t))
            prev = t
            cuts = [c for c in (buf.find(s) for s in stop) if c != -1]
            if cuts:
                # the generation keeps running until the scheduler honors
                # the cancel; tokens past the stop are never decoded here
                ticket.cancel("stop")
                push(buf[:min(cuts)], "stop")
                stopped = True
                break
            if hold and len(buf) >= hold:
                push(buf[:len(buf) - (hold - 1)], None)
                buf = buf[len(buf) - (hold - 1):]
            elif not hold and buf:
                push(buf, None)
                buf = ""
        if stopped:
            return "".join(parts), n_comp, "stop"
        finish = ticket.finish or "aborted"
        buf += dec.decode(b"", True)
        cuts = [c for c in (buf.find(s) for s in stop) if c != -1]
        if cuts:
            buf = buf[:min(cuts)]
            finish = "stop"
        push(buf, finish)
        return "".join(parts), n_comp, finish

    def handoff_resume(self, ticket, extra: dict, emitted_chars: int,
                       emit, is_aborted=None) -> tuple[str, int, str]:
        """Drive an imported hand-off request to completion (the
        ``/admin/import`` twin of :meth:`sched_drain`).

        The exporter's completion tokens (``extra["completion"]``) are
        replayed through a fresh incremental UTF-8 decoder so the decode
        and stop-scan state land exactly where the exporter's stream
        stood; only text beyond ``emitted_chars`` — the characters the
        router already forwarded to the client — is emitted.  The client
        therefore sees one seamless stream across the replica move.
        Returns ``(full_completion_text, total_completion_tokens,
        finish)``; token totals include the replayed tokens, so usage
        accounting survives the hop."""
        import codecs
        tok = self.tokenizer
        stop = [str(s) for s in extra.get("stop") or []]
        hold = max((len(s) for s in stop), default=0)
        dec = codecs.getincrementaldecoder("utf-8")("replace")
        prompt = [int(x) for x in extra["prompt"]]
        replay = [int(x) for x in extra.get("completion") or []]
        prev = prompt[-1]
        full = ""
        cursor = max(0, int(emitted_chars))

        def feed(t):
            nonlocal full, prev
            full += dec.decode(tok.decode_piece(prev, t))
            prev = t

        def flush(limit, finish=None):
            nonlocal cursor
            delta = full[cursor:limit] if limit > cursor else ""
            if delta or finish is not None:
                emit(delta, finish)
            cursor = max(cursor, limit)

        for t in replay:
            feed(t)
        n_comp = len(replay)
        stopped = False
        for t in ticket.tokens():
            if is_aborted is not None and is_aborted():
                ticket.cancel("aborted")
                break
            n_comp += 1
            feed(t)
            # global stop-scan: a stop wholly inside the exporter's
            # already-emitted prefix cannot exist (its own hold-back scan
            # would have fired), so any cut found here is new text
            cuts = [c for c in (full.find(s) for s in stop) if c != -1]
            if cuts:
                ticket.cancel("stop")
                flush(min(cuts), "stop")
                stopped = True
                break
            flush(len(full) - (hold - 1) if hold else len(full))
        if stopped:
            return full[:cursor], n_comp, "stop"
        finish = ticket.finish or "aborted"
        full += dec.decode(b"", True)
        cuts = [c for c in (full.find(s) for s in stop) if c != -1]
        limit = len(full)
        if cuts:
            limit = min(cuts)
            finish = "stop"
        flush(limit, finish)
        return full[:limit], n_comp, finish


def make_handler(state: ApiState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # socket read/write timeout (satellite fix: the reference-shaped
        # bug was a blocking read with no timeout wedging the server —
        # socket.cpp; here a stalled peer costs one 408/disconnect, never
        # a hung thread).  BaseRequestHandler.setup() applies it.
        timeout = state.io_timeout if state.io_timeout > 0 else None

        def log_message(self, fmt, *a):
            _log.debug("http", extra={"method": self.command,
                                      "path": self.path})

        def send_response(self, *a, **kw):
            self._began_response = True
            super().send_response(*a, **kw)

        def _begin_request(self) -> str:
            """Assign the request ID at accept time: a client-supplied
            ``X-Request-Id`` is echoed (sanitized — it lands in logs and
            response headers verbatim) else one is generated.  Set into
            the log contextvar so every record on this thread — server,
            engine, faults, snapshot — carries it."""
            client = self.headers.get("X-Request-Id") or ""
            rid = _RID_RE.sub("", client)[:_RID_MAX] or new_request_id()
            self._rid = rid
            # router→replica hops stamp X-Dllama-Hop (the router's hop
            # id) so this replica's flight record for the request links
            # back to the router-side ring (fleet correlation satellite)
            hop = self.headers.get("X-Dllama-Hop") or ""
            self._hop = _RID_RE.sub("", hop)[:_RID_MAX] or None
            # fleet trace context (X-Dllama-Trace): the router stamps
            # one id at accept and propagates it on every hop; binding
            # it to the rid here means scheduler-loop spans (recorded
            # with rid=t.rid) resolve to the same trace without any
            # call-site change, and DLREQ01 exports can carry it to the
            # replica that resumes the request.
            trace = obs_trace.sanitize_trace_id(
                self.headers.get("X-Dllama-Trace"))
            self._trace = trace
            obs_trace.trace_id_var.set(trace)
            if trace:
                obs_trace.set_trace(rid, trace)
            # QoS class from the transport header; the body field (when
            # present) overrides it in do_POST.  An unknown header value
            # is ignored — the router relays client headers verbatim and
            # a typo'd class must not fail the request.
            hdr = self.headers.get("X-Dllama-Priority")
            self._prio_hdr = priority_level(hdr) if hdr else None
            set_request_id(rid)
            return rid

        def _rid_header(self) -> None:
            rid = getattr(self, "_rid", None)
            if rid:
                self.send_header("X-Request-Id", rid)
            trace = getattr(self, "_trace", None)
            if trace:
                self.send_header("X-Dllama-Trace", trace)

        def _json(self, code: int, obj: dict, headers: dict | None = None):
            data = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self._rid_header()
            for k, v in (headers or {}).items():
                self.send_header(k, str(v))
            if state.draining:
                # drain wants connection threads gone promptly, not
                # parked in keep-alive reads until the io timeout
                self.close_connection = True
            self.end_headers()
            try:
                self.wfile.write(data)
            except OSError:
                self.close_connection = True

        def _text(self, code: int, text: str, content_type: str):
            data = text.encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            self._rid_header()
            if state.draining:
                self.close_connection = True
            self.end_headers()
            try:
                self.wfile.write(data)
            except OSError:
                self.close_connection = True

        def _bytes(self, code: int, data: bytes, content_type: str):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            self._rid_header()
            if state.draining:
                self.close_connection = True
            self.end_headers()
            try:
                self.wfile.write(data)
            except OSError:
                self.close_connection = True

        def _safe_write(self, data: bytes, aborted: list) -> None:
            """Stream-tail write that treats a dead client as abort, not
            as an unhandled thread exception."""
            if aborted[0]:
                return
            try:
                self.wfile.write(data)
                self.wfile.flush()
            except OSError:
                aborted[0] = True
                state.metrics.bump("client_disconnects")

        def _maybe_500(self, err: Exception) -> None:
            """Answer 500 if no response has started (a mid-stream failure
            already has its own SSE error-event path)."""
            if getattr(self, "_began_response", False):
                return
            try:
                self._json(500, {"error": {"message": str(err),
                                           "type": "server_error"}})
            except OSError:
                pass

        def _read_body(self) -> dict | None:
            """Read + parse the JSON body.  Returns None when a response
            (408/400/413) was already sent or the client vanished.  The
            ``server.read_body`` fault point stands in for a stalled
            client (a delay outlasting ``--io-timeout``, or
            ``raise:TimeoutError`` directly)."""
            try:
                FAULTS.fire("server.read_body")
                length = int(self.headers.get("Content-Length", 0) or 0)
                if length > MAX_BODY_BYTES:
                    self.close_connection = True
                    self._json(413, {"error": "request body too large"})
                    return None
                raw = self.rfile.read(length) if length > 0 else b""
                if len(raw) < length:  # peer closed mid-body
                    state.metrics.bump("client_disconnects")
                    self.close_connection = True
                    return None
            except TimeoutError:  # socket.timeout alias: stalled client
                state.metrics.bump("read_timeouts_408")
                self.close_connection = True
                self._json(408, {"error": "timed out reading request body"})
                return None
            except (TypeError, ValueError):
                self._json(400, {"error": "bad Content-Length"})
                return None
            try:
                body = json.loads(raw or b"{}")
            except json.JSONDecodeError as e:
                self._json(400, {"error": f"bad request: {e}"})
                return None
            if not isinstance(body, dict):
                self._json(400, {"error": "request body must be a JSON object"})
                return None
            return body

        def _completions(self, body: dict, deadline: float | None,
                         timer: _StreamTimer | None = None):
            """OpenAI text-completion endpoint; ``prompt`` may be a list
            and ``n`` replicates each prompt — every resulting row decodes
            as a distinct stream in one lockstep batch."""
            try:
                prompt = body.get("prompt")
                prompts = [str(p) for p in prompt] if isinstance(prompt, list) \
                    else [str(prompt or "")]
                if not any(prompts):
                    self._json(400, {"error": "prompt required"})
                    return
                n = int(body.get("n") or 1)
                if n > 1:  # n samples per prompt, row-major like OpenAI
                    prompts = [p for p in prompts for _ in range(n)]
                temperature = float(body["temperature"]) \
                    if body.get("temperature") is not None else state.default_temperature
                top_p = float(body["top_p"]) \
                    if body.get("top_p") is not None else state.default_topp
                max_tokens = int(body.get("max_tokens") or 0)
                seed = int(body["seed"]) if body.get("seed") is not None else None
                stop = body.get("stop")
                stop = [stop] if isinstance(stop, str) else \
                    [str(s) for s in stop] if isinstance(stop, list) else []
                echo = bool(body.get("echo"))
                stream = bool(body.get("stream"))
                logprobs = body.get("logprobs")
                if logprobs is not None:
                    logprobs = max(0, min(int(logprobs), 5))  # OpenAI cap
            except (TypeError, ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"bad request: {e}"})
                return
            if stream and logprobs is not None:
                self._json(400, {"error": "logprobs with stream is not "
                                          "supported; request them "
                                          "non-streaming"})
                return
            if state.batch_engine is None:
                self._json(400, {"error": "batched serving not enabled; "
                                          "start the server with --batch-slots N"})
                return
            if logprobs is not None and state.batch_engine.sp > 1:
                # reject BEFORE the generation forward: score_batch raises
                # on sp meshes, and the handler must answer 400, not drop
                # the connection after burning the decode
                self._json(400, {"error": "logprobs is not supported on "
                                          "sequence-parallel (--sp) servers"})
                return
            created = int(time.time())
            cid = f"cmpl-{uuid.uuid4().hex[:12]}"
            if stream:
                # validate BEFORE committing to SSE: an invalid request
                # gets the same 400 it would get without stream=true
                try:
                    plan = state.plan_batch(prompts, max_tokens)
                except ContextOverflow as e:
                    self._json(400, state.overflow_body(e))
                    return
                # SSE chunks carry per-row deltas tagged by choice index —
                # every live row streams concurrently from the one
                # lockstep batch (echo is a non-streaming nicety; ignored)
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self._rid_header()
                self.end_headers()

                aborted = [False]

                def emit(idx, delta, finish):
                    # a dead client mid-stream flips `aborted`; the batch
                    # loop polls it (is_aborted) and stops decoding at the
                    # next chunk instead of generating into a broken pipe
                    if aborted[0]:
                        return
                    try:
                        with obs_trace.span("api.emit", idx=idx):
                            FAULTS.fire("server.emit_delta")
                            chunk = {"id": cid, "object": "text_completion",
                                     "created": created, "model": state.model_name,
                                     "choices": [{"text": delta, "index": idx,
                                                  "finish_reason": finish,
                                                  "logprobs": None}]}
                            self.wfile.write(
                                f"data: {json.dumps(chunk)}\n\n".encode())
                            self.wfile.flush()
                        if timer is not None:
                            timer.tick()
                        if finish == "timeout":
                            state.metrics.bump("deadline_timeouts")
                    except OSError:
                        aborted[0] = True
                        state.metrics.bump("client_disconnects")

                try:
                    state.complete_batch_stream(
                        prompts, temperature=temperature, top_p=top_p,
                        max_tokens=max_tokens, seed=seed, stop=stop,
                        emit=emit, plan=plan, deadline=deadline,
                        is_aborted=lambda: aborted[0])
                except Exception as e:
                    # mid-stream failure: an OpenAI-shaped error event so
                    # clients can tell a died stream from a short success,
                    # then [DONE] (they block on it); unexpected errors
                    # still propagate to the server log afterwards
                    err = {"error": {"message": str(e),
                                     "type": "invalid_request_error"
                                     if isinstance(e, ContextOverflow)
                                     else "server_error"}}
                    self._safe_write(f"data: {json.dumps(err)}\n\n".encode()
                                     + b"data: [DONE]\n\n", aborted)
                    if not isinstance(e, ContextOverflow):
                        raise
                    return
                self._safe_write(b"data: [DONE]\n\n", aborted)
                return
            try:
                choices, n_prompt, n_completion = state.complete_batch(
                    prompts, temperature=temperature, top_p=top_p,
                    max_tokens=max_tokens, seed=seed, stop=stop, echo=echo,
                    logprobs=logprobs, deadline=deadline)
            except ContextOverflow as e:
                self._json(400, state.overflow_body(e))
                return
            if any(c["finish_reason"] == "timeout" for c in choices):
                state.metrics.bump("deadline_timeouts")
            self._json(200, {
                "id": cid,
                "object": "text_completion", "created": created,
                "model": state.model_name, "choices": choices,
                "usage": {"prompt_tokens": n_prompt,
                          "completion_tokens": n_completion,
                          "total_tokens": n_prompt + n_completion}})

        def do_GET(self):
            self._begin_request()
            path, _, query = self.path.partition("?")
            if path == "/v1/models":
                self._json(200, {"object": "list", "data": [{
                    "id": state.model_name, "object": "model",
                    "created": int(time.time()), "owned_by": "user"}]})
            elif path in ("/health", "/healthz"):
                # liveness probes keep getting a 200 during drain (the
                # process IS alive); orchestrators read "status"/"ready"
                # for the readiness decision
                self._json(200, state.health())
            elif path == "/metrics":
                # one registry, two formats (obs/metrics.py): Prometheus
                # text 0.0.4 under Accept/?format negotiation, else the
                # backward-compatible JSON dict — registry globals
                # (integrity counters, histograms, schema_version) with
                # this server's per-instance serving counters on top
                q = parse_qs(query)
                accept = self.headers.get("Accept") or ""
                if (q.get("format", [""])[0] == "prometheus"
                        or "text/plain" in accept or "openmetrics" in accept):
                    self._text(200, obs_metrics.render_prometheus(),
                               "text/plain; version=0.0.4; charset=utf-8")
                else:
                    merged = obs_metrics.snapshot_json()
                    merged.update(state.metrics.snapshot())
                    self._json(200, merged)
            elif path == "/debug/trace":
                # Chrome trace_event JSON for the last N requests' spans
                # (obs/trace.py ring buffer; tools/trace_dump.py wraps
                # this).  ?since=<seq> switches to the raw incremental
                # export — sequenced spans plus a perf/wall clock sample
                # — which the router's fleet stitcher and fleet_top poll
                # instead of re-downloading the whole ring every tick.
                qs = parse_qs(query)
                if "since" in qs:
                    try:
                        since = int(qs["since"][0])
                    except ValueError:
                        since = 0
                    self._json(200, obs_trace.raw(since))
                    return
                try:
                    last = int(q[0]) if (q := qs.get("last")) else 20
                except ValueError:
                    last = 20
                self._json(200, obs_trace.trace_json(last))
            elif path == "/debug/events":
                # the pod event journal (obs/events.py): this replica's
                # own lifecycle events (preempt/resume/handoff); the
                # router/pod process serves its fleet-level journal at
                # the same path.  ?since=<seq> tails incrementally.
                qs = parse_qs(query)
                since = None
                if "since" in qs:
                    try:
                        since = int(qs["since"][0])
                    except ValueError:
                        since = 0
                self._json(200, obs_events.snapshot(since))
            elif path == "/debug/requests":
                # flight recorder (obs/flight.py): newest-first summaries
                try:
                    n = int(q[0]) if (q := parse_qs(query).get("n")) else 50
                except ValueError:
                    n = 50
                self._json(200, {"requests": obs_flight.recent(n)})
            elif path.startswith("/debug/requests/"):
                rid = path[len("/debug/requests/"):]
                rec = obs_flight.get(rid)
                if rec is None:
                    self._json(404, {"error": f"no flight record for "
                                              f"request id {rid!r}"})
                else:
                    self._json(200, rec)
            elif path.startswith("/admin/export/"):
                # drain-time hand-off pickup (fleet router): one-shot —
                # the record leaves this process with the response, so a
                # double-fetch cannot resume the same request twice
                rid = path[len("/admin/export/"):]
                rec = state.handoff_records.pop(rid, None)
                if rec is None:
                    self._json(404, {"error": f"no hand-off record for "
                                              f"request id {rid!r}"})
                else:
                    obs_metrics.HANDOFF_EXPORTS.inc()
                    _log.info("handoff_export_served", extra={
                        "bytes": len(rec)})
                    self._bytes(200, rec, "application/octet-stream")
            elif path.startswith("/admin/checkpoint/"):
                # proactive mid-stream checkpoint (fleet router crash
                # resume): a NON-destructive DLREQ01 snapshot of one
                # live slot — the request keeps decoding here.  Unlike
                # /admin/export this is repeatable; the router caches
                # the newest record and resumes from it if this replica
                # later dies ungracefully.
                rid = path[len("/admin/checkpoint/"):]
                if not state.handoff:
                    self._json(404, {"error": "hand-off is not enabled "
                                              "(--handoff)"})
                    return
                try:
                    rec = state.scheduler.checkpoint_export(rid)
                except Exception as e:  # noqa: BLE001 — a failed
                    # checkpoint must never take down the live request
                    _log.warning("checkpoint_export_failed", extra={
                        "rid": rid, "error": repr(e)})
                    rec = None
                if rec is None:
                    self._json(404, {"error": f"no live slot for "
                                              f"request id {rid!r}"})
                else:
                    _log.debug("checkpoint_export_served", extra={
                        "rid": rid, "bytes": len(rec)})
                    self._bytes(200, rec, "application/octet-stream")
            elif path == "/debug/timeline":
                # slot timeline + goodput decomposition (obs/flight.py +
                # scheduler accounting); trace_dump.py --slots renders it
                try:
                    n = int(q[0]) if (q := parse_qs(query).get("n")) \
                        else 256
                except ValueError:
                    n = 256
                self._json(200, {
                    "slots": (state.scheduler.engine.batch
                              if state.scheduler is not None else 0),
                    "steps": obs_flight.TIMELINE.snapshot(n),
                    "components_ms":
                        obs_metrics.SCHED_STEP_TIME_MS.json_value(),
                    "goodput_ratio":
                        obs_metrics.SCHED_GOODPUT_RATIO.json_value(),
                    "host_gap_ms":
                        obs_metrics.SCHED_HOST_GAP_MS.json_value(),
                })
            else:
                self._json(404, {"error": "not found"})

        def _debug_profile(self, query: str):
            """``POST /debug/profile?steps=N&top=K`` — live per-op device
            profile of the serving engine (docs/OBSERVABILITY.md).

            Holds the engine mutex, traces N single-token decode steps
            under the XLA profiler (runtime/profiling.traced_op_times) and
            answers with the top-K ops by device time plus the
            compute/collective split.  POST (not GET) because it perturbs
            the serving engine: it borrows the mutex for ~N steps and
            advances/rewinds the KV position.  Answers 503 while draining
            and a clean 503 when the xplane proto tooling is absent."""
            from ..runtime.profiling import summarize_split, top_ops, \
                traced_op_times
            if state.draining:
                self._json(503, {"error": "server is draining"},
                           headers={"Retry-After": jittered_retry_after(30)})
                return
            q = parse_qs(query)

            def qint(name, default, lo, hi):
                try:
                    v = int(q.get(name, [default])[0])
                except ValueError:
                    v = default
                return max(lo, min(hi, v))

            steps = qint("steps", 3, 1, 16)
            top = qint("top", 10, 1, 50)
            eng = state.engine
            with state.engine_lock:
                state.mark_active(True)
                try:
                    if eng.pos + steps + 1 > eng.seq_len:
                        # no room to decode: drop the conversation state
                        # (debug endpoint; same reset path as NumericFault)
                        state.naive_cache.clear()
                        eng.reset()
                    pos0 = eng.pos
                    try:
                        # warm step OUTSIDE the trace so a fresh T=1
                        # executable books compile time into the compile
                        # histogram, not into the op profile
                        eng.decode_one(1)
                        times = traced_op_times(
                            lambda: eng.decode_one(1), steps=steps)
                    finally:
                        # profiled steps are dead rows past the live
                        # prefix — same overshoot invariant as an aborted
                        # generation
                        eng.pos = pos0
                finally:
                    state.mark_active(False)
            if times is None:
                self._json(503, {
                    "error": "per-op profiling unavailable (xplane proto "
                             "tooling missing or backend produced no "
                             "trace)"})
                return
            split = summarize_split(times, steps)
            ops = [{"op": op, "ms": round(ms, 4)}
                   for op, ms in top_ops(times, top, steps)]
            _log.info("profile", extra={"steps": steps,
                                        "n_ops": len(times)})
            self._json(200, {
                "steps": steps,
                "devices": eng.mesh.size,
                "compute_ms": round(split["compute_ms"], 4),
                "collective_ms": round(split["collective_ms"], 4),
                "collective_pct": round(split["collective_pct"], 2),
                "ops": ops,
            })

        def _sched_eligible(self, body: dict) -> bool:
            """True when this request can ride the slot scheduler
            (tentpole: decode-step admission instead of the engine
            mutex).  The mutex path keeps everything the slot engine
            cannot express: multi-prompt lockstep, n>1, logprobs scoring,
            echo, and seeded sampling (slot rows share the engine's RNG
            stream, so per-request seeds are only reproducible when the
            request owns the engine — greedy requests are exact on both
            paths)."""
            if state.scheduler is None:
                return False
            try:
                if int(body.get("n") or 1) != 1:
                    return False
                temperature = float(body["temperature"]) \
                    if body.get("temperature") is not None \
                    else state.default_temperature
            except (TypeError, ValueError):
                return False  # malformed: the mutex handlers own the 400
            if body.get("seed") is not None and temperature != 0.0:
                return False
            if self.path == "/v1/completions":
                return not isinstance(body.get("prompt"), list) \
                    and body.get("logprobs") is None \
                    and not body.get("echo")
            return True

        def _submit_or_reject(self, ids, max_tokens, *, temperature,
                              top_p, eos_id, deadline, stop=None):
            """sched_submit with every refusal mapped to its HTTP answer
            (the same codes the mutex path's admission uses).  Returns
            the ticket, or None when a response was already sent."""
            try:
                return state.sched_submit(
                    ids, max_tokens, temperature=temperature, top_p=top_p,
                    eos_id=eos_id, deadline=deadline, stop=stop,
                    priority=getattr(self, "_priority", 1))
            except ContextOverflow as e:
                self._json(400, state.overflow_body(e))
            except SchedulerSaturated as e:
                state.metrics.bump("requests_rejected_429")
                self._json(429, state.overflow_body(e),
                           headers={"Retry-After": jittered_retry_after(
                               state.retry_after_hint())})
            except SchedulerClosed:
                state.metrics.bump("requests_rejected_503")
                self._json(503, {"error": "server is draining; "
                                          "no new requests accepted"},
                           headers={"Retry-After": jittered_retry_after(30)})
            return None

        def _completions_sched(self, body: dict, deadline: float | None,
                               timer: _StreamTimer | None = None):
            """Single-prompt /v1/completions over the slot scheduler:
            joins a batch slot at the next decode-step boundary instead
            of waiting for the engine mutex."""
            try:
                prompt = body.get("prompt")
                text = str(prompt or "")
                if not text:
                    self._json(400, {"error": "prompt required"})
                    return
                temperature = float(body["temperature"]) \
                    if body.get("temperature") is not None \
                    else state.default_temperature
                top_p = float(body["top_p"]) \
                    if body.get("top_p") is not None else state.default_topp
                max_tokens = int(body.get("max_tokens") or 0)
                stop = body.get("stop")
                stop = [stop] if isinstance(stop, str) else \
                    [str(s) for s in stop] if isinstance(stop, list) else []
                stream = bool(body.get("stream"))
            except (TypeError, ValueError) as e:
                self._json(400, {"error": f"bad request: {e}"})
                return
            tok = state.tokenizer
            ids = tok.encode(text,
                             add_bos=state.scheduler.engine.cfg.add_bos)
            eos_id = tok.eos_id if tok.eos_id >= 0 else tok.chat_eos_id
            # submit BEFORE any SSE commitment so capacity/overflow
            # refusals answer with their proper status codes
            ticket = self._submit_or_reject(
                ids, max_tokens, temperature=temperature, top_p=top_p,
                eos_id=eos_id, deadline=deadline, stop=stop)
            if ticket is None:
                return
            created = int(time.time())
            cid = f"cmpl-{uuid.uuid4().hex[:12]}"
            if stream:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self._rid_header()
                self.end_headers()
                aborted = [False]

                def emit(delta, finish):
                    if aborted[0]:
                        return
                    try:
                        with obs_trace.span("api.emit"):
                            FAULTS.fire("server.emit_delta")
                            chunk = {"id": cid, "object": "text_completion",
                                     "created": created,
                                     "model": state.model_name,
                                     "choices": [{"text": delta, "index": 0,
                                                  "finish_reason": finish,
                                                  "logprobs": None}]}
                            self.wfile.write(
                                f"data: {json.dumps(chunk)}\n\n".encode())
                            self.wfile.flush()
                        if timer is not None:
                            timer.tick()
                        if finish == "timeout":
                            state.metrics.bump("deadline_timeouts")
                    except OSError:
                        aborted[0] = True
                        state.metrics.bump("client_disconnects")

                try:
                    state.sched_drain(ticket, ids[-1], stop=stop,
                                      emit=emit,
                                      is_aborted=lambda: aborted[0])
                except Exception as e:
                    ticket.cancel("aborted")
                    err = {"error": {"message": str(e),
                                     "type": "server_error"}}
                    self._safe_write(f"data: {json.dumps(err)}\n\n".encode()
                                     + b"data: [DONE]\n\n", aborted)
                    raise
                self._safe_write(b"data: [DONE]\n\n", aborted)
                return
            emit = (lambda d, f: timer.tick()) if timer is not None \
                else (lambda d, f: None)
            try:
                reply, n_comp, finish = state.sched_drain(
                    ticket, ids[-1], stop=stop, emit=emit)
            finally:
                ticket.cancel("aborted")  # no-op unless we errored out
            if finish == "timeout":
                state.metrics.bump("deadline_timeouts")
            self._json(200, {
                "id": cid, "object": "text_completion", "created": created,
                "model": state.model_name,
                "choices": [{"text": reply, "index": 0,
                             "finish_reason": finish, "logprobs": None}],
                "usage": {"prompt_tokens": len(ids),
                          "completion_tokens": n_comp,
                          "total_tokens": len(ids) + n_comp}})

        def _chat_sched(self, body: dict, deadline: float | None,
                        timer: _StreamTimer | None = None):
            """Chat over the slot scheduler.  Without prefix reuse this
            is the spillover path (a second concurrent conversation joins
            a batch slot instead of queueing on the engine mutex) and the
            slot engine re-prefills the full templated history each turn.
            With the paged radix cache it is the PRIMARY chat path: the
            scheduler matches the templated history against the tree at
            admission, binds the already-cached prefix pages copy-free,
            and prefills only the new suffix — the NaiveCache's
            prefix-resume win, but shared across conversations and
            requiring no mutex.  The NaiveCache itself is neither
            consulted nor updated here."""
            try:
                params = parse_request(body, state.default_temperature,
                                       state.default_topp)
                if not params.messages:
                    self._json(400, {"error": "messages required"})
                    return
            except (TypeError, ValueError) as e:
                self._json(400, {"error": f"bad request: {e}"})
                return
            tok = state.tokenizer
            items = [ChatItem(m.role, m.content) for m in params.messages]
            ids = tok.encode(state.template.generate(items, True),
                             add_bos=True)
            stops = state.base_stops + params.stop
            ticket = self._submit_or_reject(
                ids, params.max_tokens, temperature=params.temperature,
                top_p=params.top_p, eos_id=tok.chat_eos_id,
                deadline=deadline, stop=stops)
            if ticket is None:
                return
            created = int(time.time())
            cid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
            if params.stream:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self._rid_header()
                self.end_headers()
                aborted = [False]

                def emit(delta, finish):
                    if aborted[0] or not delta:
                        return
                    try:
                        with obs_trace.span("api.emit"):
                            FAULTS.fire("server.emit_delta")
                            chunk = {"id": cid,
                                     "object": "chat.completion.chunk",
                                     "created": created,
                                     "model": state.model_name,
                                     "choices": [{"index": 0,
                                                  "delta": {"content": delta},
                                                  "finish_reason": None}]}
                            self.wfile.write(
                                f"data: {json.dumps(chunk)}\n\n".encode())
                            self.wfile.flush()
                        if timer is not None:
                            timer.tick()
                    except OSError:
                        aborted[0] = True
                        state.metrics.bump("client_disconnects")

                _, _, finish = state.sched_drain(
                    ticket, ids[-1], stop=stops, emit=emit,
                    is_aborted=lambda: aborted[0])
                if finish == "aborted" or aborted[0]:
                    return  # nobody is listening
                if finish == "length":
                    finish = "stop"  # the chat budget contract (complete())
                if finish == "timeout":
                    state.metrics.bump("deadline_timeouts")
                final = {"id": cid, "object": "chat.completion.chunk",
                         "created": created, "model": state.model_name,
                         "choices": [{"index": 0, "delta": {},
                                      "finish_reason": finish}]}
                self._safe_write(f"data: {json.dumps(final)}\n\n".encode()
                                 + b"data: [DONE]\n\n", aborted)
                return
            emit = (lambda d, f: timer.tick()) if timer is not None \
                else (lambda d, f: None)
            reply, n_comp, finish = state.sched_drain(
                ticket, ids[-1], stop=stops, emit=emit)
            if finish == "length":
                finish = "stop"
            if finish == "timeout":
                state.metrics.bump("deadline_timeouts")
            self._json(200, {
                "id": cid, "object": "chat.completion", "created": created,
                "model": state.model_name,
                "choices": [{"index": 0, "finish_reason": finish,
                             "message": {"role": "assistant",
                                         "content": reply}}],
                "usage": {"prompt_tokens": len(ids),
                          "completion_tokens": n_comp,
                          "total_tokens": len(ids) + n_comp}})

        def _admin_import(self, query: str):
            """``POST /admin/import?emitted_chars=N`` — re-bind a DLREQ01
            hand-off record (octet-stream body) into a free slot and
            stream the request's remaining completion back as
            text_completion-shaped SSE deltas (the router adapts the
            shape for chat/non-streaming clients).  ``emitted_chars`` is
            how many completion characters the router already forwarded
            to the client from the exporting replica; only text beyond
            it is emitted.  409 on geometry mismatch so the router can
            try another peer."""
            if not state.handoff:
                self._json(404, {"error": "hand-off is not enabled "
                                          "(--handoff)"})
                return
            q = parse_qs(query)
            try:
                emitted_chars = max(0, int(q.get("emitted_chars",
                                                 ["0"])[0]))
            except ValueError:
                emitted_chars = 0
            try:
                length = int(self.headers.get("Content-Length", 0) or 0)
            except (TypeError, ValueError):
                self._json(400, {"error": "bad Content-Length"})
                return
            if length > MAX_HANDOFF_BYTES:
                self.close_connection = True
                self._json(413, {"error": "hand-off record too large"})
                return
            if length <= 0:
                self._json(400, {"error": "hand-off record body required"})
                return
            try:
                raw = self.rfile.read(length)
            except TimeoutError:
                state.metrics.bump("read_timeouts_408")
                self.close_connection = True
                self._json(408, {"error": "timed out reading hand-off "
                                          "record"})
                return
            if len(raw) < length:
                state.metrics.bump("client_disconnects")
                self.close_connection = True
                return
            try:
                ticket, extra = state.scheduler.import_request(raw)
            except SnapshotMismatch as e:
                obs_metrics.HANDOFF_IMPORT_REJECTS.inc()
                self._json(409, {"error": str(e)})
                return
            except ArtifactError as e:
                obs_metrics.HANDOFF_IMPORT_REJECTS.inc()
                self._json(400, {"error": str(e)})
                return
            except ContextOverflow as e:
                self._json(400, state.overflow_body(e))
                return
            except SchedulerSaturated as e:
                state.metrics.bump("requests_rejected_429")
                self._json(429, state.overflow_body(e),
                           headers={"Retry-After": jittered_retry_after(
                               state.retry_after_hint())})
                return
            except SchedulerClosed:
                state.metrics.bump("requests_rejected_503")
                self._json(503, {"error": "server is draining; "
                                          "no new requests accepted"},
                           headers={"Retry-After": jittered_retry_after(30)})
                return
            obs_metrics.HANDOFF_IMPORTS.inc()
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self._rid_header()
            self.end_headers()
            aborted = [False]

            def emit(delta, finish):
                if aborted[0]:
                    return
                try:
                    chunk = {"object": "text_completion",
                             "model": state.model_name,
                             "choices": [{"text": delta, "index": 0,
                                          "finish_reason": finish,
                                          "logprobs": None}]}
                    self.wfile.write(
                        f"data: {json.dumps(chunk)}\n\n".encode())
                    self.wfile.flush()
                except OSError:
                    aborted[0] = True
                    state.metrics.bump("client_disconnects")

            state.mark_active(True)
            try:
                text, n_comp, finish = state.handoff_resume(
                    ticket, extra, emitted_chars, emit,
                    is_aborted=lambda: aborted[0])
            except Exception as e:
                ticket.cancel("aborted")
                err = {"error": {"message": str(e),
                                 "type": "server_error"}}
                self._safe_write(f"data: {json.dumps(err)}\n\n".encode()
                                 + b"data: [DONE]\n\n", aborted)
                raise
            finally:
                state.mark_active(False)
            usage = {"object": "handoff.usage",
                     "usage": {"prompt_tokens": len(extra.get("prompt")
                                                    or []),
                               "completion_tokens": n_comp,
                               "finish_reason": finish}}
            self._safe_write(f"data: {json.dumps(usage)}\n\n".encode()
                             + b"data: [DONE]\n\n", aborted)

        def do_POST(self):
            self._begin_request()
            ppath, _, pquery = self.path.partition("?")
            if ppath == "/debug/profile":
                self._debug_profile(pquery)
                return
            if ppath == "/admin/import":
                self._admin_import(pquery)
                return
            if self.path not in ("/v1/chat/completions", "/v1/completions"):
                self._json(404, {"error": "not found"})
                return
            _log.info("accept", extra={"path": self.path})
            body = self._read_body()
            if body is None:
                return
            # QoS class: body field wins over X-Dllama-Priority, default
            # standard.  A malformed body value is a 400 (the header is
            # lenient; the body is the caller's explicit contract).
            prio_body = body.get("priority")
            if prio_body is not None:
                lvl = priority_level(prio_body)
                if lvl is None:
                    self._json(400, {
                        "error": f"unknown priority class {prio_body!r}; "
                                 "expected interactive|standard|batch"})
                    return
                self._priority = lvl
            else:
                self._priority = self._prio_hdr \
                    if self._prio_hdr is not None \
                    else PRIORITY_LEVELS["standard"]
            prio_name = PRIORITY_NAMES.get(self._priority, "standard")
            # SLO-driven shedding: drop best-effort admissions while the
            # error budget burns, BEFORE this request counts against
            # capacity (interactive traffic is never shed here)
            if state.should_shed(self._priority):
                state.metrics.bump("requests_rejected_429")
                obs_metrics.ADMISSIONS_SHED.inc(prio_name)
                _log.info("reject", extra={"status": 429,
                                           "reason": "slo_shed",
                                           "priority": prio_name})
                self._json(429, {"error": "SLO error budget burning; "
                                          f"shedding {prio_name}-class "
                                          "admissions — retry later"},
                           headers={"Retry-After": jittered_retry_after(
                               state.retry_after_hint())})
                return
            verdict = state.try_enter()
            if verdict == "draining":
                state.metrics.bump("requests_rejected_503")
                _log.info("reject", extra={"status": 503,
                                           "reason": "draining"})
                self._json(503, {"error": "server is draining; "
                                          "no new requests accepted"},
                           headers={"Retry-After": jittered_retry_after(30)})
                return
            if verdict == "full":
                state.metrics.bump("requests_rejected_429")
                _log.info("reject", extra={"status": 429, "reason": "full"})
                self._json(429, {"error": f"server at capacity "
                                          f"({state.max_pending} requests "
                                          "pending); retry later"},
                           headers={"Retry-After": jittered_retry_after(
                               state.retry_after_hint())})
                return
            t0 = time.monotonic()
            deadline = state.request_deadline(body)
            # stream timer starts at admission: queue wait counts into TTFT
            timer = _StreamTimer(rid=self._rid)
            # flight record opens at admission; the scheduler path merges
            # its per-dispatch detail into this same record by request ID
            # (hop = the router's ring id, for cross-fleet correlation)
            if getattr(self, "_hop", None):
                obs_flight.submit(self._rid, path=self.path, hop=self._hop,
                                  priority=prio_name)
            else:
                obs_flight.submit(self._rid, path=self.path,
                                  priority=prio_name)
            ok = False
            # entered here and left in the finally below: the handler's
            # whole try block is the span
            req_span = obs_trace.span("api.request", path=self.path)
            req_span.__enter__()
            try:
                locked = False
                use_sched = False
                if self._sched_eligible(body):
                    if self.path == "/v1/completions":
                        use_sched = True
                    elif state.scheduler.prefix_cache is not None:
                        # paged scheduler with a radix prefix cache: chat
                        # always rides a slot — repeated system prompts and
                        # growing conversation histories match the tree and
                        # bind shared pages copy-free, which beats the
                        # mutex path's single-conversation NaiveCache (and
                        # the old spillover behavior of re-prefilling the
                        # full history on every contended request)
                        use_sched = True
                    else:
                        # chat spillover: the mutex path keeps the
                        # NaiveCache prefix-resume win while uncontended;
                        # under contention the request joins a slot
                        # instead of queueing on the mutex
                        locked = state.engine_lock.acquire(blocking=False)
                        use_sched = not locked
                if use_sched:
                    # slot path: no engine mutex — the scheduler
                    # interleaves this request with whatever else is live
                    # (its sched_admit span records the slot-queue wait)
                    state.mark_active(True)
                    try:
                        if self.path == "/v1/completions":
                            self._completions_sched(body, deadline, timer)
                        else:
                            self._chat_sched(body, deadline, timer)
                    finally:
                        state.mark_active(False)
                else:
                    # THE engine mutex: one generation at a time per KV
                    # cache; the wait here IS the admission queue
                    # try_enter bounded
                    q0 = time.perf_counter()
                    with obs_trace.span("api.lock_wait"):
                        if not locked:
                            state.engine_lock.acquire()
                    q1 = time.perf_counter()
                    obs_metrics.QUEUE_WAIT.observe(q1 - q0)
                    obs_flight.admit(self._rid, queued_ms=(q1 - q0) * 1e3)
                    _log.info("queue", extra={"wait_s": round(q1 - q0, 6)})
                    try:
                        state.mark_active(True)
                        try:
                            if self.path == "/v1/completions":
                                self._completions(body, deadline, timer)
                            else:
                                self._chat(body, deadline, timer)
                        finally:
                            state.mark_active(False)
                    finally:
                        state.engine_lock.release()
                state.metrics.bump("requests_served")
                ok = True
                _log.info("finish", extra={
                    "path": self.path,
                    "duration_s": round(time.monotonic() - t0, 6)})
            except (BrokenPipeError, ConnectionResetError):
                # client gone between chunks with nothing left to send;
                # generation already stopped via the abort flag
                state.metrics.bump("client_disconnects")
                self.close_connection = True
                _log.info("client_disconnect", extra={"path": self.path})
            except NumericFault as e:
                # NaN/Inf logits (--numeric-checks): the KV cache may be
                # poisoned from the step that diverged, so resume is NOT
                # safe — drop the conversation cache and position instead
                # of serving garbage continuations.  The request gets a
                # 500 (counted in numeric_faults via the engine) and the
                # server keeps serving fresh conversations.
                state.metrics.bump("server_errors")
                state.naive_cache.clear()
                state.engine.reset()
                self._maybe_500(e)
                _log.error("error", extra={"path": self.path,
                                           "kind": "NumericFault",
                                           "error": str(e)})
                raise  # surface in the server log — corruption is a page
            except Exception as e:
                state.metrics.bump("server_errors")
                self._maybe_500(e)
                _log.error("error", extra={"path": self.path,
                                           "kind": type(e).__name__,
                                           "error": str(e)})
                raise  # surface in the server log — a 500 is a bug to fix
            finally:
                state.leave(time.monotonic() - t0)
                req_span.__exit__(None, None, None)
                # fallback close for any path that didn't retire with a
                # specific finish (no-op when one already did)
                obs_flight.retire(self._rid, "served" if ok else "error")

        def _chat(self, body: dict, deadline: float | None,
                  timer: _StreamTimer | None = None):
            try:
                params = parse_request(body, state.default_temperature,
                                       state.default_topp)
                if not params.messages:
                    self._json(400, {"error": "messages required"})
                    return
            except (TypeError, ValueError) as e:
                self._json(400, {"error": f"bad request: {e}"})
                return

            created = int(time.time())
            cid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
            if params.n > 1:
                if params.stream:
                    self._json(400, {"error": "stream with n>1 is not "
                                              "supported; request them "
                                              "separately"})
                    return
                if state.batch_engine is None:
                    self._json(400, {"error": "n>1 needs batched serving; "
                                              "start the server with "
                                              "--batch-slots N"})
                    return
                try:
                    n_choices, n_prompt, n_completion = state.complete_n(
                        params, deadline=deadline)
                except ContextOverflow as e:
                    self._json(400, state.overflow_body(e))
                    return
                if any(fin == "timeout" for _, fin in n_choices):
                    state.metrics.bump("deadline_timeouts")
                self._json(200, {
                    "id": cid, "object": "chat.completion", "created": created,
                    "model": state.model_name,
                    "choices": [{"index": i, "finish_reason": fin,
                                 "message": {"role": "assistant", "content": r}}
                                for i, (r, fin) in enumerate(n_choices)],
                    "usage": {"prompt_tokens": n_prompt,
                              "completion_tokens": n_completion,
                              "total_tokens": n_prompt + n_completion}})
                return
            if params.stream:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self._rid_header()
                self.end_headers()

                aborted = [False]

                def emit(delta):
                    # a dead client sets `aborted`; complete() polls it
                    # between chunks (is_aborted) and ends the stream via
                    # drain_generation's normal pos-rewind path
                    if aborted[0]:
                        return
                    try:
                        with obs_trace.span("api.emit"):
                            FAULTS.fire("server.emit_delta")
                            chunk = {"id": cid, "object": "chat.completion.chunk",
                                     "created": created, "model": state.model_name,
                                     "choices": [{"index": 0,
                                                  "delta": {"content": delta},
                                                  "finish_reason": None}]}
                            self.wfile.write(
                                f"data: {json.dumps(chunk)}\n\n".encode())
                            self.wfile.flush()
                        if timer is not None:
                            timer.tick()
                    except OSError:
                        aborted[0] = True
                        state.metrics.bump("client_disconnects")

                try:
                    _, _, _, finish = state.complete(
                        params, emit, deadline=deadline,
                        is_aborted=lambda: aborted[0])
                except ContextOverflow as e:
                    # headers already sent: emit an OpenAI-shaped error
                    # object and terminate WITHOUT a normal finish chunk, so
                    # clients don't mistake the failure for an empty success.
                    # Only the context-window refusal maps to a client error;
                    # anything else is a server bug and propagates as a 500
                    # (ADVICE r01: a bare ValueError catch masked bugs).
                    err = {"error": {"message": str(e),
                                     "type": "invalid_request_error"}}
                    self._safe_write(f"data: {json.dumps(err)}\n\n".encode()
                                     + b"data: [DONE]\n\n", aborted)
                    return
                if finish == "aborted" or aborted[0]:
                    return  # nobody is listening; engine state is rewound
                if finish == "timeout":
                    state.metrics.bump("deadline_timeouts")
                final = {"id": cid, "object": "chat.completion.chunk",
                         "created": created, "model": state.model_name,
                         "choices": [{"index": 0, "delta": {},
                                      "finish_reason": finish}]}
                self._safe_write(f"data: {json.dumps(final)}\n\n".encode()
                                 + b"data: [DONE]\n\n", aborted)
            else:
                on_delta = (lambda d: timer.tick()) if timer is not None \
                    else (lambda d: None)
                try:
                    reply, n_prompt, n_completion, finish = state.complete(
                        params, on_delta, deadline=deadline)
                except ContextOverflow as e:
                    self._json(400, {"error": str(e)})
                    return
                if finish == "timeout":
                    state.metrics.bump("deadline_timeouts")
                self._json(200, {
                    "id": cid, "object": "chat.completion", "created": created,
                    "model": state.model_name,
                    "choices": [{"index": 0, "finish_reason": finish,
                                 "message": {"role": "assistant", "content": reply}}],
                    "usage": {"prompt_tokens": n_prompt,
                              "completion_tokens": n_completion,
                              "total_tokens": n_prompt + n_completion}})

    return Handler


class ApiServer(ThreadingHTTPServer):
    """Threaded HTTP server wired for graceful drain: non-daemon handler
    threads + ``block_on_close`` make ``shutdown()`` wait for in-flight
    requests (each bounded by the drain deadline), and ``allow_reuse_address``
    lets a restart rebind the port while old sockets linger in TIME_WAIT."""
    daemon_threads = False
    block_on_close = True
    allow_reuse_address = True

    def __init__(self, addr, handler, state: ApiState):
        self.state = state
        super().__init__(addr, handler)


def serve(state: ApiState, host: str = "0.0.0.0", port: int = 9990, *,
          block: bool = True, install_signals: bool | None = None
          ) -> ApiServer:
    """Bind and serve.  Returns the server object; with ``block=False`` it
    serves on a background thread (tests drive requests and then call
    ``server.shutdown()`` themselves).

    Graceful drain (satellite + tentpole contract): SIGTERM/SIGINT flips
    the state into draining — new requests get 503, every in-flight
    deadline is clamped to now + ``--drain-grace`` — then ``shutdown()``
    runs from a helper thread (calling it from the signal frame inside
    ``serve_forever`` would deadlock on its own event).  A second signal
    hard-exits."""
    server = ApiServer((host, port), make_handler(state), state)
    if install_signals is None:
        install_signals = block and \
            threading.current_thread() is threading.main_thread()
    if install_signals:
        def _drain(signum, frame):
            if state.draining:  # second signal: operator means NOW
                os._exit(1)
            state.begin_drain()
            _log.info("draining", extra={
                "signal": signal.Signals(signum).name,
                "grace_s": round(state.drain_grace, 1)})

            def _shutdown():
                # hand-off records are PULLED: the router learns of the
                # drain from the finish_reason="handoff" stream chunks
                # and then GETs /admin/export/<rid> on a NEW connection.
                # shutdown() stops accepting new connections, so it must
                # wait (bounded by the drain deadline) until every
                # exported record has been picked up
                deadline = state.drain_deadline or time.monotonic()
                while state.handoff_records and \
                        time.monotonic() < deadline:
                    time.sleep(0.05)
                if state.handoff_records:
                    _log.warning("handoff_records_unclaimed", extra={
                        "count": len(state.handoff_records)})
                server.shutdown()

            threading.Thread(target=_shutdown, daemon=True).start()
        signal.signal(signal.SIGTERM, _drain)
        signal.signal(signal.SIGINT, _drain)
    _log.info("listening", extra={"host": host, "port": port})
    if block:
        try:
            server.serve_forever()
        finally:
            server.server_close()
        # after shutdown() + server_close(): in-flight requests finished,
        # the engine is quiescent — snapshot here so the next boot is a
        # warm start (--snapshot-dir; ApiState.restore_snapshot)
        if state.draining:
            state.save_snapshot()
        _log.info("drained")
    else:
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
    return server


def main(argv=None):
    import sys

    from ..cli import build_parser, load_draft_engine, load_stack
    from ..hostenv import configure_compile_cache
    configure_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    # reuse the dllama flag surface; the server has no positional mode
    args = build_parser().parse_args(["inference", *argv])
    configure_logging(args.log_format, args.log_level)
    obs_trace.configure(args.trace_buffer)
    obs_flight.configure(args.flight_buffer)
    obs_events.configure(getattr(args, "event_buffer", None),
                         getattr(args, "event_log", None))
    slo = None
    slo_spec = args.slo or os.environ.get("DLLAMA_SLO", "")
    if slo_spec:
        from ..obs.slo import SloEngine
        try:
            slo = SloEngine.from_spec(slo_spec)
        except ValueError as e:
            raise SystemExit(f"--slo: {e}")
        _log.info("slo_enabled", extra={
            "spec": slo.spec_display,
            "windows": [w for w, _ in slo.windows]})
    if args.spec != "off" and args.batch_slots <= 0:
        # speculation lives in the slot scheduler; failing fast beats a
        # silently ignored flag (and beats loading a draft model for
        # nothing)
        raise SystemExit("--spec needs --batch-slots (speculative "
                         "decoding runs under the slot scheduler)")
    if args.batch_slots > 0 and args.sp > 1:
        # the batch engine's ragged prefill needs the whole sequence axis
        # per shard (engine.prefill_ragged); accepting the flag would make
        # every /v1/completions request die mid-handler instead of this
        # one clear startup error — raised BEFORE the (minutes-long) model
        # load
        raise SystemExit("--batch-slots is not supported with --sp "
                         "(sequence-sharded KV cache); drop one of them")
    engine, tok = load_stack(args)
    batch_engine = None
    scheduler = None
    if args.batch_slots > 0:
        # share the chat engine's placed weights; only a new KV cache is
        # allocated (see ApiState docstring)
        kv_quant = getattr(args, "kv_quant", "off") == "int8"
        if args.kv_pages > 0 and engine.cache.quantized:
            raise SystemExit("--kv-pages needs a dense chat-engine KV "
                             "cache; drop --kv-cache-dtype q8 (use "
                             "--kv-quant int8 to quantize the paged pool)")
        if kv_quant and args.kv_pages <= 0:
            raise SystemExit("--kv-quant int8 needs a paged pool "
                             "(--kv-pages); contiguous slot rows have no "
                             "per-page scales")
        batch_engine = Engine(engine.cfg, engine.params, mesh=engine.mesh,
                              batch=args.batch_slots, seq_len=args.max_seq_len,
                              kv_dtype="q8" if kv_quant
                              else engine.cache.k.dtype,
                              step_timeout=args.step_timeout,
                              kv_pages=args.kv_pages,
                              kv_page_size=args.kv_page_size)
        _log.info("batch_serving_enabled",
                  extra={"slots": args.batch_slots,
                         "kv_pages": args.kv_pages,
                         "kv_quant": "int8" if kv_quant else "off"})
        try:
            # tentpole: continuous batching — single-stream requests join
            # the batch engine at decode-step granularity instead of
            # serializing on the engine mutex (which stays the fallback
            # path for seeded sampling, logprobs, echo, and n>1)
            spec = None
            if args.spec != "off":
                from ..runtime.spec import make_proposer
                draft_eng = (load_draft_engine(args, batch_engine)
                             if args.spec == "draft" else None)
                spec = make_proposer(args.spec, batch_engine,
                                     draft_engine=draft_eng)
            scheduler = SlotScheduler(
                batch_engine, prefill_chunk=args.sched_prefill_chunk,
                max_wait_ms=args.sched_max_wait_ms,
                max_queue=args.sched_max_queue,
                prefix_reuse=not args.no_prefix_reuse,
                overlap=not args.no_sched_overlap,
                preempt=not args.no_preempt,
                preempt_age_ms=args.preempt_age_ms,
                preempt_cap=args.preempt_cap,
                spill_dir=args.preempt_spill_dir,
                spec=spec, spec_k=args.spec_k,
                kv_reserve=getattr(args, "kv_reserve", "full"),
                spill_headroom=getattr(args, "spill_headroom", 16),
                host_pool_mb=getattr(args, "kv_host_pool_mb", 64.0))
            _log.info("slot_scheduler_enabled", extra={
                "slots": args.batch_slots,
                "prefill_chunk": args.sched_prefill_chunk,
                "max_wait_ms": args.sched_max_wait_ms,
                "paged": scheduler.paged,
                "prefix_reuse": scheduler.prefix_cache is not None,
                "overlap": scheduler.overlap,
                "preempt": scheduler.preempt and scheduler.paged,
                "kv_reserve": scheduler.kv_reserve,
                "kv_quant": "int8" if kv_quant else "off",
                "spec": args.spec, "spec_k": args.spec_k})
        except ValueError as e:
            # quantized KV / sp mesh: lockstep batch serving still works,
            # only decode-step admission is off
            _log.warning("slot_scheduler_disabled",
                         extra={"reason": str(e)})
    state = ApiState(engine, tok, default_temperature=args.temperature,
                     default_topp=args.topp, chunk=args.chunk,
                     batch_engine=batch_engine,
                     max_pending=args.max_pending,
                     request_timeout=args.request_timeout,
                     io_timeout=args.io_timeout,
                     drain_grace=args.drain_grace,
                     snapshot_dir=args.snapshot_dir,
                     scheduler=scheduler,
                     slo=slo, handoff=getattr(args, "handoff", False),
                     handoff_ttl=getattr(args, "handoff_ttl", 0.0))
    if args.snapshot_dir:
        state.restore_snapshot()
    try:
        serve(state, host=args.host, port=args.port)
    finally:
        if scheduler is not None:
            scheduler.close()
        if slo is not None:
            # end-of-run verdict next to the dispatch summary, same as the
            # CLI modes (cli._print_slo_summary)
            print(slo.summary_line())


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
