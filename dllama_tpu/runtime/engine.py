"""Inference engine: compiled prefill/decode + generation loop + stats.

Replaces the reference's execution layer (`Inference::infer` tasks.cpp:199-
210 + the per-token task-list walk): here a *whole decode step* — embed,
all layers, logits — is one XLA program with ``pos`` as a traced scalar, so
autoregression never recompiles, and the KV cache is a donated device
buffer updated in place.

Prefill is a separate bucketed program (prompt padded up to the next
bucket) that processes the whole prompt in one batched pass — the reference
feeds prompt tokens one at a time (dllama.cpp:53-58), which is parity-fine
but wastes the MXU; true prefill is the TPU-idiomatic replacement.

Stats keep the reference's per-token G/I/T contract (dllama.cpp:45-93,
`Inference::getStats` tasks.cpp:212-215): G = whole-step wall ms, I =
on-device compute ms, T = device→host transfer ms.  On the reference, T is
socket time between nodes; on a TPU mesh the inter-chip hops are XLA
collectives *inside* I (that's the point — T ≈ 0), so T here counts the
only remaining boundary: fetching logits for the host-side sampler.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
import weakref
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import hostenv
from ..io import mfile
from ..models import cache_kinds, grouping
from ..models.config import ModelConfig
from ..models.params import Params
from ..models.transformer import (LATENT_PAGE_AXES, PAGE_AXES, forward_last,
                                  init_kv_cache)
from ..obs import dispatch as obs_dispatch, memory as obs_memory, \
    metrics as obs_metrics, trace as obs_trace
from ..obs.log import get_logger
from ..ops import conv, q40, q8, retention
from ..parallel import sharding
from ..parallel.mesh import active_mesh, make_mesh
from ..sampling import Sampler

_log = get_logger("runtime.engine")


def _device_stats() -> dict[str, dict]:
    """``{device_id: memory_stats()}`` over the local devices; a backend with
    no allocator stats (CPU, some emulators) is left out."""
    out: dict[str, dict] = {}
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            out[str(d.id)] = ms
    return out


def _hbm_reader(stat: str):
    """Bind a per-device memory_stats field to a labeled gauge: returns
    ``{device_id: bytes}`` at read time, or ``{}`` where the backend has
    no allocator stats — absence reads as no samples, never as zeros."""
    def read() -> dict:
        return {d: float(ms[stat]) for d, ms in _device_stats().items()
                if stat in ms}
    return read


# The obs package stays jax-free; the engine (which already owns the
# devices) donates the reader at import.  LabeledGauge calls it lazily at
# each /metrics read, so the gauges track live allocator state; the memory
# account (obs/memory.py) calls it at its edges only.
obs_metrics.HBM_BYTES_IN_USE.fn = _hbm_reader("bytes_in_use")
obs_metrics.HBM_BYTES_PEAK.fn = _hbm_reader("peak_bytes_in_use")
obs_memory.ACCOUNT.stats = _device_stats


def _resident_param_bytes(params: Params) -> dict[str, int]:
    """``{device_id: bytes}`` of the placed parameters' addressable shards
    (or of any pytree of placed arrays: a cache's planes)."""
    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def _zeros_on_mesh(make, sharding: NamedSharding):
    """``make()`` (a pytree of zeros: the KV cache or pool) built sharded, each
    device filling only its own part: made eagerly it would sit whole on
    device 0 first (8 GB for Yi-34B's 32k cache) before ``device_put`` spread
    it."""
    return jax.jit(make, out_shardings=sharding)()


def _next_bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _unfuse(params: Params, cfg: ModelConfig) -> Params:
    """Split fused ``wqkv``/``w13`` tensors into per-projection weights for
    tensor-parallel placement (the fused layout is a single-chip launch
    optimization; its concat axis does not align with TP shard boundaries)."""
    def split(w, sizes):
        if isinstance(w, (q40.QTensor, q8.Q8Tensor)):
            return q40.split_d(w, sizes)
        off, out = 0, []
        for s in sizes:
            out.append(w[..., :, off:off + s])
            off += s
        return out

    p = dict(params)
    if "wqkv" in p:
        dh = cfg.head_size
        p["wq"], p["wk"], p["wv"] = split(
            p.pop("wqkv"), [cfg.n_heads * dh, cfg.n_kv_heads * dh, cfg.n_kv_heads * dh])
    if "w13" in p:
        p["w1"], p["w3"] = split(p.pop("w13"), [cfg.hidden_dim, cfg.hidden_dim])
    return p


def _compile_totals() -> tuple[int, int, float, float]:
    return (obs_metrics.COMPILE_CACHE_REQUESTS.value,
            obs_metrics.COMPILE_CACHE_HITS.value,
            obs_metrics.BACKEND_COMPILE_SECONDS.value,
            obs_metrics.COMPILE_CACHE_RETRIEVAL_SECONDS.value)


@contextlib.contextmanager
def _compile_span(key: tuple):
    before = _compile_totals()
    with obs_trace.span("engine.compile", key=repr(key)) as sp:
        acct = obs_memory.ACCOUNT
        acct.launching(repr(key), sp)
        try:
            with acct.exhaustion(repr(key)):
                yield
        finally:
            req, hits, secs, load = (a - b for a, b in zip(_compile_totals(),
                                                           before))
            sp.update(cache_requests=req, cache_hits=hits,
                      backend_s=round(secs, 6),
                      compiled_s=round(max(secs - load, 0.0), 6))


def _compiling(fresh: bool, *key):
    """The span ``engine.compile`` around a fresh program's first call, which
    blocks through trace and compile (no span once the program has run):
    ``key`` as the ``compile`` log line has it, and what JAX counted
    meanwhile (``obs_metrics.watch_compiles``): programs looked up in the
    persistent cache, programs found there, the seconds inside the backend's
    compile-or-load (``backend_s``) and those of them not spent loading a
    hit (``compiled_s``).  Where the backend reports allocator statistics the
    span also carries ``hbm_in_use`` and ``hbm_peak_before`` (read at its
    entry) and, in the ring's record, ``hbm_peak_after`` (read where the call
    is first waited for, :meth:`Engine._sync`): the memory account's two
    reads a fresh program (``obs/memory.py``); an allocation that fails in
    the call logs ``hbm_exhausted`` on its way out."""
    return _compile_span(key) if fresh else contextlib.nullcontext()


def _compile_clock() -> float:
    """Seconds JAX has spent tracing and compiling in this process: it moves
    over a call that compiled, whether the engine knew the program or not
    (``jax.jit`` traces a known program again for operands placed anew: a
    key's second call, whose cache is the one its first call returned)."""
    return obs_metrics.JAXPR_TRACE_SECONDS.value \
        + obs_metrics.BACKEND_COMPILE_SECONDS.value


def _launched(span_args, kind: str, clock0: float) -> None:
    """Close of an ``engine.launch`` block: a launch that traced or compiled
    is a compile, not the host's part of a step, and its duration goes to
    ``sched_host_ms{phase="compile"}`` (which no reader counts)."""
    if _compile_clock() != clock0:
        span_args.update(compiled=True)
        span_args.total = obs_metrics.host_ms("compile", kind)


class ContextOverflow(ValueError):
    """The requested tokens do not fit the engine's context window.

    A dedicated type so the API server can map it to an HTTP 400 without
    masking unrelated ValueErrors as client errors (ADVICE r01)."""


class NumericFault(RuntimeError):
    """NaN/Inf detected in the logits under ``numeric_checks``.

    The reference has no numeric guard at all: a corrupt weight or a
    numerically-diverged KV cache surfaces as garbage *text* (or a
    sampler crash) minutes later, with no pointer back to the step that
    went bad.  With ``--numeric-checks`` the engine checks every
    host-fetched logits array and raises this instead, naming the step,
    the sequence position, and a hint — detection happens at the logits
    (the one tensor the host already sees each step, so the check costs
    no extra device→host traffic), which cannot name the layer that
    produced the NaN; the hint says what to bisect next.  The server
    maps it to a 500 and resets the engine (a NaN anywhere implies the
    KV cache may be poisoned)."""

    def __init__(self, step: str, pos: int, hint: str = ""):
        self.step = step
        self.pos = pos
        self.hint = hint
        msg = f"non-finite logits at {step}, pos={pos}"
        super().__init__(msg + (f" ({hint})" if hint else ""))


class StepTimeout(RuntimeError):
    """A device step exceeded the engine's watchdog deadline.

    The reference's failure shape here is a silent hang — a blocking
    socket ``read()`` with no timeout wedges the whole cluster
    (socket.cpp).  Our equivalent blocking edge is
    ``jax.block_until_ready`` on a step's outputs: a wedged device
    would park the serving thread forever while it holds the engine
    mutex.  The watchdog (``step_timeout``, or ``DLLAMA_STEP_TIMEOUT``)
    turns that into a diagnosable exception naming the step and position
    so the server can answer 500 and keep serving."""


@dataclass
class StepStats:
    """Per-token timing + host↔device traffic, reference benchmark-mode
    contract (dllama.cpp:74-82: G/I/T ms and sent/recv kB columns —
    there the bytes are TCP traffic between nodes, socket.cpp:280-285;
    on a TPU mesh inter-chip traffic rides ICI inside the XLA program, so
    S/R count the only remaining boundary: host↔device transfers)."""
    generation_ms: float = 0.0  # G: total wall time for the token
    inference_ms: float = 0.0   # I: device execution
    transfer_ms: float = 0.0    # T: host<->device boundary
    sent_bytes: float = 0.0     # S: host → device (fractional per token when
    recv_bytes: float = 0.0     # R: device → host   averaged over a chunk)


@dataclass
class RunStats:
    # Running sums keep every avg_* property O(1); the per-token list is
    # retained for callers that want the full series (benchmarks, tests).
    tokens: list[StepStats] = field(default_factory=list)
    _g_sum: float = field(default=0.0, repr=False)
    _i_sum: float = field(default=0.0, repr=False)
    _t_sum: float = field(default=0.0, repr=False)
    _s_sum: float = field(default=0.0, repr=False)
    _r_sum: float = field(default=0.0, repr=False)

    def add(self, s: StepStats):
        self.tokens.append(s)
        self._g_sum += s.generation_ms
        self._i_sum += s.inference_ms
        self._t_sum += s.transfer_ms
        self._s_sum += s.sent_bytes
        self._r_sum += s.recv_bytes

    def _avg(self, total: float) -> float:
        return total / len(self.tokens) if self.tokens else 0.0

    @property
    def avg_generation_ms(self):
        return self._avg(self._g_sum)

    @property
    def avg_inference_ms(self):
        return self._avg(self._i_sum)

    @property
    def avg_transfer_ms(self):
        return self._avg(self._t_sum)

    @property
    def avg_sent_bytes(self):
        return self._avg(self._s_sum)

    @property
    def avg_recv_bytes(self):
        return self._avg(self._r_sum)

    @property
    def tokens_per_second(self):
        g = self.avg_generation_ms
        return 1000.0 / g if g > 0 else 0.0


def page_axes(cache) -> str:
    """The axis order inside one pool page, by name, learned from the cache:
    part of the snapshot and DLREQ01 fingerprints."""
    return LATENT_PAGE_AXES if cache.latent else PAGE_AXES


def _refuse_mesh_and_int8(mesh, kv_quant: bool, what: str, why: str) -> None:
    """What a model whose cache is not one per-head plane a layer cannot do
    yet (``what`` and ``why``: the kind's row in ``models/cache_kinds.py``),
    refused here by name and not discovered in a trace."""
    for ax in ("tp", "sp", "ep"):
        if mesh.shape.get(ax, 1) > 1:
            raise ValueError(
                f"{what} runs on one device: a {ax}={mesh.shape[ax]} mesh is "
                f"not supported for this architecture ({why}; not wired)")
    if kv_quant:
        raise ValueError(f"--kv-quant int8 is not supported with {what}: "
                         "its cache has no int8 form")


def _model_fields(c: ModelConfig) -> dict:
    """The model's part of a snapshot's and a hand-off record's fingerprint.  A
    looped model's planes are (pass, layer), so its pass count is part of what a
    record's leading axis means; where the stack runs once the key is absent
    and the digest is what it was."""
    return {"arch": c.arch, "dim": c.dim, "hidden_dim": c.hidden_dim,
            "n_layers": c.n_layers, "n_heads": c.n_heads,
            "n_kv_heads": c.n_kv_heads, "n_experts": c.n_experts,
            "n_active_experts": c.n_active_experts,
            "vocab_size": c.vocab_size, "hidden_act": c.hidden_act,
            "rope_theta": c.rope_theta,
            **({"n_loops": c.n_loops} if c.n_loops > 1 else {})}


class StateRewindTooDeep(ValueError):
    """A call starts so far below the highest position a recurrent state was
    written at that the rows before it are no longer there to resume from: they
    have left a convolution state's ring (``ops/conv.py``), or a retention
    layer (or a state-space mixer, ``ops/ssm.py``) has folded them into its
    state (``ops/retention.py``).  The caller
    resets the engine and prefills the conversation again from position 0."""


def _note_cache_bytes(cfg: ModelConfig, cache, tokens: int, batch: int,
                      paged: bool) -> int:
    """Set the cache's gauges from its own arrays and return what one cached
    token occupies over all layers, each plane under its owner's label and
    counted as its owner grows (``models/cache_kinds.py``: ``gauge``,
    ``grows_by``).  ``kv_cache_bytes{kind="window"}`` is what the bound on the
    rings saves against ``kind="full"``'s planes per layer; a state-space
    mixer's planes (``kind="ssm"``) stand BESIDE ``kind="full"``'s."""
    per_token, by_kind = 0, dict.fromkeys((k.gauge for k in cache_kinds.KINDS), 0)
    for name, a in cache.planes().items():
        kind = cache_kinds.owner(cfg, name)
        by_kind[kind.gauge] += int(a.nbytes)
        if not a.size or not kind.grows_by or (paged and kind.grows_by == "ring"):
            continue
        positions = tokens if kind.grows_by == "tokens" else batch * a.shape[3]
        per_token += int(a.nbytes) // positions
    for kind, nbytes in by_kind.items():
        obs_metrics.KV_CACHE_BYTES.set(kind, nbytes)
    obs_metrics.KV_BYTES_PER_TOKEN.set(per_token)
    return per_token


def _closes_request(stream):
    """A one-stream generator method whose end (exhausted, returned early, or
    abandoned by its consumer) is the close of a request:
    :meth:`Engine.settled` runs after the stream's own clean-up.  A stream
    that raises is no idle instant (its cache may be gone with a donation):
    the error goes on as it is and a later close reads."""
    @functools.wraps(stream)
    def closing(self, *args, **kwargs):
        try:
            yield from stream(self, *args, **kwargs)
        except GeneratorExit:
            self.settled()
            raise
        self.settled()
    return closing


class Engine:
    """Owns placed params, the KV cache, and the compiled step functions."""

    def __init__(self, cfg: ModelConfig, params: Params, mesh=None,
                 batch: int = 1, seq_len: int | None = None, kv_dtype=None,
                 step_timeout: float | None = None,
                 numeric_checks: bool | None = None,
                 kv_pages: int = 0, kv_page_size: int = 16):
        self.batch = batch
        # decode watchdog (see StepTimeout); 0/None disables.  Env default
        # so a live server can arm it without a code path change.
        if step_timeout is None:
            step_timeout = float(os.environ.get("DLLAMA_STEP_TIMEOUT", "0"))
        self.step_timeout = step_timeout if step_timeout > 0 else None
        # opt-in NaN/Inf guard over every host-fetched logits array (see
        # NumericFault); env default mirrors the watchdog.  Off by
        # default: np.isfinite over (B, V) costs ~µs but the *policy*
        # (fail the request) should be a choice.
        if numeric_checks is None:
            numeric_checks = os.environ.get(
                "DLLAMA_NUMERIC_CHECKS", "") not in ("", "0", "false")
        self.numeric_checks = bool(numeric_checks)
        self.seq_len = min(seq_len or cfg.seq_len, cfg.seq_len)
        self.mesh = mesh if mesh is not None else make_mesh(tp=1, devices=jax.devices()[:1])
        tp = self.mesh.shape.get("tp", 1)
        if tp > 1:
            sharding.check_tp_constraint(cfg, tp)
            # the fused wqkv/w13 concat axis mixes q/k/v shard ranges under
            # tp — split back into per-projection tensors whose output axes
            # shard cleanly (RowMatmulSlice boundaries, commands.cpp:8-40)
            params = _unfuse(params, cfg)
        # Packed-Q40 matmul dispatch on a multi-device mesh runs the fused
        # Pallas kernel per shard under shard_map (ops/q40.py
        # _sharded_matmul) — no downgrade; weights whose shapes don't
        # divide the mesh evenly fall back per-tensor inside q40.matmul.
        self.sp = self.mesh.shape.get("sp", 1)
        if self.sp > 1 and self.seq_len % self.sp:
            raise ValueError(f"seq_len {self.seq_len} not divisible by sp={self.sp}")
        ep = self.mesh.shape.get("ep", 1)
        if ep > 1:
            if not cfg.is_moe:
                raise ValueError("ep>1 needs an MoE model (no expert axis to shard)")
            if cfg.n_experts % ep:
                raise ValueError(
                    f"n_experts {cfg.n_experts} not divisible by ep={ep}")
        self.cfg = cfg
        # kv_dtype "q8" (or int8) selects the quantized cache: int8 values +
        # per-position f32 scales (models.transformer.init_kv_cache has why)
        kv_quant = kv_dtype == "q8" or (
            kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8)
        for kind in cfg.cache_kinds:
            what = kind.what.format(arch=mfile.ARCH_NAMES[cfg.arch])
            if kind.one_device:
                _refuse_mesh_and_int8(self.mesh, kv_quant, what, kind.one_device)
            if kv_pages and kind.no_pages:
                raise ValueError(f"{what} {kind.no_pages}")
        if self.mesh.shape.get("tp", 1) > 1 \
                and jax.default_backend() != "tpu" \
                and os.environ.get("DLLAMA_TP_REDUCE", "") != "psum":
            # tp serving off-TPU cannot take the fused collective-matmul
            # decode path (ops/q40.py _tp_ring_allreduce is built on
            # inter-chip RDMA): decode collectives degrade to plain
            # psum/GSPMD all-reduce.  Through the degrade ledger (warn-once
            # record, labeled counter, degraded flag): the run still
            # serves, but a bench number from this configuration must not
            # read as the fused number
            obs_dispatch.record_degrade(
                "q40", "tp_psum", warn_key=jax.default_backend(),
                backend=jax.default_backend(),
                tp=self.mesh.shape.get("tp", 1),
                hint="fused collective-matmul decode is TPU-only; tp "
                     "collectives run as plain psum all-reduce")
        obs_metrics.watch_compiles()  # before this engine's first program
        hostenv.kernels_without_frames()  # for a caller that is no entry point
        # the memory account (obs/memory.py): what the process held on the
        # chip before its first upload, then one reading a load phase; an
        # allocation that fails in the load says who held what
        acct = obs_memory.ACCOUNT
        acct.found()
        with acct.exhaustion():
            self.params = sharding.place_params(params, cfg, self.mesh)
        for dev, nbytes in _resident_param_bytes(self.params).items():
            obs_metrics.PARAM_BYTES_RESIDENT.set(dev, nbytes)
        acct.phase("load_place")
        if kv_quant and self.sp > 1:
            raise ValueError("quantized KV cache is not supported on sp "
                             "meshes (shard-local sp cache writes are "
                             "dense); use sp=1 or a dense cache dtype")
        # sp>1 shards the cache's sequence axis: max context scales with
        # sp × per-chip HBM (capability the reference lacks, SURVEY §5);
        # the same sharding is pinned as jit out_shardings below so cache
        # placement and step outputs can never silently diverge
        latent = cache_kinds.LATENT in cfg.cache_kinds
        self._cache_sh = sharding.kv_cache_sharding(
            self.mesh, "sp" if self.sp > 1 else None, latent=latent)
        # kv_pages > 0 replaces the per-slot contiguous cache with a paged
        # pool + per-slot page tables (ops/attention.py paged section):
        # memory is bounded by live tokens, not batch × seq_len, and the
        # scheduler's radix tree can share prompt-prefix pages across
        # requests.  Slot-serving only: the one-shot conversation/batch
        # paths keep contiguous addressing.
        self.paged = kv_pages > 0
        self.kv_pages = int(kv_pages)
        self.kv_page_size = int(kv_page_size)
        if self.paged:
            if self.sp > 1:
                raise ValueError("paged KV is not supported on sp meshes "
                                 "(sequence-sharded pools are not wired)")
            if self.kv_pages < 2:
                raise ValueError("kv_pages must be >= 2 (page 0 is the "
                                 "reserved scratch page)")
            if self.kv_page_size < 1:
                raise ValueError(f"kv_page_size must be >= 1, "
                                 f"got {self.kv_page_size}")
            # per-slot table width: enough logical pages to cover seq_len
            self.max_pages_per_slot = -(-self.seq_len // self.kv_page_size)
            from ..models.transformer import init_kv_pool
            # pool layout (L, P, ps, Hkv, Dh): pages ride the batch ("dp")
            # axis, a page is token-major, so the kv-head axis that tp
            # shards is axis 3 — a spec of its own
            self._cache_sh = sharding.kv_pool_sharding(self.mesh, latent=latent)
            # --kv-quant int8: pool pages hold int8 values + per-position
            # f32 scale planes (the Q80 weight codec's trick applied to
            # pages); paged attention dequantizes after the int8-sized
            # page read, so cache HBM traffic and residency halve again
            # on top of paging
            with acct.exhaustion():
                self.cache = _zeros_on_mesh(
                    lambda: init_kv_pool(cfg, self.kv_pages, self.kv_page_size,
                                         dtype=None if kv_quant else kv_dtype,
                                         quant=kv_quant, slots=batch,
                                         max_pages=self.max_pages_per_slot),
                    self._cache_sh)
            obs_metrics.KV_PAGE_CODEC.set(
                "int8" if kv_quant else str(self.cache.k.dtype), 1)
        else:
            with acct.exhaustion():
                self.cache = _zeros_on_mesh(
                    lambda: init_kv_cache(cfg, batch, self.seq_len,
                                          dtype=None if kv_quant else kv_dtype,
                                          quant=kv_quant),
                    self._cache_sh)
        # what one cached token occupies over all layers, learned from the
        # cache itself (a latent cache: layers x C x element size; a windowed
        # model's rings at their own positions)
        tokens = (self.kv_pages * self.kv_page_size if self.paged
                  else batch * self.seq_len)
        self.kv_bytes_per_token = _note_cache_bytes(cfg, self.cache, tokens,
                                                    batch, self.paged)
        obs_metrics.MODEL_LOOP_PASSES.set(cfg.n_loops)
        # the account's ``cache`` owner: this engine's planes on each device,
        # beside every other live engine's (a server's chat engine keeps its
        # contiguous cache beside the batch engine's pool), until it goes
        held = _resident_param_bytes(self.cache.planes())
        acct.cache_built(held)
        weakref.finalize(self, acct.cache_dropped, held)
        self.pos = 0
        # the one-stream account of a recurrent state (``_state``: its row of
        # ``models/cache_kinds.py``): a call may start at a position whose rows
        # before it lie in [lo, hi) (_state_enter / _state_wrote): a
        # convolution's ring, or where a state lags the clock (the row has
        # ``folds``) from its watermark on; a state-space mixer has both, its
        # convolution ring's low in ``_state_ring_lo``
        self._state = next((k for k in cfg.cache_kinds if k.rewinds), None)
        self._state_lo = self._state_hi = self._state_ring_lo = 0
        # a slot engine's mirror of its slots' watermarks, for the fold
        # counter alone (_note_slot_folds)
        self._slot_marks = np.zeros(batch, np.int64)

        def step(params, cache, tokens, pos, last_index, offsets=None):
            # a third output where some layer sorted its rows by expert (a
            # prompt's call of an expert model on one device): how full the
            # blocks were; a dense model is traced as it always was
            with (grouping.collecting() if cfg.is_moe
                  else contextlib.nullcontext([])) as notes:
                logits, cache = forward_last(params, cfg, tokens, cache, pos,
                                             last_index, offsets=offsets)
            return logits, cache, grouping.total(notes)

        # Outputs that the host reads (logits, sampled tokens) are pinned
        # replicated while the cache keeps its mesh sharding: on a
        # multi-process mesh (parallel/distributed.py) a sharded output
        # spans non-addressable devices and cannot be fetched — replication
        # makes every fetch process-local (the gather rides ICI inside the
        # program, which is where inter-chip traffic belongs; T≈0 contract).
        self._rep = NamedSharding(self.mesh, P())
        # one compiled program per (batch, T-bucket); decode is bucket T=1
        self._step = jax.jit(step, donate_argnums=(1,),
                             out_shardings=(self._rep, self._cache_sh, self._rep))
        if self.sp > 1:
            cfg_ring = cfg.with_(ring_prefill=True)

            def ring_step(params, cache, tokens, pos, last_index):
                return forward_last(params, cfg_ring, tokens, cache, pos, last_index)

            self._step_ring = jax.jit(ring_step, donate_argnums=(1,),
                                      out_shardings=(self._rep, self._cache_sh))
        self._chunk_fns: dict = {}
        # compile telemetry: step shapes that already built an executable
        # (self._step/_step_ring jit-compile per (batch, T-bucket) shape;
        # self._chunk_fns is its own executable cache) — lets _run tell a
        # recompile from a cache hit without reaching into jax internals
        self._compiled_steps: set = set()
        self._key = jax.random.PRNGKey(0)
        self._chunk_counter = 0
        # device-resident RNG chain for sampled slot dispatches: seeded
        # lazily off the host stream, then advanced by the key each
        # compiled chunk returns — sampled pure decode never syncs the
        # host for randomness (one-dispatch decode, ISSUE 20)
        self._dev_key: jax.Array | None = None
        self._no_feed: jax.Array | None = None  # see _slot_operands
        # which sampling implementation owns this engine's draws; rides
        # snapshots/hand-off records so a sampled slot never resumes on
        # a replica whose stream would diverge
        self.sampling_path = os.environ.get(
            "DLLAMA_SAMPLING_PATH", "device").strip().lower() or "device"
        self._offsets: jax.Array | None = None  # ragged-batch left padding
        acct.rss("ready")

    def settled(self) -> None:
        """Nothing of this engine is in flight: the scheduler is about to
        park, or a one-stream request has closed.  Where a program was
        compiled or a cache built since the last reading, the account reads
        what stays resident (a cut stream may have left a burst in flight:
        the cache is the last launch's output, so it is waited for first); a
        warm engine reads nothing and waits for nothing."""
        acct = obs_memory.ACCOUNT
        if acct.dirty and acct.active:
            jax.block_until_ready(self.cache)
            acct.idle()

    # ------------------------------------------------------------------
    def reset(self):
        """Restart the sequence (new conversation); cache memory is reused."""
        self.pos = 0
        self._offsets = None
        self._state_lo = self._state_hi = self._state_ring_lo = 0

    # -- the pos-rewind invariant ---------------------------------------
    # Callers set ``pos`` back and go on: a decode burst that ran past an
    # end-of-sequence token (generate_stream), a rejected draft
    # (generate_pld_stream), a stop string or a cancelled request
    # (runtime/stream.py, server/api.py).  KEYS AND VALUES survive that
    # because they are addressed by position: rows above ``pos`` are masked
    # and the next call overwrites them.  A RECURRENT STATE survives it only
    # if what lies above the new ``pos`` is addressed by position too and
    # what lies below it is still there: a convolution layer's state is a
    # ring of ``ops/conv.py RING`` positions, and a retention layer keeps its
    # newest ``ops/retention.py REWIND`` positions in a ring and OUT of its
    # state matrix, which absorbs them a block behind the clock (the bounds
    # live there, with the rewinds they were sized for); a state-space mixer
    # (``ops/ssm.py``) keeps both, a convolution's ring and a state behind
    # retention's watermark, and both rules hold for it.  This engine keeps
    # account of the positions a call may start at, ``[lo, hi]`` less what a
    # call reads before its first row.  An arch with another state brings its
    # planes into ``KVCache``, its row into ``models/cache_kinds.py`` and its
    # two rules here (``state_holds``, ``_state_wrote``); nothing else in the
    # engines changes.
    def state_holds(self, pos: int) -> bool:
        """Whether a one-stream call may start at ``pos``: always for keys and
        values; with a recurrent state, only while the rows it resumes from
        are still addressed by position."""
        st = self._state
        if st is None or self.paged or pos == 0:
            return True
        # a call reads the ``taps - 1`` rows before its first from a
        # convolution's ring, and a state that folds its own ring from the
        # watermark on, whatever it holds
        taps = st.taps(self.cfg)
        before = max(pos - taps + 1, 0) if taps else pos
        if not st.folds:
            return self._state_lo <= before and pos <= self._state_hi
        return (self._state_lo <= pos <= self._state_hi
                and self._state_ring_lo <= before)

    def resume_at(self, pos: int) -> bool:
        """Set the position clock to ``pos``, a conversation's cached end
        (``server/api.py NaiveCache``), if everything the cache keeps still
        holds it: always for keys and values; a recurrent state only while
        the rows before ``pos`` are in its ring.  Otherwise count the refusal,
        reset, and return False: the caller prefills the conversation again
        from position 0."""
        if self.state_holds(pos):
            self.pos = pos
            return True
        self._state.rewinds.inc(self._state.too_deep)
        self.reset()
        return False

    def _state_enter(self, pos: int) -> None:
        """Before a one-stream call at ``pos``: count a rewind, refuse one that
        left the ring (module-level :class:`StateRewindTooDeep`)."""
        if self._state is None or pos == self._state_hi:
            return
        if pos > self._state_hi:
            raise StateRewindTooDeep(
                f"a call at position {pos} skips positions the recurrent "
                f"state has not seen (written up to {self._state_hi}): "
                "prefill them first")
        held = self.state_holds(pos)
        self._state.rewinds.inc("in_ring" if held else self._state.too_deep)
        if not held:
            raise StateRewindTooDeep(
                f"position {pos} is behind what the recurrent state still "
                f"addresses by position (it holds {self._state_lo}.."
                f"{self._state_hi}: a convolution's ring, or what a retention "
                "layer or a state-space mixer has not yet folded into its state"
                + (f", and its convolution's ring from {self._state_ring_lo}"
                   if self._state.folds and self._state.taps_field else "")
                + "): reset() and prefill "
                "the conversation again from position 0")

    def _state_wrote(self, pos: int, n_real: int, rows: int) -> None:
        """After a one-stream call of ``rows`` rows at ``pos`` of which the
        first ``n_real`` hold a token: what is still addressed by position.
        A convolution's ring holds what it held, less what the rows written
        (``ops/conv.py written``) displaced; a lagging state's watermark is
        where ``ops/retention.py watermark`` puts it."""
        st = self._state
        if st is None:
            return
        taps = st.taps(self.cfg)
        if st.folds:
            was = self._state_lo if pos else 0  # position 0 starts a sequence
            lo = retention.watermark(was, pos + n_real)
            st.folds.inc(int((lo - was) // retention.FOLD) * st.depth(self.cfg))
            self._state_lo, self._state_hi = lo, pos + n_real
            if taps:
                self._state_ring_lo = self._ring_low(
                    self._state_ring_lo, pos, n_real, rows, taps)
        else:
            self._state_lo, self._state_hi = self._ring_low(
                self._state_lo, pos, n_real, rows, taps), pos + n_real

    @staticmethod
    def _ring_low(lo: int, pos: int, n_real: int, rows: int, taps: int) -> int:
        """The lowest position a convolution's ring still holds after a call of
        ``rows`` rows at ``pos`` (``ops/conv.py written``: which of them it
        took)."""
        first, count = conv.written(int(n_real), rows, conv.RING, taps)
        # rows that start inside the call are not joined to what came before
        return max(pos + first if first else max(min(lo, pos),
                                                 pos + count - conv.RING), 0)

    def _note_slot_folds(self, pos_rows_np, clock_np) -> None:
        """A slot dispatch's folds, for ``retention_folds`` / ``ssm_folds``: the
        host's mirror of each slot's watermark, moved by the device's own rule
        to the clock the dispatch leaves."""
        if not (self._state and self._state.folds):
            return
        was = np.where(pos_rows_np == 0, 0, self._slot_marks)
        self._slot_marks = retention.watermark(was, clock_np)
        blocks = int(np.sum((self._slot_marks - was) // retention.FOLD))
        self._state.folds.inc(blocks * self._state.depth(self.cfg))  # a block a layer of the kind

    def _max_burst(self, chunk: int) -> int:
        """``chunk`` within what a recurrent state's rewinds allow."""
        return self._state.max_burst(self.cfg, chunk) if self._state else chunk

    # -- state snapshot/restore (runtime/snapshot.py format) -----------
    def config_fingerprint(self) -> str:
        """Short digest of everything that must match for a snapshot's
        state to be meaningful in this engine: model hyperparameters,
        batch, context length, and the cache's dtype/shape layout.  Mesh
        shape is deliberately excluded — KV *values* are placement-
        independent, so a snapshot taken on one mesh restores onto
        another (device_put reshards)."""
        from . import snapshot as snapfmt
        fields = {
            **_model_fields(self.cfg),
            "batch": self.batch, "seq_len": self.seq_len,
            "cache": [[n, str(a.dtype), list(a.shape)]
                      for n, a in self._cache_arrays().items()],
            # pool geometry: a paged snapshot only means something in an
            # engine with the same page count/size (page ids are physical)
            # and the same axis order inside a page
            "paged": [self.kv_pages, self.kv_page_size, page_axes(self.cache)]
            if self.paged else None,
        }
        return snapfmt.fingerprint(fields)

    def _cache_arrays(self) -> dict:
        return {f"cache.{n}": a for n, a in self.cache.planes().items()}

    def snapshot(self, path: str | os.PathLike,
                 extra: dict | None = None,
                 extra_arrays: dict | None = None) -> str:
        """Serialize the engine's conversation state (KV cache, position,
        sampler RNG stream, ragged offsets) to a versioned, checksummed
        file (runtime/snapshot.py).  Atomic; returns the path.  ``extra``
        is caller JSON carried in the snapshot meta and handed back by
        :meth:`restore` (the API server stores its conversation cache
        there so a warm restart resumes chats, not just KV bytes);
        ``extra_arrays`` are caller numpy arrays stored alongside the
        cache (the paged scheduler persists its page tables this way) and
        handed back via :attr:`restored_arrays`."""
        from . import snapshot as snapfmt
        arrays = {n: np.asarray(a) for n, a in self._cache_arrays().items()}
        arrays["rng_key"] = np.asarray(self._key)
        if self._dev_key is not None:
            arrays["rng_dev_key"] = np.asarray(self._dev_key)
        meta_extra = dict(extra or {})
        meta_extra.setdefault("sampling_path", self.sampling_path)
        if self._state is not None:
            meta_extra["conv_state"] = [self._state_lo, self._state_hi,
                                        self._state_ring_lo]
        if self._offsets is not None:
            arrays["offsets"] = np.asarray(self._offsets)
            meta_extra["has_offsets"] = True
        for n, a in (extra_arrays or {}).items():
            if n in arrays:
                raise ValueError(f"extra array name {n!r} collides")
            arrays[n] = np.asarray(a)
        return snapfmt.save(path, fingerprint=self.config_fingerprint(),
                            pos=self.pos, chunk_counter=self._chunk_counter,
                            arrays=arrays, extra=meta_extra)

    def restore(self, path: str | os.PathLike) -> dict:
        """Restore state saved by :meth:`snapshot`.

        Raises :class:`~dllama_tpu.io.integrity.ArtifactError` on
        corruption and its :class:`~dllama_tpu.runtime.snapshot.
        SnapshotMismatch` subclass when the snapshot came from a
        differently-shaped engine — the caller (server boot) catches
        ArtifactError and cold-starts.  On success the continued decode
        stream is token-identical to never having restarted
        (tests/test_snapshot.py); returns the snapshot's ``extra`` dict."""
        from ..io.integrity import bump_counter
        from . import snapshot as snapfmt
        meta, arrays = snapfmt.load(path)
        want_fp = self.config_fingerprint()
        if meta["fingerprint"] != want_fp:
            raise snapfmt.SnapshotMismatch(
                path, "fingerprint",
                "snapshot is from a differently-configured engine",
                expected=want_fp, got=meta["fingerprint"])
        cache_np = {}
        for name, cur in self._cache_arrays().items():
            arr = arrays.get(name)
            if arr is None:
                raise snapfmt.SnapshotMismatch(
                    path, f"array {name!r}", "missing cache array")
            if tuple(arr.shape) != tuple(cur.shape) or \
                    str(arr.dtype) != str(np.asarray(cur).dtype):
                raise snapfmt.SnapshotMismatch(
                    path, f"array {name!r}",
                    "cache array layout mismatch",
                    expected=f"{np.asarray(cur).dtype}{tuple(cur.shape)}",
                    got=f"{arr.dtype}{tuple(arr.shape)}")
            cache_np[name] = arr
        pos = int(meta["pos"])
        if not (0 <= pos <= self.seq_len):
            raise snapfmt.SnapshotMismatch(
                path, "pos", "restored position outside the context window",
                expected=f"0..{self.seq_len}", got=pos)
        snap_sp = meta.get("extra", {}).get("sampling_path")
        if snap_sp is not None and snap_sp != self.sampling_path:
            # a sampled stream drawn on one path cannot continue on the
            # other without silently changing the distribution — refuse
            # (absent flag = pre-ISSUE-20 snapshot, greedy-safe either way)
            raise snapfmt.SnapshotMismatch(
                path, "sampling_path",
                "snapshot sampled on a different sampling path",
                expected=self.sampling_path, got=snap_sp)
        cache = self.cache._replace(
            **{n: cache_np[f"cache.{n}"] for n in self.cache.planes()})
        self.cache = jax.device_put(cache, self._cache_sh)
        self.pos = pos
        self._state_lo, self._state_hi, self._state_ring_lo = (
            list(meta.get("extra", {}).get("conv_state", (0, pos))) + [0])[:3]
        self._chunk_counter = int(meta["chunk_counter"])
        self._key = jnp.asarray(arrays["rng_key"]) if "rng_key" in arrays \
            else jax.random.PRNGKey(0)
        self._dev_key = jnp.asarray(arrays["rng_dev_key"]) \
            if "rng_dev_key" in arrays else None
        self._offsets = jnp.asarray(arrays["offsets"]) \
            if meta.get("extra", {}).get("has_offsets") else None
        # caller arrays saved via snapshot(extra_arrays=...) — e.g. the
        # paged scheduler's page tables — handed back out-of-band
        known = set(self._cache_arrays()) | {"rng_key", "rng_dev_key",
                                             "offsets"}
        self.restored_arrays = {n: a for n, a in arrays.items()
                                if n not in known}
        bump_counter("snapshot_restores")
        return dict(meta.get("extra", {}))

    # -- per-request KV hand-off (DLREQ01, runtime/snapshot.py) ---------
    def handoff_fingerprint(self) -> str:
        """Geometry digest for per-request KV hand-off.

        Looser than :meth:`config_fingerprint`: a request's pages mean
        the same thing on any replica with the same model, context
        window, and page shape/dtype — batch width and pool *size* are
        deliberately excluded (the importer allocates its own physical
        pages), so a 4-slot and an 8-slot replica can exchange requests
        as long as their page geometry matches."""
        from . import snapshot as snapfmt
        self._refuse_slot_state("per-request hand-off (DLREQ01)")
        if not self.paged:
            raise ValueError("per-request hand-off needs a paged KV cache "
                             "(kv_pages > 0)")
        k = self.cache.k
        fields = {
            **_model_fields(self.cfg), "seq_len": self.seq_len,
            # page shape (ps, Hkv, Dh | ps, C) + dtype, not pool page count, with
            # the axis order by name: a record written head-major (before
            # PR 27) is refused even where Hkv == ps; the codec is
            # explicit so int8-paged vs dense records reject cleanly even
            # where the raw dtype string would coincide
            "page": [str(k.dtype), list(k.shape[2:])],
            "page_axes": page_axes(self.cache),
            "codec": "int8" if self.cache.quantized else "dense",
            "handoff": 1,
        }
        return snapfmt.fingerprint(fields)

    def set_rng(self, key_np, chunk_counter: int, dev_key_np=None) -> None:
        """Rebase the sampler RNG stream (hand-off import: continue the
        exporting replica's draw sequence instead of this process's).
        ``dev_key_np`` rebases the device-resident sampling chain too, so
        a preempted sampled slot resumes with an identical distribution;
        None resets the chain to re-seed off the host stream."""
        self._key = jnp.asarray(key_np)
        self._chunk_counter = int(chunk_counter)
        self._dev_key = None if dev_key_np is None else jnp.asarray(dev_key_np)

    def _next_dev_key(self) -> jax.Array:
        """Current device RNG chain head, seeding it from the host stream
        on first use (fold_in keeps legacy greedy snapshots byte-stable:
        the host stream itself never advances differently)."""
        if self._dev_key is None:
            self._dev_key = jax.random.fold_in(self._key,
                                               self._chunk_counter)
            self._chunk_counter += 1
        return self._dev_key

    @property
    def ring_pages(self) -> int:
        """Pages of a slot's ring in a windowed model's window planes (0: the
        engine has none)."""
        wk = self.cache.wk if self.paged else None
        return 0 if wk is None else wk.shape[1] // self.batch

    @property
    def slot_state(self) -> str:
        """What a slot engine's slots own that no page id addresses (empty:
        nothing): ``slot_owns`` of the kind's row in ``models/cache_kinds.py``.
        The scheduler keeps everything that moves a request's cache page by page
        off while this is set, and its steps within ``slot_rows``."""
        return next((k.slot_owns for k in self.cfg.cache_kinds if k.slot_owns
                     and (self.paged or k.slot_unpaged)), "")

    slot_rows = cache_kinds.SLOT_ROWS  # the widest step of a slot that owns one

    def _refuse_slot_state(self, what: str) -> None:
        """What moves a request's cache page by page is refused by name for a
        model whose slots own a state that no page id addresses."""
        if self.slot_state:
            raise ValueError(
                f"{what} is not supported for a "
                f"{mfile.ARCH_NAMES[self.cfg.arch]} model: a slot's "
                f"{self.slot_state} cannot be carried page by page")

    def read_pool_pages(self, pages) -> dict[str, np.ndarray]:
        """Copy the given physical pages out of the paged pool to host
        numpy, all layers at once: shape ``(L, n, ps, Hkv, Dh)`` (plus the
        ``(L, n, ps, Hkv, 1)`` scale planes for an int8 pool; a latent pool
        is ``pages.k`` alone, ``(L, n, ps, C)``).  Used by
        the scheduler's drain-time export and the spill path."""
        return {k: h.wait() for k, h in
                self.read_pool_pages_async(pages).items()}

    def read_pool_pages_async(self, pages) -> dict:
        """Start device-to-host copies of the given physical pages and
        return ``{name: handle}`` where ``handle.wait()`` yields the host
        ndarray.  The gather is enqueued on the device stream behind
        whatever is already in flight and ``copy_to_host_async`` makes
        the D2H transfer non-blocking — the spill path issues the copies,
        does its host-side bookkeeping, and only ``wait()``s right before
        freeing the pages, so the transfer hides behind the next dispatch
        burst."""
        idx = jnp.asarray(np.asarray(pages, np.int32))

        class _Handle:
            def __init__(self, dev):
                self._dev = dev
                try:
                    dev.copy_to_host_async()
                except Exception:
                    pass  # backend without async D2H: wait() still works

            def wait(self):
                return np.asarray(self._dev)

        return {f"pages.{n}": _Handle(a[:, idx])
                for n, a in self.cache.pool_planes().items()}

    def write_pool_pages(self, pages, arrays: dict[str, np.ndarray]) -> None:
        """Write exported page slices (from :meth:`read_pool_pages` on a
        peer) into this engine's pool at the given physical page ids.
        One transient pool copy — acceptable at hand-off import time,
        which is off the steady-state decode path."""
        idx = jnp.asarray(np.asarray(pages, np.int32))
        self._refuse_slot_state("writing a request's pages into the pool")
        cache = self.cache._replace(**{
            n: a.at[:, idx].set(jnp.asarray(arrays[f"pages.{n}"], a.dtype))
            for n, a in self.cache.pool_planes().items()})
        self.cache = jax.device_put(cache, self._cache_sh)

    def _sync(self, arrays, what: str) -> list[str]:
        """Block until ``arrays`` are device-ready — THE engine's blocking
        edge — under the watchdog, firing the ``engine.device_step`` fault
        point first (runtime/faults.py).  Returns the fault actions that
        ask the call site to transform its value (``nan``).

        With ``step_timeout`` set, the wait runs on a helper thread and a
        wait that outlives the deadline raises :class:`StepTimeout` (the
        helper is a daemon; a truly wedged runtime leaks one parked
        thread, which is the price of the caller staying responsive).
        """
        from .faults import FAULTS

        def wait() -> list[str]:
            actions = FAULTS.fire("engine.device_step")
            jax.block_until_ready(arrays)
            if obs_memory.ACCOUNT.pending:
                # the first wait after a fresh program's launch: the cache is
                # the last launch's output, so the program itself has ended
                # (a pipelined step waits for its predecessor's tokens here)
                # before the account reads where it left the peak
                jax.block_until_ready(self.cache)
                obs_memory.ACCOUNT.landed()
            return actions

        if not self.step_timeout:
            return wait()
        import threading
        box: dict = {}

        def run():
            try:
                box["actions"] = wait()
            except BaseException as e:  # surfaced below, on the caller
                box["error"] = e

        t = threading.Thread(target=run, daemon=True,
                             name=f"dllama-step-watchdog({what})")
        t.start()
        t.join(self.step_timeout)
        if t.is_alive():
            raise StepTimeout(
                f"{what} did not become ready within {self.step_timeout}s "
                f"(pos={self.pos}, batch={self.batch}, mesh={dict(self.mesh.shape)})")
        if "error" in box:
            raise box["error"]
        return box["actions"]

    def _numeric_guard(self, host_logits: np.ndarray, step: str) -> np.ndarray:
        """Check a host-fetched logits array for NaN/Inf (``numeric_checks``
        mode; see :class:`NumericFault`).  Fires the ``engine.numeric``
        fault point first — its ``nan`` action poisons the checked array so
        the fault path is testable without real corruption.  Guards cover
        every host-logits step (prefill, single-token decode, ragged
        prefill); the on-device chunked decode loop only ships token ids
        to the host, so a divergence there surfaces at the next
        host-logits step (the following turn's prefill) — the bounded
        blind spot is documented in docs/ROBUSTNESS.md."""
        if not self.numeric_checks:
            return host_logits
        from .faults import FAULTS
        from ..io.integrity import bump_counter
        if "nan" in FAULTS.fire("engine.numeric"):
            host_logits = np.full_like(host_logits, np.nan)
        if not np.isfinite(host_logits).all():
            bump_counter("numeric_faults")
            bad = int(np.size(host_logits) - np.count_nonzero(
                np.isfinite(host_logits)))
            raise NumericFault(
                step, self.pos,
                hint=f"{bad}/{host_logits.size} non-finite logits; detection "
                     "is at the output logits (no layer attribution) — "
                     "bisect with --verify-weights and a dense kv cache")
        return host_logits

    def _note_executable(self, fresh: bool, compile_s: float | None = None,
                         key=None):
        """Feed the compile-telemetry metrics for one executable lookup:
        a recompile (with its first-call wall time, where the caller has a
        clean boundary) or a cache hit, plus the live-executable gauge."""
        if fresh:
            obs_metrics.ENGINE_RECOMPILES.inc()
            if compile_s is not None:
                obs_metrics.ENGINE_COMPILE_S.observe(compile_s)
            _log.info("compile", extra={
                "key": repr(key),
                "compile_s": None if compile_s is None
                else round(compile_s, 3)})
        else:
            obs_metrics.ENGINE_CACHE_HITS.inc()
        obs_metrics.ENGINE_LIVE_EXECUTABLES.set(
            len(self._compiled_steps) + len(self._chunk_fns))

    def _run(self, tokens_np: np.ndarray, last_index: int,
             offsets: jax.Array | None = None) -> tuple[np.ndarray, StepStats]:
        if self.paged:
            raise ValueError("paged engine is slot-only: the pool has no "
                             "contiguous per-row addressing; drive it via "
                             "slot_step / the slot scheduler")
        k = int(tokens_np.shape[1])
        with obs_trace.span("engine.prefill" if k > 1 else
                            "engine.decode_step", pos=self.pos, k=k):
            return self._run_step(tokens_np, last_index, offsets)

    def _run_step(self, tokens_np: np.ndarray, last_index: int,
                  offsets: jax.Array | None, fetch: bool = True
                  ) -> tuple[np.ndarray | None, StepStats]:
        """:meth:`_run` inside its span: enqueue, wait, fetch the logits
        (``fetch=False``: wait only, for a chunk of a prompt that is not its
        last; the logits stay on the device and ``None`` is returned)."""
        stats = StepStats()
        t0 = time.perf_counter()
        self._state_enter(self.pos)
        # from-scratch prefill on an sp mesh → blockwise ring attention with
        # the tokens (and therefore all activations) sharded on the
        # sequence axis: per-chip activation memory scales 1/sp, which is
        # what lets a prompt longer than one chip's HBM prefill at all
        use_ring = (self.sp > 1 and self.pos == 0 and tokens_np.shape[1] > 1
                    and tokens_np.shape[1] % self.sp == 0)
        # jit compiles per input shape: a shape first seen here is a fresh
        # XLA executable, whose first-call wall (t1 - t0) is dominated by
        # trace + compile — that's what the compile histogram records
        step_key = ("ring" if use_ring else "step",
                    tokens_np.shape, offsets is not None)
        fresh_exec = step_key not in self._compiled_steps
        with _compiling(fresh_exec, *step_key), active_mesh(self.mesh):
            if use_ring:
                toks = jax.device_put(
                    tokens_np, NamedSharding(self.mesh, P("dp", "sp")))
                (logits, self.cache), fill = self._step_ring(
                    self.params, self.cache, toks,
                    jnp.int32(self.pos), jnp.int32(last_index)), None
            else:
                logits, self.cache, fill = self._step(
                    self.params, self.cache, jnp.asarray(tokens_np),
                    jnp.int32(self.pos), jnp.int32(last_index), offsets)
        self._state_wrote(self.pos, last_index + 1, int(tokens_np.shape[1]))
        fired = self._sync(logits, "prefill/decode step")
        if fill is not None:  # blocks were filled, and the call has been waited for
            pairs, slots = (int(v) for v in np.asarray(fill))
            obs_metrics.MOE_GROUPED_ROWS.inc("pairs", n=pairs)
            obs_metrics.MOE_GROUPED_ROWS.inc("slots", n=slots)
        t1 = time.perf_counter()
        if fresh_exec:
            self._compiled_steps.add(step_key)
        self._note_executable(fresh_exec, (t1 - t0) if fresh_exec else None,
                              key=step_key)
        host_logits = None
        if fetch:
            host_logits = np.asarray(logits)  # (B, V)
            if "nan" in fired:  # injected device fault: poisoned logits
                host_logits = np.full_like(host_logits, np.nan)
            host_logits = self._numeric_guard(
                host_logits, "prefill" if tokens_np.shape[1] > 1 else "decode")
        t2 = time.perf_counter()
        # block_until_ready (t1) marks end of execution; the rest is fetch
        stats.inference_ms = (t1 - t0) * 1000
        stats.transfer_ms = (t2 - t1) * 1000
        stats.generation_ms = (t2 - t0) * 1000
        stats.sent_bytes = tokens_np.nbytes + 8  # token ids + pos/last scalars
        stats.recv_bytes = host_logits.nbytes if fetch else 0
        obs_metrics.ENGINE_GENERATION_MS.observe(stats.generation_ms)
        obs_metrics.ENGINE_INFERENCE_MS.observe(stats.inference_ms)
        obs_metrics.ENGINE_TRANSFER_MS.observe(stats.transfer_ms)
        obs_metrics.HOST_DEVICE_SENT_BYTES.observe(stats.sent_bytes)
        obs_metrics.HOST_DEVICE_RECV_BYTES.observe(stats.recv_bytes)
        return host_logits, stats

    def prefill(self, prompt_tokens: list[int]) -> tuple[np.ndarray, StepStats]:
        """Process the whole prompt; returns logits for its last token."""
        n = len(prompt_tokens)
        if n == 0:
            raise ValueError("empty prompt")
        if self.pos + n > self.seq_len:
            raise ContextOverflow(
                f"prompt of {n} exceeds seq_len {self.seq_len} at pos {self.pos}")
        if n > self.cfg.prefill_chunk() and self.sp == 1:
            return self._prefill_chunked(prompt_tokens)
        logits, stats = self._run(self._bucketed(prompt_tokens), n - 1)
        self.pos += n
        _log.info("prefill", extra={
            "n_tokens": n, "pos": self.pos,
            "generation_ms": round(stats.generation_ms, 3)})
        return logits, stats

    def _bucketed(self, tokens: list[int]) -> np.ndarray:
        """``tokens`` in every row of a zero array padded to their compile
        bucket.  The padded bucket must also fit the cache:
        dynamic_update_slice clamps out-of-range starts *backwards*, which
        would silently overwrite valid KV history near the end of context."""
        n = len(tokens)
        bucket = max(n, min(_next_bucket(n), self.seq_len - self.pos))
        toks = np.zeros((self.batch, bucket), np.int32)
        toks[:, :n] = tokens
        return toks

    def _prefill_chunked(self, prompt_tokens: list[int]
                         ) -> tuple[np.ndarray, StepStats]:
        """A prompt longer than one prefill chunk (``ModelConfig.prefill_chunk``,
        a static rule from the shapes): whole chunks through ONE compiled
        program (``pos`` is traced), then the tail through its power-of-two
        bucket, as a short prompt goes.  Each call is waited for, so that its
        span ``engine.prefill_chunk`` is the chunk's time; only the last
        call's logits come to the host.  Returns them and the summed stats."""
        if self.paged:
            raise ValueError("paged engine is slot-only: drive it via "
                             "slot_step / the slot scheduler")
        chunk = self.cfg.prefill_chunk()
        n = len(prompt_tokens)
        total = StepStats()
        with obs_trace.span("engine.prefill", pos=self.pos, k=n,
                            chunks=-(-n // chunk)):
            for lo in range(0, n, chunk):
                piece = prompt_tokens[lo:lo + chunk]
                toks = self._bucketed(piece)
                with obs_trace.span("engine.prefill_chunk", pos=self.pos,
                                    k=len(piece), rows=toks.shape[1]):
                    logits, stats = self._run_step(
                        toks, len(piece) - 1, None, fetch=lo + chunk >= n)
                obs_metrics.ENGINE_PREFILL_CHUNKS.inc()
                self.pos += len(piece)
                for f in ("generation_ms", "inference_ms", "transfer_ms",
                          "sent_bytes", "recv_bytes"):
                    setattr(total, f, getattr(total, f) + getattr(stats, f))
        _log.info("prefill", extra={
            "n_tokens": n, "pos": self.pos, "chunks": -(-n // chunk),
            "generation_ms": round(total.generation_ms, 3)})
        return logits, total

    def prefill_ragged(self, prompts: list[list[int]]
                       ) -> tuple[np.ndarray, StepStats]:
        """Prefill B *distinct* prompts left-padded to one bucket.

        Beyond reference (the reference fixes batch=1, tasks.cpp:199-210).
        Each prompt is right-aligned so every row's last real token lands
        on the shared index ``longest-1``; ``offsets[r] = longest -
        len(prompt_r)`` is kept on the engine and threaded into every
        subsequent decode step (per-row RoPE positions + attention key
        floors).  Rows see exactly the keys/angles they would see alone,
        so greedy decode matches the single-stream output per row.

        Like single-stream :meth:`prefill`, the token array pads up to a
        compile bucket but ``pos`` advances only to ``longest`` — the pad
        tail's garbage KV sits beyond the live region and the first
        decode steps overwrite it.  Lockstep caveat: the whole batch
        shares one position clock starting at ``longest``, so a short row
        batched with a much longer one has ``longest - len(prompt_r)``
        fewer context slots than it would alone; parity with the
        single-stream run holds while the requested steps fit that
        budget.
        """
        if len(prompts) != self.batch:
            raise ValueError(f"{len(prompts)} prompts for batch={self.batch}")
        if any(len(p) == 0 for p in prompts):
            raise ValueError("empty prompt")
        if self.sp > 1:
            raise ValueError("ragged batches are not supported on sp meshes "
                             "(sequence-sharded cache); use sp=1")
        if self.pos != 0:
            raise ValueError("ragged prefill starts a fresh batch; call reset()")
        longest = max(len(p) for p in prompts)
        if longest > self.seq_len:
            raise ContextOverflow(
                f"prompt of {longest} exceeds seq_len {self.seq_len}")
        bucket = max(longest, min(_next_bucket(longest), self.seq_len))
        toks = np.zeros((self.batch, bucket), np.int32)
        offsets = np.zeros((self.batch,), np.int32)
        for r, p in enumerate(prompts):
            toks[r, longest - len(p):longest] = p
            offsets[r] = longest - len(p)
        self._offsets = jnp.asarray(offsets)
        logits, stats = self._run(toks, longest - 1, offsets=self._offsets)
        self.pos = longest
        return logits, stats

    def decode_one(self, token: int) -> tuple[np.ndarray, StepStats]:
        """One autoregressive step at the current position."""
        if self.pos >= self.seq_len:
            raise ContextOverflow(f"position {self.pos} at seq_len limit {self.seq_len}")
        toks = np.full((self.batch, 1), token, np.int32)
        logits, stats = self._run(toks, 0)
        self.pos += 1
        return logits, stats

    # ------------------------------------------------------------------
    def _chunk_fn(self, steps: int, temperature: float, topp: float):
        """Compiled on-device K-step generation loop (runtime/decode_loop.py)."""
        from .decode_loop import decode_chunk
        key = (steps, float(temperature), float(topp))
        fresh = key not in self._chunk_fns
        if fresh:
            cfg = self.cfg
            self._chunk_fns[key] = jax.jit(
                lambda p, c, tok, pos, k, off=None: decode_chunk(
                    p, cfg, c, tok, pos, k,
                    steps=steps, temperature=key[1], topp=key[2], offsets=off),
                donate_argnums=(1,),
                # tokens/scalars replicated for process-local fetch; cache
                # keeps its sharding (see __init__)
                out_shardings=(self._rep, self._cache_sh,
                               self._rep, self._rep, self._rep))
        # compile seconds are observed at the first *call* (the dispatch
        # sites), where jit actually traces + compiles; here only the
        # recompile/cache-hit decision exists
        self._note_executable(fresh, key=("chunk",) + key)
        return self._chunk_fns[key]

    @_closes_request
    def generate_stream(self, prompt_tokens: list[int], steps: int, *,
                        temperature: float = 0.0, topp: float = 0.9,
                        seed: int | None = 0, eos_ids: tuple[int, ...] = (),
                        chunk: int = 16):
        """High-throughput generation: sampling and the decode loop run on
        device; token ids stream back in chunks.

        Yields ``(token_id, StepStats)``.  Prompt tokens are echoed first
        (reference generate-mode contract, dllama.cpp:45-93); the per-token
        stats of a chunk are the chunk averages.

        ``seed=None`` continues the engine's existing RNG stream instead of
        restarting it — multi-turn chat seeds once per session and lets the
        stream advance across turns, like the reference's single Sampler
        whose xorshift state persists for the process (app.cpp:33,
        dllama.cpp:196-203; VERDICT r04 Weak #6).
        """
        steps = min(steps, self.seq_len - self.pos)
        chunk = self._max_burst(chunk)
        if seed is not None:
            self._key = jax.random.PRNGKey(seed)
            self._chunk_counter = 0

        logits, pstats = self.prefill(prompt_tokens[:])
        for i, t in enumerate(prompt_tokens):
            yield t, pstats if i == len(prompt_tokens) - 1 else StepStats()
        produced = len(prompt_tokens)
        if produced >= steps:
            return

        # one RNG stream per generation: the first token samples from the
        # fetched prefill logits with the *same* JAX counter-based PRNG the
        # on-device chunks use (fold_in of the seed key), so a fixed seed
        # corresponds to exactly one stream (ADVICE r01: previously token 1
        # came from the host xorshift Sampler and the rest from JAX)
        from .decode_loop import device_sample
        sub = jax.random.fold_in(self._key, self._chunk_counter)
        self._chunk_counter += 1
        token = int(np.asarray(device_sample(
            jnp.asarray(logits), sub, temperature, topp))[0])
        # prefill cost was already attributed to the last prompt token; this
        # token only cost a sample over the fetched logits
        yield token, StepStats()
        produced += 1
        if token in eos_ids:
            return

        # Pipelined chunk dispatch: chunk N+1 is enqueued — fed the
        # on-device last token the chunk fn returns — BEFORE chunk N's ids
        # are fetched, so the host dispatch bubble overlaps device
        # execution.  Token streams are bit-identical to
        # the serial schedule (same compiled fn, same inputs; only host
        # scheduling changes).  An EOS that lands mid-chunk discards the
        # one speculative in-flight chunk: its cache writes sit past the
        # rewound position (dead rows, overwritten later, same overshoot
        # invariant as within-chunk EOS) and its RNG tick is rolled back.
        def dispatch(in_tok_dev, done):
            # ``done`` counts tokens EXPECTED by prior dispatches (not yet
            # necessarily fetched) so a speculative chunk never overshoots
            # the requested steps
            k = min(chunk, steps - done, self.seq_len - self.pos)
            key = (k, float(temperature), float(topp))
            fresh = key not in self._chunk_fns
            fn = self._chunk_fn(*key)
            sub = jax.random.fold_in(self._key, self._chunk_counter)
            self._chunk_counter += 1
            p0 = self.pos
            self._state_enter(p0)
            # host→device bytes actually crossing for THIS dispatch: the
            # pos scalar + folded key always; the token array only when it
            # comes from the host (first chunk) — later chunks feed the
            # device-carried last token, which never touches the host
            sent = 12 + (in_tok_dev.nbytes
                         if isinstance(in_tok_dev, np.ndarray) else 0)
            t0 = time.perf_counter()
            with obs_trace.span("engine.chunk_enqueue", pos=p0, k=k), \
                    _compiling(fresh, "chunk", *key), active_mesh(self.mesh):
                toks_dev, self.cache, last_dev, _pos, _key = fn(
                    self.params, self.cache, jnp.asarray(in_tok_dev),
                    jnp.int32(p0), sub)
            if fresh:
                # jit's first call blocks through trace + XLA compile
                # before the async dispatch returns — this wall is the
                # compile cost the histogram tracks
                obs_metrics.ENGINE_COMPILE_S.observe(time.perf_counter() - t0)
            self.pos = p0 + k
            self._state_wrote(p0, k, k)
            return k, p0, toks_dev, last_dev, t0, sent

        if produced >= steps or self.pos >= self.seq_len:
            return  # nothing left to dispatch (e.g. max_tokens == 1)
        pending = dispatch(np.full((self.batch,), token, np.int32), produced)
        expected = produced
        boundary = None
        try:
            while pending is not None:
                k, p0, toks_dev, last_dev, t0, sent = pending
                expected += k
                pending = dispatch(last_dev, expected) \
                    if expected < steps and self.pos < self.seq_len else None
                with obs_trace.span("engine.chunk_fetch", pos=p0, k=k):
                    self._sync(toks_dev, f"decode chunk at pos {p0}")
                    t1 = time.perf_counter()
                    toks = np.asarray(toks_dev)[:, 0]  # (k,)
                t2 = time.perf_counter()
                # steady-state chunk wall = boundary to boundary (this
                # chunk was dispatched before the PREVIOUS fetch returned)
                g0 = t0 if boundary is None else max(boundary, t0)
                boundary = t2
                i_ms, t_ms = (t1 - g0) * 1000 / k, (t2 - t1) * 1000 / k
                # chunk averages: each of the k tokens carries 1/k of the
                # chunk's wall/device/boundary cost (labeled in the CLI)
                per = StepStats(
                    generation_ms=(t2 - g0) * 1000 / k,
                    inference_ms=i_ms,
                    transfer_ms=t_ms,
                    sent_bytes=sent / k,
                    recv_bytes=toks.nbytes / k)
                obs_metrics.ENGINE_GENERATION_MS.observe(per.generation_ms)
                obs_metrics.ENGINE_INFERENCE_MS.observe(per.inference_ms)
                obs_metrics.ENGINE_TRANSFER_MS.observe(per.transfer_ms)
                obs_metrics.HOST_DEVICE_SENT_BYTES.observe(sent)
                obs_metrics.HOST_DEVICE_RECV_BYTES.observe(toks.nbytes)
                _log.debug("decode_chunk", extra={
                    "pos": p0, "k": k,
                    "generation_ms": round(per.generation_ms, 3)})
                for j, tk in enumerate(toks.tolist()):
                    token = int(tk)
                    yield token, per
                    produced += 1
                    if token in eos_ids:
                        # rewind past the unconsumed overshoot so a
                        # following turn prefills at the right position:
                        # keys and values above it are masked rows, never
                        # attended and overwritten; a convolution state is
                        # a ring of positions that still holds the rows
                        # before it (the pos-rewind invariant above
                        # ``state_holds``; a burst is capped to fit,
                        # ``_max_burst``); the finally below returns the
                        # speculative chunk's RNG tick
                        self.pos = p0 + j + 1
                        return
                    if produced >= steps:
                        return
        finally:
            # Reached on EOS return AND when the consumer abandons the
            # generator (stop-string break in drain_generation →
            # GeneratorExit): a speculative in-flight chunk is dead rows
            # past the live position, and its unconsumed RNG tick is
            # returned so a later turn's sampled stream is
            # schedule-independent of the pipelining.
            if pending is not None:
                self._chunk_counter -= 1

    def generate_batch(self, prompts: list[list[int]], steps: int, *,
                       temperature: float = 0.0, topp: float = 0.9,
                       seed: int | None = 0,
                       eos_ids: tuple[int, ...] = (), chunk: int = 16
                       ) -> list[list[int]]:
        """Decode B *distinct* prompts in lockstep on one mesh.

        Beyond reference — the reference fixes batch=1 per cluster
        (tasks.cpp:199-210); this is the TPU throughput lever that needs
        no extra chips: the decode matmuls amortize one weight read over
        B rows.  Returns B token lists, each ``prompts[r]`` followed by
        its continuation, truncated per row at ``steps`` total tokens or
        the row's EOS.  Greedy (temperature 0) rows match the
        single-stream ``generate_stream`` output token for token while
        the steps fit the shared position budget (the clock starts at the
        longest prompt's length — see :meth:`prefill_ragged`); sampled
        rows are reproducible from ``seed`` but draw from a different
        PRNG stream than a batch-1 run.

        Rows that finish early stay in the lockstep batch (their cache
        rows keep advancing with ignored tokens) until every row is done
        — the batch is one-shot, not a continuable conversation; the
        per-row bookkeeping an incremental server needs lives in
        server/api.py.
        """
        if steps <= 0:
            raise ValueError("steps must be positive")
        steps = min(steps, self.seq_len)  # same clamp as the stream core
        outs = [list(p) for p in prompts]
        done = [len(o) >= steps for o in outs]
        for row_tokens in self.generate_batch_stream(
                prompts, steps, temperature=temperature, topp=topp,
                seed=seed, chunk=chunk):
            for r, t in enumerate(row_tokens.tolist()):
                if done[r]:
                    continue
                outs[r].append(int(t))
                if int(t) in eos_ids or len(outs[r]) >= steps:
                    done[r] = True
            if all(done):
                break
        return outs

    @_closes_request
    def generate_batch_stream(self, prompts: list[list[int]], steps: int, *,
                              temperature: float = 0.0, topp: float = 0.9,
                              seed: int | None = 0, chunk: int = 16):
        """The lockstep core of :meth:`generate_batch`, as a generator:
        yields one ``(B,)`` int32 array per decoded step, every row's
        sampled token, as each on-device chunk lands.  EOS/length policy
        belongs to the consumer (generate_batch truncates per row; the
        API server streams per-row deltas with its own stop detectors) —
        finished rows keep decoding in lockstep and their later tokens
        are simply ignored.  The stream ends at ``steps`` total yields or
        the context window, whichever first (every row's per-prompt cap
        lies below ``steps``, see generate_batch); consumers that want
        fewer tokens stop iterating (both built-in consumers break when
        every row is done).  Abandoning the generator mid-batch is fine:
        the batch is one-shot, not a continuable conversation."""
        from .decode_loop import device_sample
        if steps <= 0:
            raise ValueError("steps must be positive")
        if seed is not None:
            self._key = jax.random.PRNGKey(seed)
            self._chunk_counter = 0

        chunk = self._max_burst(chunk)
        logits, _ = self.prefill_ragged(prompts)  # validates batch/sp/pos
        sub = jax.random.fold_in(self._key, self._chunk_counter)
        self._chunk_counter += 1
        tok_vec = np.asarray(device_sample(
            jnp.asarray(logits), sub, temperature, topp))  # (B,)
        yield tok_vec

        # depth-1 pipelined dispatch, mirroring generate_stream: chunk N+1
        # is enqueued on the device-carried last row of tokens before
        # chunk N's fetch, overlapping the host dispatch bubble with
        # device execution.  Consumers break when every row is done
        # (GeneratorExit) — the finally returns the speculative chunk's
        # RNG tick; its cache rows are dead (the batch is one-shot and
        # reset() precedes reuse).
        def dispatch(in_tok, done):
            # ``done`` = steps already covered by prior dispatches, so a
            # speculative chunk never runs past the consumer's budget
            k = min(chunk, steps - done, self.seq_len - self.pos)
            key = (k, float(temperature), float(topp))
            fresh = key not in self._chunk_fns
            fn = self._chunk_fn(*key)
            sub = jax.random.fold_in(self._key, self._chunk_counter)
            self._chunk_counter += 1
            tc = time.perf_counter()
            self._state_enter(self.pos)
            with _compiling(fresh, "chunk", *key), active_mesh(self.mesh):
                toks_dev, self.cache, last_dev, _pos, _key = fn(
                    self.params, self.cache, jnp.asarray(in_tok, jnp.int32),
                    jnp.int32(self.pos), sub, self._offsets)
            if fresh:  # first call blocks through trace + compile
                obs_metrics.ENGINE_COMPILE_S.observe(time.perf_counter() - tc)
            self._state_wrote(self.pos, k, k)
            self.pos += k
            return k, toks_dev, last_dev

        expected = 1  # the prefill-sample step already yielded
        if expected >= steps or self.pos >= self.seq_len:
            return
        pending = dispatch(tok_vec, expected)
        try:
            while pending is not None:
                k, toks_dev, last_dev = pending
                expected += k
                pending = dispatch(last_dev, expected) \
                    if expected < steps and self.pos < self.seq_len else None
                t0 = time.perf_counter()
                self._sync(toks_dev, "batch decode chunk")
                obs_trace.record("decode_chunk", t0, time.perf_counter(),
                                 pos=self.pos - k, k=k, batch=True)
                toks = np.asarray(toks_dev)  # (k, B)
                for j in range(toks.shape[0]):
                    yield toks[j]
        finally:
            if pending is not None:
                self._chunk_counter -= 1

    # ------------------------------------------------------------------
    def _slot_operands(self, kind: str, tokens_np, feed_dev, pos_rows_np,
                       n_valid_np, sub, temps_np, topps_np, topks_np,
                       page_tables_np, vocab_mask_np) -> tuple:
        """The operands of a slot program in its argument order.  The host
        operands are packed into one numpy vector
        (decode_loop.pack_slot_operands), which the jitted call uploads in
        its own argument path (inside ``engine.launch``): no Python-level
        upload an operand in front of it.  The array is private, so the
        caller may overwrite its buffers (the scheduler's page tables) the
        moment the enqueue returns, whenever the runtime reads it.  The span
        ``engine.h2d`` (``arrays`` and ``bytes`` handed over to cross: the
        packed operands, and the vocabulary mask where there is one;
        ``feed_dev``: the tokens were on the device already) and
        ``sched_host_ms{phase="h2d"}`` under the step's ``kind``."""
        from .decode_loop import pack_slot_operands
        with obs_trace.span("engine.h2d", feed_dev=feed_dev is not None,
                            total=obs_metrics.host_ms("h2d", kind)) as sp:
            host = [pack_slot_operands(
                tokens_np, pos_rows_np, n_valid_np, temps_np, topps_np,
                topks_np, page_tables_np if self.paged else None)]
            if vocab_mask_np is not None:
                host.append(np.array(vocab_mask_np, np.bool_, order="C"))
            if self._no_feed is None:  # what a host-fed step hands in as
                # ``fed``: placed like a dispatch's ``last_dev``, so that a
                # key's one executable is traced once
                self._no_feed = jax.device_put(
                    np.zeros((self.batch,), np.int32), self._rep)
            fed = self._no_feed if feed_dev is None else feed_dev
            sp.update(arrays=len(host), bytes=sum(a.nbytes for a in host))
            return (self.params, self.cache, host[0], fed, sub, *host[1:])

    def slot_step_async(self, tokens_np: np.ndarray | None,
                        pos_rows_np: np.ndarray, n_valid_np: np.ndarray, *,
                        temps_np: np.ndarray, topps_np: np.ndarray,
                        topks_np: np.ndarray | None = None,
                        steps: int = 1,
                        page_tables_np: np.ndarray | None = None,
                        vocab_mask_np: np.ndarray | None = None,
                        feed_dev=None) -> "SlotDispatch":
        """Enqueue one continuous-batching dispatch over the
        slot-addressable batch WITHOUT blocking on the result: row ``r``
        consumes its first ``n_valid_np[r]`` tokens of ``tokens_np``
        (B, T) at its own cache positions ``pos_rows_np[r]..``, then
        ``steps - 1`` pure decode steps run on device
        (decode_loop.slot_chunk).  Returns a :class:`SlotDispatch`
        completion handle holding the sampled-id futures; call
        ``.wait()`` for the host (steps, B) array.

        This is the primitive the slot scheduler (runtime/scheduler.py)
        drives: a joining request's prefill chunk and its neighbors'
        decode tokens share one dispatch, and a freed slot is reused by
        just handing its row position 0 again — the previous occupant's
        stale KV sits above the new request's causal ceiling (see
        ops.attention.slot_gqa_attention_at), so per-slot reset costs
        nothing.

        ``feed_dev`` is the device-resident feedback path: pass a prior
        dispatch's ``last_dev`` (B,) and the new dispatch consumes it
        directly as its T=1 token column — the sampled tokens never
        visit the host on the input side, eliminating the
        device→host→device round trip per pure-decode dispatch (the
        paper's T ≈ 0 overlap goal applied to the host boundary).  With
        ``feed_dev`` set, ``tokens_np`` must be None.

        Deliberately does NOT touch ``self.pos`` / ``self._offsets``:
        the one-shot conversation/batch paths and the slot path can share
        one engine as long as their uses don't overlap in time (the
        scheduler's ``exclusive()`` guarantees that), and the scheduler
        tracks every slot's clock host-side.  Compiled per
        ``(T, steps, all-greedy, fused-attention mode, mask presence)``;
        temperature/top-p/top-k ride in as (B,) arrays so heterogeneous
        requests share one program — a feed-fed dispatch shares the T=1
        executable with a host-fed one.  Sampled dispatches draw from the
        device-resident key chain (:meth:`_next_dev_key`) and the chunk
        returns the advanced key, so sampled ``feed_dev`` decode runs
        with zero host round trips.  ``vocab_mask_np`` is the optional
        (V,) or (B, V) boolean keep-mask (grammar seam, identity today).

        On a paged engine ``page_tables_np`` (B, max_pages) int32 is
        required: reads/writes indirect through it into the pool
        (decode_loop.slot_chunk).  Its shape is static per engine, so it
        rides the same compile buckets as one extra operand.
        """
        from .decode_loop import slot_chunk, unpack_slot_operands
        if self.sp > 1:
            raise ValueError("slot serving is not supported on sp meshes "
                             "(sequence-sharded cache); use sp=1")
        if self.cache.quantized and not self.paged:
            raise ValueError("slot serving needs a dense or paged-int8 KV "
                             "cache (contiguous per-row quantized writes "
                             "are not wired)")
        if self.paged and page_tables_np is None:
            raise ValueError("paged engine: slot_step needs page_tables_np")
        if not self.paged and page_tables_np is not None:
            raise ValueError("page tables passed to a contiguous engine")
        if feed_dev is not None:
            if tokens_np is not None:
                raise ValueError("feed_dev replaces tokens_np; pass one")
            t = 1
        elif tokens_np is None:
            raise ValueError("slot step needs tokens_np or feed_dev")
        else:
            t = int(tokens_np.shape[1])
        if steps < 1:
            raise ValueError("steps must be positive")
        # dynamic_update_slice clamps out-of-range starts backwards, which
        # would silently overwrite valid history — refuse instead.  (The
        # paged write path clamps into the scratch page rather than
        # backwards, but the logical-position budget is the same.)
        hi = max(int(np.max(pos_rows_np)) + t,
                 int(np.max(pos_rows_np + n_valid_np)) + (steps - 1))
        if hi > self.seq_len:
            raise ContextOverflow(
                f"slot step would write position {hi - 1} past seq_len "
                f"{self.seq_len}; retire rows at the context edge first")
        self._note_slot_folds(pos_rows_np,
                              pos_rows_np + n_valid_np + (steps - 1))
        greedy = bool(np.all(temps_np == 0.0))
        from ..ops.attention import fused_mode
        has_mask = vocab_mask_np is not None
        key = ("slot_paged" if self.paged else "slot", t, steps, greedy,
               fused_mode() if self.paged else "", has_mask)
        fresh = key not in self._chunk_fns
        if fresh:
            cfg, paged = self.cfg, self.paged
            self._chunk_fns[key] = jax.jit(
                lambda p, c, ops, fed, k, vm=None: slot_chunk(
                    p, cfg, c, key=k, steps=steps, greedy=greedy,
                    vocab_mask=vm,
                    **unpack_slot_operands(ops, fed, t, paged)),
                donate_argnums=(1,),
                out_shardings=(self._rep, self._cache_sh, self._rep,
                               self._rep))
        self._note_executable(fresh, key=key)
        fn = self._chunk_fns[key]
        sub = self._next_dev_key()
        kind = "mixed" if t > 1 else "decode"  # the step's, as given here
        t0 = time.perf_counter()
        # the host's part of the enqueue, in two phases that each feed a
        # cell of ``sched_host_ms``: the operands' preparation
        # (``engine.h2d``) and the jitted call (``engine.launch``:
        # executable look-up, argument handling with the host operands'
        # transfers, PJRT enqueue and any wait inside it; a call that
        # compiled goes to the ``compile`` cell instead)
        with obs_trace.span("engine.slot_enqueue", t=t, steps=steps):
            args = self._slot_operands(
                kind, tokens_np, feed_dev, pos_rows_np, n_valid_np, sub,
                temps_np, topps_np, topks_np, page_tables_np, vocab_mask_np)
            clock0 = _compile_clock()
            with obs_trace.span(
                    "engine.launch", fresh=fresh,
                    total=obs_metrics.host_ms("launch", kind)) as launch, \
                    _compiling(fresh, *key), active_mesh(self.mesh):
                toks_dev, self.cache, last_dev, self._dev_key = fn(*args)
                _launched(launch, kind, clock0)
        return SlotDispatch(self, toks_dev, last_dev, t=t, steps=steps,
                            fresh=fresh, enqueued_at=t0)

    def slot_step(self, tokens_np: np.ndarray, pos_rows_np: np.ndarray,
                  n_valid_np: np.ndarray, *, temps_np: np.ndarray,
                  topps_np: np.ndarray,
                  topks_np: np.ndarray | None = None, steps: int = 1,
                  page_tables_np: np.ndarray | None = None,
                  vocab_mask_np: np.ndarray | None = None) -> np.ndarray:
        """Synchronous :meth:`slot_step_async`: enqueue and immediately
        wait.  Returns the sampled ids (steps, B)."""
        return self.slot_step_async(
            tokens_np, pos_rows_np, n_valid_np, temps_np=temps_np,
            topps_np=topps_np, topks_np=topks_np, steps=steps,
            page_tables_np=page_tables_np,
            vocab_mask_np=vocab_mask_np).wait()

    def slot_verify_async(self, tokens_np: np.ndarray,
                          pos_rows_np: np.ndarray, n_valid_np: np.ndarray, *,
                          temps_np: np.ndarray, topps_np: np.ndarray,
                          topks_np: np.ndarray | None = None,
                          page_tables_np: np.ndarray | None = None,
                          vocab_mask_np: np.ndarray | None = None
                          ) -> "SlotVerifyDispatch":
        """Enqueue one ragged slot-VERIFY dispatch (the batched,
        per-slot generalization of :meth:`_verify_fn`'s single-stream
        verify window): row ``r`` feeds its previous sample plus
        ``n_valid_np[r] - 1`` proposed draft tokens at positions
        ``pos_rows_np[r]..``, and the landed result carries the model's
        prediction at every fed position plus the per-row count of
        accepted leading drafts (decode_loop.slot_verify_chunk).

        A row with ``n_valid`` 1 carries no proposal and rides the burst
        as one plain decode step — the scheduler mixes proposing and
        non-proposing slots freely in a single dispatch, so one slot
        speculating never stalls a neighbor.  Rejected drafts wrote KV
        above their row's accepted ceiling; those entries are dead under
        the causal-ceiling masking (or redirected harmlessly in paged
        mode) exactly like slot-reuse garbage, so rejection truncates
        that row only and costs nothing to undo.

        Compiled per ``(T, all-greedy)``; the verified next-token row
        ``last_dev`` stays device-resident on the handle so a caller can
        feed it onward like :meth:`slot_step_async`'s ``feed_dev``.
        Same engine-state discipline as ``slot_step_async``: slot clocks
        stay host-side with the scheduler; ``self.pos`` is untouched.
        """
        from .decode_loop import slot_verify_chunk, unpack_slot_operands
        if self.sp > 1:
            raise ValueError("slot serving is not supported on sp meshes "
                             "(sequence-sharded cache); use sp=1")
        if self.cache.quantized and not self.paged:
            raise ValueError("slot serving needs a dense or paged-int8 KV "
                             "cache (contiguous per-row quantized writes "
                             "are not wired)")
        if self.paged and page_tables_np is None:
            raise ValueError("paged engine: slot_verify needs page_tables_np")
        if not self.paged and page_tables_np is not None:
            raise ValueError("page tables passed to a contiguous engine")
        t = int(tokens_np.shape[1])
        if t < 2:
            raise ValueError("slot_verify needs T >= 2 (a previous sample "
                             "plus at least one proposal column)")
        if int(np.max(n_valid_np)) > t:
            raise ValueError("n_valid exceeds the verify window width")
        # every fed column writes KV at pos..pos+T-1 (invalid columns land
        # above the ceiling / in the scratch page), so the whole window
        # must fit — same refusal as slot_step_async
        hi = int(np.max(pos_rows_np)) + t
        if hi > self.seq_len:
            raise ContextOverflow(
                f"slot verify would write position {hi - 1} past seq_len "
                f"{self.seq_len}; retire rows at the context edge first")
        self._note_slot_folds(pos_rows_np, pos_rows_np + n_valid_np)
        greedy = bool(np.all(temps_np == 0.0))
        from ..ops.attention import fused_mode
        has_mask = vocab_mask_np is not None
        key = ("slot_verify_paged" if self.paged else "slot_verify",
               t, greedy, fused_mode() if self.paged else "", has_mask)
        fresh = key not in self._chunk_fns
        if fresh:
            cfg, paged = self.cfg, self.paged
            self._chunk_fns[key] = jax.jit(
                lambda p, c, ops, fed, k, vm=None: slot_verify_chunk(
                    p, cfg, c, key=k, greedy=greedy, vocab_mask=vm,
                    **unpack_slot_operands(ops, fed, t, paged)),
                donate_argnums=(1,),
                out_shardings=(self._rep, self._cache_sh,
                               self._rep, self._rep, self._rep))
        self._note_executable(fresh, key=key)
        fn = self._chunk_fns[key]
        sub = self._next_dev_key()
        t0 = time.perf_counter()
        with obs_trace.span("engine.slot_enqueue", t=t, steps=1, verify=True):
            args = self._slot_operands(
                "verify", tokens_np, None, pos_rows_np, n_valid_np, sub,
                temps_np, topps_np, topks_np, page_tables_np, vocab_mask_np)
            clock0 = _compile_clock()
            with obs_trace.span(
                    "engine.launch", fresh=fresh,
                    total=obs_metrics.host_ms("launch", "verify")) as launch, \
                    _compiling(fresh, *key), active_mesh(self.mesh):
                preds_dev, self.cache, accepted_dev, last_dev, \
                    self._dev_key = fn(*args)
                _launched(launch, "verify", clock0)
        return SlotVerifyDispatch(self, preds_dev, accepted_dev, last_dev,
                                  t=t, fresh=fresh, enqueued_at=t0)

    # ------------------------------------------------------------------
    def score_batch(self, sequences: list[list[int]], top_k: int = 0
                    ) -> tuple[np.ndarray, np.ndarray | None, np.ndarray | None]:
        """Teacher-force B sequences through ONE left-padded ragged forward
        and return per-token log-probabilities (beyond reference — the
        API's ``logprobs``).

        Returns ``(token_lp, top_ids, top_lp)``: ``token_lp[r, j]`` is
        ``log P(sequences[r][j+1] | prefix)`` for ``j+1 < len(seq_r)``
        (position 0 has no conditional; rows are right-aligned in a
        bucketed width, so entry ``j`` lives at padded column
        ``tok_lp.shape[1] - len(seq_r) + j``; pad columns hold garbage —
        callers slice by their own lengths).  With ``top_k`` the
        per-position top-k alternative ids and log-probs come back too.
        Scoring runs on a scratch cache copy (no donation) and leaves the
        engine's conversation state untouched except ``reset()``.
        """
        from ..models.transformer import forward, init_kv_cache
        if self.sp > 1:
            raise ValueError("score_batch is not supported on sp meshes")
        if self.paged:
            raise ValueError("paged engine is slot-only; scoring needs a "
                             "contiguous scratch cache")
        if len(sequences) != self.batch:
            raise ValueError(f"{len(sequences)} sequences for batch={self.batch}")
        if any(len(s) < 2 for s in sequences):
            raise ValueError("scoring needs ≥2 tokens per sequence")
        longest = max(len(s) for s in sequences)
        if longest > self.seq_len:
            raise ContextOverflow(
                f"sequence of {longest} exceeds seq_len {self.seq_len}")
        # bucket the padded length so a serving loop compiles one scoring
        # program per bucket, not one per distinct request length (extra
        # left-padding is invisible: offsets grow, masks/RoPE follow)
        bucket = max(longest, min(_next_bucket(longest), self.seq_len))
        toks = np.zeros((self.batch, bucket), np.int32)
        offsets = np.zeros((self.batch,), np.int32)
        for r, s in enumerate(sequences):
            toks[r, bucket - len(s):] = s
            offsets[r] = bucket - len(s)
        key = ("score", bucket, top_k)
        fresh_score = key not in self._chunk_fns
        if fresh_score:
            cfg = self.cfg

            def score(p, c, tk, off):
                logits, _ = forward(p, cfg, tk, c, jnp.int32(0), offsets=off)
                lg = logits.astype(jnp.float32)
                # normalize via a (B, T) logsumexp instead of materializing
                # a second full-vocab log_softmax buffer next to the logits
                lse = jax.scipy.special.logsumexp(lg, axis=-1)  # (B, T)
                # log P of the NEXT fed token, at the position producing it
                nxt = jnp.roll(tk, -1, axis=1)  # (B, T); last col garbage
                tok_lp = jnp.take_along_axis(
                    lg, nxt[..., None], axis=-1)[..., 0] - lse  # (B, T)
                if top_k > 0:
                    tl, ti = jax.lax.top_k(lg, top_k)  # (B, T, k)
                    return tok_lp, ti.astype(jnp.int32), tl - lse[..., None]
                return tok_lp, None, None

            # one replicated sharding as a pytree prefix covers however
            # many array outputs the top_k variant returns
            self._chunk_fns[key] = jax.jit(score, out_shardings=self._rep)
        tc = time.perf_counter()
        with _compiling(fresh_score, *key), active_mesh(self.mesh):
            cache = init_kv_cache(self.cfg, self.batch, bucket,
                                  dtype=self.cache.k.dtype
                                  if not self.cache.quantized else None)
            tok_lp, ti, tl = self._chunk_fns[key](
                self.params, cache, jnp.asarray(toks), jnp.asarray(offsets))
        self._note_executable(
            fresh_score,
            (time.perf_counter() - tc) if fresh_score else None, key=key)
        return (np.asarray(tok_lp),
                None if ti is None else np.asarray(ti),
                None if tl is None else np.asarray(tl))

    # ------------------------------------------------------------------
    def _verify_fn(self, t: int):
        """Compiled T-token verification step returning ALL positions'
        logits (B, T, V) — the speculative-decoding workhorse."""
        from ..models.transformer import forward
        key = ("verify", t)
        fresh = key not in self._chunk_fns
        if fresh:
            cfg = self.cfg

            def verify(p, c, toks, pos):
                logits, c = forward(p, cfg, toks, c, pos)
                # argmax ON DEVICE: only T int32 ids cross the host
                # boundary, not (T, V) logits — the same boundary
                # discipline as the decode chunk (a 128k vocab would
                # otherwise ship ~4 MB per window to the host)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

            self._chunk_fns[key] = jax.jit(
                verify, donate_argnums=(1,),
                out_shardings=(self._rep, self._cache_sh))
        self._note_executable(fresh, key=key)
        return self._chunk_fns[key]

    def generate_pld(self, prompt_tokens: list[int], steps: int, *,
                     ngram: int = 2, k: int = 7,
                     eos_ids: tuple[int, ...] = ()) -> list[int]:
        """Greedy decode with prompt-lookup speculation (beyond reference).

        Draft-model-free speculative decoding: propose the ``k`` tokens
        that followed the most recent occurrence of the current ``ngram``
        suffix earlier in the sequence, then verify the whole window in
        ONE ``T=k+1`` forward.  Decode is weight-bandwidth-bound, so a
        verify step reads the weights once for up to ``k+1`` accepted
        tokens — on repetitive continuations (summarization, code, quoted
        context) this multiplies tokens/weight-read by the acceptance
        rate.  Rejected proposals cost nothing extra: the cache rows they
        wrote sit beyond the live prefix (``pos`` only advances over
        accepted tokens) and are overwritten by the next window, exactly
        like bucketed-prefill padding.

        Output is the vanilla greedy stream (tests pin ``generate_pld ==
        generate_stream`` token for token on the CPU test mesh): every
        emitted token is an argmax of the model distribution at its
        position — speculation only changes how many positions one
        dispatch verifies.  Hardware caveat: the ``T=k+1`` forward may
        reduce bf16 matmuls in a different order than the ``T=1`` decode
        forward, and on packed Q40 weights the fused kernel contracts a
        row that is alone in its block against weights no one rounded
        to bf16 (ops/q40.py ``_body``: logits 1e-3 of the largest apart),
        so an argmax near-tie can resolve differently on a real
        chip; both streams are valid greedy decodes of the model, but
        bit-identity across the two is only guaranteed where reduction
        order and weight rounding match.
        """
        return list(self.generate_pld_stream(prompt_tokens, steps,
                                             ngram=ngram, k=k,
                                             eos_ids=eos_ids))

    @_closes_request
    def generate_pld_stream(self, prompt_tokens: list[int], steps: int, *,
                            ngram: int = 2, k: int = 7,
                            eos_ids: tuple[int, ...] = ()):
        """Generator core of :meth:`generate_pld`: yields the prompt echo,
        then each verified token as its window lands — so the CLI streams
        text during speculation exactly like plain greedy decode."""
        if self.batch != 1:
            raise ValueError("speculative decode is single-stream (batch=1)")
        if self.sp > 1:
            raise ValueError("speculative decode is not supported on sp meshes")
        if self.paged:
            raise ValueError("paged engine is slot-only; speculative decode "
                             "uses contiguous addressing")
        steps = min(steps, self.seq_len - self.pos)
        out = list(prompt_tokens)
        # latest-occurrence n-gram index, maintained incrementally: O(1)
        # lookup per window instead of an O(context) rescan (the host
        # would otherwise idle the device at exactly the long-context
        # lengths speculation targets).  Value = position AFTER the match;
        # only positions ≤ len(out)-1 are indexed, so a lookup never
        # matches the current suffix against itself (the continuation
        # would be empty).
        index: dict[tuple, int] = {}
        indexed = ngram - 1

        def extend_index():
            nonlocal indexed
            hi = len(out) - 1
            for p in range(max(indexed + 1, ngram), hi + 1):
                index[tuple(out[p - ngram:p])] = p
            indexed = max(indexed, hi)

        logits, _ = self.prefill(prompt_tokens[:])
        yield from out
        if len(out) >= steps:
            return  # the prompt always echoes whole (stream contract)
        cur = int(np.asarray(logits)[0].argmax())
        out.append(cur)
        yield cur
        if cur in eos_ids:
            return

        def propose() -> list[int]:
            """Continuation after the latest earlier occurrence of the
            current ngram-suffix; zeros when none (wrong guesses merely
            verify short)."""
            if len(out) > ngram:
                i = index.get(tuple(out[-ngram:]))
                if i is not None:
                    cand = out[i:i + k]
                    return cand + [0] * (k - len(cand))
            return [0] * k

        fn = self._verify_fn(k + 1)
        while len(out) < steps and self.pos + k + 1 <= self.seq_len:
            extend_index()
            window = np.asarray([[cur] + propose()], np.int32)  # (1, k+1)
            p0 = self.pos
            self._state_enter(p0)
            with active_mesh(self.mesh):
                preds_dev, self.cache = fn(
                    self.params, self.cache, jnp.asarray(window),
                    jnp.int32(p0))
            self._state_wrote(p0, k + 1, k + 1)
            preds = np.asarray(preds_dev)[0]  # (k+1,) int32
            accepted = 0
            while accepted < k and window[0, accepted + 1] == preds[accepted]:
                accepted += 1
            # every verified position's argmax is a true greedy token: the
            # `accepted` matching proposals plus the model's own next token
            emit = [int(t) for t in preds[:accepted + 1]]
            base = len(out)
            out.extend(emit)
            # the window's first `accepted+1` fed tokens are now part of
            # the sequence; rows written beyond that are dead (never
            # attended: the causal mask reads s_idx <= pos)
            self.pos = p0 + accepted + 1
            cur = emit[-1]
            for j, t in enumerate(emit):
                yield t
                if t in eos_ids or base + j + 1 >= steps:
                    del out[base + j + 1:]
                    self.pos = p0 + j + 1
                    return
        # tail: plain single-token steps when the window no longer fits
        while len(out) < steps and self.pos < self.seq_len:
            logits, _ = self.decode_one(cur)
            cur = int(np.asarray(logits)[0].argmax())
            out.append(cur)
            yield cur
            if cur in eos_ids:
                break

    @_closes_request
    def generate(self, prompt_tokens: list[int], steps: int, sampler: Sampler,
                 eos_ids: tuple[int, ...] = (), prefill_single_token: bool = False):
        """Yield ``(token_id, stats)`` for up to ``steps`` generated tokens.

        Mirrors the reference generate loop (dllama.cpp:17-93): prompt
        tokens are consumed first (emitted with their stats but not
        sampled), then sampled tokens stream out until ``steps`` tokens
        total, seq_len, or an EOS id.  ``prefill_single_token=True``
        reproduces the reference's token-at-a-time prefill for parity
        testing.
        """
        steps = min(steps, self.seq_len - self.pos)
        produced = 0
        if prefill_single_token:
            logits = None
            for t in prompt_tokens:
                logits, stats = self.decode_one(t)
                produced += 1
                yield t, stats
                if produced >= steps:
                    return
        else:
            logits, stats = self.prefill(prompt_tokens[:])
            produced += len(prompt_tokens)
            for i, t in enumerate(prompt_tokens):
                yield t, stats if i == len(prompt_tokens) - 1 else StepStats()
            if produced >= steps:
                return

        token = int(sampler.sample(logits[0]))
        stats = StepStats()  # prefill cost already attributed above
        while True:
            yield token, stats
            produced += 1
            if produced >= steps or self.pos >= self.seq_len or token in eos_ids:
                return
            logits, stats = self.decode_one(token)
            token = int(sampler.sample(logits[0]))


class SlotDispatch:
    """Completion handle for one in-flight :meth:`Engine.slot_step_async`
    dispatch.

    ``tokens_dev`` is the (steps, B) sampled-id future; ``last_dev`` the
    (B,) final sampled row, kept device-resident so the next pure-decode
    dispatch can consume it via ``feed_dev`` without any host transfer.
    ``fresh`` reports whether this dispatch minted a new XLA executable —
    the scheduler uses it to keep trace+compile walls out of its
    step-time EMA.  ``wait()`` is the blocking edge (idempotent): it runs
    :meth:`Engine._sync` (fault point + step watchdog), feeds the compile
    histogram on a fresh executable, stamps the engine's
    ``last_slot_dispatch_ms``, and returns the tokens as one host array —
    the single device→host transfer a dispatch pays.
    """

    __slots__ = ("_engine", "tokens_dev", "last_dev", "t", "steps",
                 "fresh", "enqueued_at", "ready_at", "_out")

    def __init__(self, engine, tokens_dev, last_dev, *, t: int, steps: int,
                 fresh: bool, enqueued_at: float):
        self._engine = engine
        self.tokens_dev = tokens_dev
        self.last_dev = last_dev
        self.t = t
        self.steps = steps
        self.fresh = fresh
        self.enqueued_at = enqueued_at  # perf_counter at enqueue
        self.ready_at: float | None = None
        self._out: np.ndarray | None = None

    def wait(self) -> np.ndarray:
        """Block until the dispatch lands; returns the (steps, B) ids."""
        if self._out is not None:
            return self._out
        eng = self._engine
        eng._sync(self.tokens_dev, "slot step")
        t1 = time.perf_counter()
        self.ready_at = t1
        if self.fresh:  # first call blocked through trace + compile
            obs_metrics.ENGINE_COMPILE_S.observe(t1 - self.enqueued_at)
        # enqueue→ready span, read by the scheduler's slot timeline
        # (obs/flight.py); for an overlapped dispatch it includes the
        # predecessor still executing, so it bounds device time from above
        eng.last_slot_dispatch_ms = (t1 - self.enqueued_at) * 1e3
        self._out = np.asarray(self.tokens_dev)  # (steps, B)
        return self._out


class SlotVerifyDispatch:
    """Completion handle for one in-flight
    :meth:`Engine.slot_verify_async` dispatch.

    ``preds_dev`` (B, T) holds the model's prediction at every fed
    position, ``accepted_dev`` (B,) the per-row count of leading drafts
    that matched, and ``last_dev`` (B,) the verified next token
    (``preds[r, accepted[r]]``) kept device-resident for onward feeding.
    ``wait()`` mirrors :class:`SlotDispatch.wait` — fault point + step
    watchdog via :meth:`Engine._sync`, compile-histogram feed on a fresh
    executable, ``last_slot_dispatch_ms`` — and returns
    ``(preds, accepted)`` as host arrays in one boundary crossing.
    """

    __slots__ = ("_engine", "preds_dev", "accepted_dev", "last_dev", "t",
                 "fresh", "enqueued_at", "ready_at", "_out")

    def __init__(self, engine, preds_dev, accepted_dev, last_dev, *,
                 t: int, fresh: bool, enqueued_at: float):
        self._engine = engine
        self.preds_dev = preds_dev
        self.accepted_dev = accepted_dev
        self.last_dev = last_dev
        self.t = t
        self.fresh = fresh
        self.enqueued_at = enqueued_at  # perf_counter at enqueue
        self.ready_at: float | None = None
        self._out: tuple[np.ndarray, np.ndarray] | None = None

    def wait(self) -> tuple[np.ndarray, np.ndarray]:
        """Block until the verify lands; returns ``(preds (B, T),
        accepted (B,))`` as host int32 arrays."""
        if self._out is not None:
            return self._out
        eng = self._engine
        eng._sync(self.preds_dev, "slot verify")
        t1 = time.perf_counter()
        self.ready_at = t1
        if self.fresh:  # first call blocked through trace + compile
            obs_metrics.ENGINE_COMPILE_S.observe(t1 - self.enqueued_at)
        eng.last_slot_dispatch_ms = (t1 - self.enqueued_at) * 1e3
        self._out = (np.asarray(self.preds_dev),
                     np.asarray(self.accepted_dev))
        return self._out
