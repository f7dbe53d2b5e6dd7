"""Analytic roofline cost model for the dispatch ledger.

The ledger (:mod:`.dispatch`) records *which* kernel path every dispatch
took; this module says what each dispatch *cost* — FLOPs and bytes moved
— from nothing but the model config and the dispatch shape, so the
accounting adds zero work to the hot path (no device counters, no
profiler).  The scheduler calls :meth:`CostModel.dispatch_cost` once per
landed dispatch and:

* bumps ``dllama_dispatch_flops_total`` / ``dllama_dispatch_bytes_total``
  ``{codec, path, phase}`` through the ledger seam
  (:func:`.dispatch.record_cost`),
* pro-rates chip-time and FLOPs across the occupied rows into each
  request's flight-record cost block and
  ``dllama_class_chip_ms_total{class}``,
* feeds :data:`TRACKER`, whose achieved FLOP/s / bytes-per-s divided by
  the per-backend peak table give the ``dllama_mfu`` / ``dllama_mbu``
  gauges.

The model is deliberately *simple enough to hand-check* (tests pin it
token by token for the tiny config) and is documented in docs/PERF.md:

* matmul FLOPs: ``2 * tokens * params_touched`` over the seven per-layer
  projections (wq/wk/wv/wo, w1/w2/w3) plus the logits head for every
  sampled/verified position.  Norms, rotary and elementwise work are
  excluded (<<1%).
* attention FLOPs: ``4 * dim * ctx`` per query token per layer (QK^T
  plus the weighted value sum).
* weight bytes: the packed size of every matmul weight — Q40 18 B /
  Q80 34 B per 32-weight block, dense ``itemsize`` per weight — read
  ONCE per forward pass (a decode burst of ``steps`` sequential
  single-token passes reads them ``steps`` times; that is exactly the
  batching-amortization story the roofline exists to show).
* KV bytes: per-position write + context read per layer; the int8 codec
  counts 1 B values plus the per-(head, position) f32 scale planes;
  paged reads round context up to page granularity (pages move whole).
* TP ring bytes: ``2 * (tp-1) * elems * 4`` aggregate hop bytes per
  all-reduce, two all-reduces (o-proj, w2) per layer per token.  Ring
  bytes ride their own ``tp-ring`` ledger path and are *excluded* from
  MBU (interconnect, not HBM).

Import contract: stdlib-only at module import, like every ``obs``
module.  numpy is imported lazily inside the CPU microbenchmark and the
engine adapter, which only run where the runtime already did.
"""

from __future__ import annotations

import os
import threading
import time

# Q40/Q80 packed-block geometry (dllama_tpu.quants; duplicated here as
# plain ints so importing obs never pulls numpy).
_BLOCK = 32
_CODEC_BLOCK_BYTES = {"q40": 18, "q8": 34}

#: per-device peaks, matched by substring of the lowercased jax
#: ``device_kind`` — (dense bf16 FLOP/s, HBM bytes/s).  v2/v3 entries are
#: per *core* (one jax device); v4+ are per chip (megacore).
TPU_PEAKS = (
    ("v6e", 918e12, 1640e9),
    ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 61.25e12, 450e9),
    ("v2", 22.5e12, 300e9),
)

_lock = threading.Lock()
_device_kind: str | None = None
_platform: str | None = None
_peaks_cache: dict | None = None
_cpu_measured: tuple[float, float] | None = None


def set_backend(device_kind: str | None, platform: str | None) -> None:
    """Bind the accelerator identity the peak lookup keys on (called by
    the runtime once it knows its devices; obs itself never imports jax).
    """
    global _device_kind, _platform, _peaks_cache
    with _lock:
        _device_kind = device_kind
        _platform = platform
        _peaks_cache = None


def _measure_cpu_peaks() -> tuple[float, float]:
    """Measured-once CPU fallback: a small f32 GEMM for FLOP/s and a big
    array copy for memory bytes/s.  Crude (one shape, one trial kept),
    but it anchors MFU/MBU to *this* host instead of pretending a CPU
    has TPU peaks.  Override with DLLAMA_PEAK_FLOPS / DLLAMA_PEAK_BYTES_S
    when determinism matters (tests do)."""
    global _cpu_measured
    if _cpu_measured is not None:
        return _cpu_measured
    import numpy as np
    n = 384
    a = np.random.default_rng(0).standard_normal((n, n), np.float32)
    b = a.T.copy()
    a @ b  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        a @ b
        best = min(best, time.perf_counter() - t0)
    flops = 2 * n ** 3 / max(best, 1e-9)
    buf = np.zeros(32 << 20, np.uint8)
    t0 = time.perf_counter()
    buf.copy()
    dt = max(time.perf_counter() - t0, 1e-9)
    bps = 2.0 * buf.nbytes / dt  # one read + one write stream
    _cpu_measured = (flops, bps)
    return _cpu_measured


def peaks() -> dict:
    """``{"flops", "bytes_per_s", "source", "device"}`` for the bound
    backend — env override first, then the TPU table, then the CPU
    microbenchmark; all-``None`` peaks when nothing matched (gauges stay
    0 rather than lying)."""
    global _peaks_cache
    with _lock:
        if _peaks_cache is not None:
            return _peaks_cache
        kind, platform = _device_kind, _platform
    env_f = os.environ.get("DLLAMA_PEAK_FLOPS")
    env_b = os.environ.get("DLLAMA_PEAK_BYTES_S")
    out = None
    if env_f or env_b:
        out = {"flops": float(env_f) if env_f else None,
               "bytes_per_s": float(env_b) if env_b else None,
               "source": "env", "device": kind or platform}
    elif kind:
        lk = kind.lower()
        for sub, fl, bp in TPU_PEAKS:
            if sub in lk:
                out = {"flops": fl, "bytes_per_s": bp,
                       "source": "table", "device": kind}
                break
    if out is None and platform == "cpu":
        try:
            fl, bp = _measure_cpu_peaks()
            out = {"flops": fl, "bytes_per_s": bp,
                   "source": "measured", "device": kind or "cpu"}
        except Exception:  # numpy missing / sandboxed — stay peakless
            out = None
    if out is None:
        out = {"flops": None, "bytes_per_s": None,
               "source": "none", "device": kind or platform}
    with _lock:
        _peaks_cache = out
    return out


class PerfTracker:
    """Cumulative achieved work over cumulative dispatch wall, the
    denominators MFU/MBU need.  ``wall_ms`` is the full dispatch wall
    (the chip is busy for the whole lockstep step, padding included), so
    padding and short batches show up as lower utilization — which is
    the point."""

    def __init__(self):
        self._lock = threading.Lock()
        self.flops = 0.0
        self.hbm_bytes = 0.0
        self.wall_ms = 0.0

    def note(self, flops: float, hbm_bytes: float, wall_ms: float) -> None:
        with self._lock:
            self.flops += flops
            self.hbm_bytes += hbm_bytes
            self.wall_ms += wall_ms

    def _util(self, achieved: float, peak: float | None) -> float | None:
        with self._lock:
            wall_s = self.wall_ms / 1e3
        if not peak or wall_s <= 0:
            return None
        return achieved / wall_s / peak

    def mfu(self) -> float | None:
        with self._lock:
            f = self.flops
        return self._util(f, peaks()["flops"])

    def mbu(self) -> float | None:
        with self._lock:
            b = self.hbm_bytes
        return self._util(b, peaks()["bytes_per_s"])

    def snapshot(self) -> dict:
        with self._lock:
            out = {"flops_total": self.flops,
                   "hbm_bytes_total": self.hbm_bytes,
                   "chip_wall_ms": round(self.wall_ms, 3)}
        out["mfu"] = self.mfu()
        out["mbu"] = self.mbu()
        return out

    def reset(self) -> None:
        with self._lock:
            self.flops = self.hbm_bytes = 0.0
            self.wall_ms = 0.0


#: process-global tracker behind the dllama_mfu / dllama_mbu gauges
TRACKER = PerfTracker()


def summary() -> dict:
    """The ``/health`` perf block: utilization, cumulative work, and the
    peak table entry it was divided by."""
    out = TRACKER.snapshot()
    out["peaks"] = peaks()
    try:
        from . import metrics as obs_metrics
        out["chip_ms_by_class"] = obs_metrics.CLASS_CHIP_MS.json_value()
    except Exception:
        out["chip_ms_by_class"] = {}
    return out


class CostModel:
    """FLOPs/bytes for one llama-family model at one serving config.

    Pure integer arithmetic per row (the tests hand-count it); only the
    dispatch-level weight-read split across phases divides.  ``rows``
    passed to :meth:`dispatch_cost` are ``(phase, pos, n_new)`` tuples —
    ``phase`` in {"prefill", "decode", "verify"}, ``pos`` the row's cache
    clock at enqueue, ``n_new`` the *useful* tokens it advanced (chunk
    width, burst steps, or 1 + drafts)."""

    def __init__(self, *, dim: int, hidden_dim: int, n_layers: int,
                 n_heads: int, n_kv_heads: int, vocab_size: int,
                 weight_codec: str = "dense", weight_el_bytes: int = 2,
                 kv_codec: str = "kv_f32", kv_el_bytes: int = 4,
                 tp: int = 1, paged: bool = False, page_size: int = 0,
                 n_experts: int = 0, n_active_experts: int = 0,
                 fused: bool = False, n_dense_layers: int = 0,
                 moe_hidden_dim: int = 0, n_shared_experts: int = 0,
                 mla: dict | None = None, head_dim: int = 0,
                 window: int = 0, window_period: int = 0, n_loops: int = 1,
                 ssm: dict | None = None, n_ssm_layers: int | None = None):
        self.dim = dim
        self.hidden_dim = hidden_dim
        #: a looped model runs its ``n_layers`` weight sets ``n_loops`` times a
        #: token: every weight is streamed, every block computed and a position
        #: cached (a plane a (pass, layer)) that many times.  ``n_layers`` is
        #: from here on the blocks a token runs; the weight sets are
        #: ``n_layers // n_loops``
        self.n_loops = max(1, int(n_loops))
        n_layers = n_layers * self.n_loops
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.vocab_size = vocab_size
        self.head_size = head_dim or dim // n_heads
        self.kv_dim = self.head_size * n_kv_heads
        q_dim = self.head_size * n_heads  # dim unless the head size is stated
        #: a windowed model (SmallThinker): layer l is full iff l % period == 0,
        #: the others see the last ``window`` positions
        self.window = int(window or 0)
        self.n_window_layers = (n_layers - n_layers // window_period
                                if self.window else 0)
        self.weight_codec = weight_codec
        self.weight_el_bytes = weight_el_bytes
        self.kv_codec = kv_codec
        self.kv_el_bytes = kv_el_bytes
        self.tp = max(1, int(tp))
        self.paged = paged
        self.page_size = int(page_size or 0)
        #: paged attention runs the fused page-walk Pallas kernel (one
        #: attention-family dispatch at every step width; same
        #: FLOPs/bytes, different family so MFU/MBU attribution matches
        #: the ledger path)
        self.fused = bool(fused)
        self.moe = n_experts > 0
        self.n_active_experts = n_active_experts

        ffn = 3 * dim * hidden_dim  # w1 + w2 + w3
        if self.moe:
            ffn *= n_active_experts
        attn = 2 * dim * q_dim + 2 * dim * self.kv_dim  # wq+wo, wk+wv
        #: values one cached position holds in one layer, and FLOPs of one
        #: (query, context) pair in one layer (QK^T + weighted V sum)
        self.kv_values = 2 * self.kv_dim
        self.pair_flops = 4 * q_dim
        #: latent attention (MLA): ``mla`` holds q_lora_rank, kv_lora_rank,
        #: qk_nope_head_dim, qk_rope_head_dim, v_head_dim.  A cached position
        #: is one latent and one rotated key for all heads, and a pair costs
        #: the absorbed form's two products over them (W_kvb's absorb is in
        #: the per-token matmuls)
        self.mla = mla
        if mla:
            r, dr = mla["kv_lora_rank"], mla["qk_rope_head_dim"]
            dn, dv, ql = (mla["qk_nope_head_dim"], mla["v_head_dim"],
                          mla["q_lora_rank"])
            attn = (dim * ql + ql * n_heads * (dn + dr) + dim * (r + dr)
                    + r * n_heads * (dn + dv) + n_heads * dv * dim)
            self.kv_values = r + dr
            self.pair_flops = 2 * n_heads * (2 * r + dr)
        #: a state-space mixer beside attention in every block (Falcon-H1):
        #: ``ssm`` holds heads, head_dim, state, groups and ring (the recent
        #: positions a row keeps out of its state).  Its two projections join
        #: the matmuls; a row's forward pass reads its state matrix once a
        #: layer, ``heads * state * head_dim`` float32, and its rings, whatever
        #: the context's depth (``state_read_bytes``), and computes ``C^T S``
        #: and the ring's attention form (``state_flops``): depth-free, where
        #: the same block's keys and values are not
        self.ssm = ssm
        #: ``n_ssm_layers`` (Granite): the mixer is a layer KIND, so that many
        #: layers have its projections and state and the OTHERS attention's
        #: projections, keys and values; ``None``: every layer has both
        self.n_ssm_layers = (n_layers if n_ssm_layers is None else n_ssm_layers
                             ) if ssm else 0
        self.n_kv_layers = n_layers if n_ssm_layers is None else (
            n_layers - n_ssm_layers)
        mixer = 0
        if ssm:
            inner = ssm["heads"] * ssm["head_dim"]
            bc = 2 * ssm["groups"] * ssm["state"]
            mixer = dim * (2 * inner + bc + ssm["heads"]) + inner * dim
        if (n_dense_layers or moe_hidden_dim) and self.moe:
            # two layer kinds: a dense FFN in the leading layers, and in the
            # rest the token's routed experts plus the shared expert
            fe = moe_hidden_dim or hidden_dim
            moe_ffn = 3 * dim * fe * (n_active_experts + n_shared_experts)
            ffns = (n_dense_layers * 3 * dim * hidden_dim
                    + (n_layers - n_dense_layers) * moe_ffn)
        else:
            ffns = n_layers * ffn
        #: matmul weights touched per token (logits head separate)
        self.params_per_token = (self.n_kv_layers * attn
                                 + self.n_ssm_layers * mixer + ffns)

    # --- building blocks (all return ints) -------------------------------

    def codec_bytes(self, n_params: int) -> int:
        """Stored bytes of ``n_params`` matmul weights under the weight
        codec (Q40 18 B per 32, Q80 34 B per 32, dense itemsize each)."""
        bb = _CODEC_BLOCK_BYTES.get(self.weight_codec)
        if bb is not None:
            return n_params // _BLOCK * bb
        return n_params * self.weight_el_bytes

    def weight_read_bytes(self) -> int:
        """Bytes to stream the full matmul weight set (incl. the logits
        head) through the chip once — one forward pass."""
        return (self.codec_bytes(self.params_per_token)
                + self.codec_bytes(self.dim * self.vocab_size))

    def matmul_flops(self, tokens: int) -> int:
        return 2 * tokens * self.params_per_token

    def logit_flops(self, n_positions: int) -> int:
        return 2 * n_positions * self.dim * self.vocab_size

    @staticmethod
    def _ctx_sum(pos: int, n_new: int) -> int:
        # sum of context lengths seen by the n_new query tokens:
        # (pos+1) + (pos+2) + ... + (pos+n_new)
        return n_new * pos + n_new * (n_new + 1) // 2

    def attn_flops(self, pos: int, n_new: int) -> int:
        """QK^T + weighted V sum: 4 * dim MACs -> FLOPs per (query,
        context) pair, per layer."""
        full = self.n_kv_layers - self.n_window_layers
        seen = full * self._ctx_sum(pos, n_new) + self.n_window_layers * sum(
            min(pos + j + 1, self.window) for j in range(
                n_new if self.n_window_layers else 0))
        return self.pair_flops * seen

    def kv_pos_bytes(self) -> int:
        """Bytes one (k, v) position occupies in one layer."""
        if self.kv_codec == "kv_int8":
            # 1 B values + per-(head, position) f32 scale planes
            return 2 * (self.kv_dim + 4 * self.n_kv_heads)
        return self.kv_values * self.kv_el_bytes

    def kv_write_bytes(self, n_new: int) -> int:
        return n_new * self.n_kv_layers * self.kv_pos_bytes()

    def _read_positions(self, pos: int, n_new: int, burst: bool,
                        window: int = 0) -> int:
        def paged_up(c: int) -> int:
            if window:  # a window layer reads its window and the block's rows
                c = min(c, window + (0 if burst else n_new - 1))
            if self.paged and self.page_size:
                return -(-c // self.page_size) * self.page_size
            return c
        if burst:
            # steps sequential single-token passes, each re-reading its
            # full context
            return sum(paged_up(pos + j + 1) for j in range(n_new))
        # one block forward over n_new tokens streams the final context
        return paged_up(pos + n_new)

    def kv_read_bytes(self, pos: int, n_new: int, burst: bool) -> int:
        full = self.n_kv_layers - self.n_window_layers
        positions = full * self._read_positions(pos, n_new, burst)
        if self.n_window_layers:
            positions += self.n_window_layers * self._read_positions(
                pos, n_new, burst, self.window)
        return positions * self.kv_pos_bytes()

    def state_read_bytes(self, passes: int) -> int:
        """Bytes ``passes`` forward passes of one row read of a state-space
        mixer's state and rings, all layers (0: the model has none)."""
        z = self.ssm
        if not z:
            return 0
        state = z["heads"] * z["state"] * z["head_dim"] * 4
        ring = z["ring"] * ((z["heads"] * z["head_dim"] + z["groups"]
                             * z["state"]) * self.kv_el_bytes + z["heads"] * 4)
        return passes * self.n_ssm_layers * (state + ring)

    def state_flops(self, n_new: int) -> int:
        """Multiply-adds x 2 of the same for ``n_new`` query tokens: each
        head's ``C`` against its state, and its scores and values over the
        ring."""
        z = self.ssm
        if not z:
            return 0
        per_head = z["state"] * z["head_dim"] + z["ring"] * (
            z["state"] + z["head_dim"])
        return 2 * n_new * self.n_ssm_layers * z["heads"] * per_head

    def ring_bytes(self, tokens: int) -> int:
        """Aggregate TP ring all-reduce hop bytes: two f32 reduces of
        ``dim`` per layer per token, ``2*(tp-1)`` hop copies per
        element across the ring."""
        if self.tp <= 1:
            return 0
        return tokens * self.n_layers * 2 * (2 * (self.tp - 1)) * self.dim * 4

    # --- per-dispatch assembly -------------------------------------------

    def row_cost(self, phase: str, pos: int, n_new: int) -> dict:
        """One row's own work (weight reads EXCLUDED — they are shared
        per pass and split at dispatch level)."""
        burst = phase == "decode"
        n_logits = 1 if phase == "prefill" else n_new
        attn = self.attn_flops(pos, n_new) + self.state_flops(n_new)
        flops = self.matmul_flops(n_new) + self.logit_flops(n_logits) + attn
        kv = (self.kv_write_bytes(n_new)
              + self.kv_read_bytes(pos, n_new, burst)
              + self.state_read_bytes(n_new if burst else 1))
        return {"phase": phase, "flops": flops, "kv_bytes": kv,
                "attn_flops": attn,
                "ring_bytes": self.ring_bytes(n_new)}

    def attn_path(self, phase: str) -> str:
        if self.mla:
            return "mla-absorbed"
        if not self.paged:
            return "attention"
        if self.fused:
            return "paged-fused"
        return "paged-decode" if phase == "decode" else "paged-gather"

    def dispatch_cost(self, rows, steps: int = 1) -> dict:
        """Cost of one landed dispatch.

        ``rows``: ``(phase, pos, n_new)`` per occupied row; ``steps``:
        forward passes the dispatch ran (a decode burst re-reads weights
        every pass — callers pass the burst length, 1 otherwise).

        Returns ``{"entries": {(codec, path, phase): {"flops", "bytes"}},
        "per_row": [...], "flops": total, "hbm_bytes": total-minus-ring}``.
        """
        rows = [(p, int(pos), int(n)) for p, pos, n in rows]
        n_rows = max(1, len(rows))
        passes = max(1, int(steps))
        w_read = self.weight_read_bytes() * passes
        entries: dict[tuple, dict] = {}

        def bump(codec, path, phase, flops=0, nbytes=0):
            e = entries.setdefault((codec, path, phase),
                                   {"flops": 0, "bytes": 0})
            e["flops"] += flops
            e["bytes"] += nbytes

        per_row = []
        for phase, pos, n_new in rows:
            rc = self.row_cost(phase, pos, n_new)
            w_share = w_read / n_rows
            bump(self.weight_codec, "matmul", phase,
                 flops=rc["flops"] - rc["attn_flops"], nbytes=w_share)
            bump(self.kv_codec, self.attn_path(phase), phase,
                 flops=rc["attn_flops"], nbytes=rc["kv_bytes"])
            if rc["ring_bytes"]:
                bump(self.weight_codec, "tp-ring", phase,
                     nbytes=rc["ring_bytes"])
            per_row.append({"phase": phase, "flops": rc["flops"],
                            "hbm_bytes": w_share + rc["kv_bytes"]})
        flops = sum(e["flops"] for e in entries.values())
        hbm = sum(e["bytes"] for (c, path, p), e in entries.items()
                  if path != "tp-ring")
        return {"entries": entries, "per_row": per_row,
                "flops": flops, "hbm_bytes": hbm}


def model_from_engine(engine) -> CostModel | None:
    """Build a CostModel from a live engine (weight codec sniffed from
    the placed params, KV codec from the cache planes) and bind the peak
    lookup to its devices.  Returns None rather than raise: cost
    accounting must never take serving down."""
    try:
        cfg = engine.cfg
        codec, el = "dense", 2
        vals = []
        for v in (engine.params or {}).values():
            vals.extend(v if isinstance(v, (list, tuple)) else [v])
        for v in vals:
            m = type(v).__module__ or ""
            if m.endswith(".q40"):
                codec = "q40"
                break
            if m.endswith(".q8"):
                codec = "q8"
                break
        else:
            import numpy as np
            for v in vals:
                if hasattr(v, "dtype") and hasattr(v, "ndim") \
                        and getattr(v, "ndim", 0) >= 2:
                    el = np.dtype(v.dtype).itemsize
                    break
        cache = engine.cache
        if getattr(cache, "quantized", False):
            kv_codec, kv_el = "kv_int8", 1
        else:
            import numpy as np
            kv_el = np.dtype(cache.k.dtype).itemsize
            kv_codec = f"kv_{np.dtype(cache.k.dtype).name}"
        try:
            dev = next(iter(engine.mesh.devices.flat))
            set_backend(getattr(dev, "device_kind", None),
                        getattr(dev, "platform", None))
        except Exception:
            pass
        fused = False
        if engine.paged and not cfg.is_mla:
            try:
                # ask the attention ladder what the decode trace will
                # actually pick for this geometry, so cost families
                # track the ledger path
                from ..ops import attention as _attn
                from ..parallel.mesh import active_mesh
                with active_mesh(engine.mesh):
                    fused, _ = _attn._fused_choice(
                        1, cfg.n_heads, cfg.n_kv_heads, cfg.head_size,
                        bool(engine.cache.quantized), engine.kv_page_size,
                        engine.max_pages_per_slot, cache.k.shape[-1])
            except Exception:
                fused = False
        return CostModel(
            dim=cfg.dim, hidden_dim=cfg.hidden_dim, n_layers=cfg.n_layers,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            vocab_size=cfg.vocab_size, weight_codec=codec,
            weight_el_bytes=el, kv_codec=kv_codec, kv_el_bytes=kv_el,
            tp=engine.mesh.shape.get("tp", 1), paged=bool(engine.paged),
            page_size=getattr(engine, "kv_page_size", 0) or 0,
            n_experts=getattr(cfg, "n_experts", 0) or 0,
            # of a token's k routed experts, those held here on average (all
            # k unless the file is a share of an expert-parallel deployment)
            n_active_experts=max(1, round(
                cfg.n_active_experts * cfg.n_experts_held / cfg.n_experts))
            if cfg.is_moe else 0,
            fused=fused, n_dense_layers=cfg.n_dense_layers,
            moe_hidden_dim=cfg.moe_hidden_dim,
            n_shared_experts=cfg.n_shared_experts,
            mla=dict(q_lora_rank=cfg.q_lora_rank,
                     kv_lora_rank=cfg.kv_lora_rank,
                     qk_nope_head_dim=cfg.qk_nope_head_dim,
                     qk_rope_head_dim=cfg.qk_rope_head_dim,
                     v_head_dim=cfg.v_head_dim) if cfg.is_mla else None,
            head_dim=cfg.head_dim, window=cfg.window,
            window_period=cfg.window_period, n_loops=cfg.n_loops,
            ssm=dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                     state=cfg.ssm_state, groups=cfg.ssm_groups,
                     ring=engine.cache.rk.shape[3]) if cfg.has_ssm else None,
            n_ssm_layers=cfg.n_ssm_layers if cfg.has_ssm and cfg.periodic
            else None)
    except Exception:
        return None


def reset() -> None:
    """Test isolation: clear the tracker and cached backend peaks."""
    global _peaks_cache
    TRACKER.reset()
    with _lock:
        _peaks_cache = None
