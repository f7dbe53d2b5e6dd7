"""Parameter pytrees: random init (tests) and `.m`-file loading.

Weights are stored **input-dim-first** (``x @ w``) and **layer-stacked**
(leading ``n_layers`` axis) so the whole transformer body runs as one
``lax.scan`` — one compiled block program regardless of depth, instead of
the reference's 25·nLayers-entry static task list (tasks.cpp:36-42).

The `.m` file stores each matmul row-major ``(d_out, n_in)``
(transformer.cpp:428-487 walk order); the loader dequantizes and transposes
once on host.  Sharding happens at device placement (parallel/sharding.py),
which replaces the reference's ``splitWeights`` + socket streaming
(transformer.cpp:389-404).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..io import mfile
from ..obs import memory as obs_memory, metrics as obs_metrics, \
    trace as obs_trace
from ..ops import q40, q8
from .config import ModelConfig

Params = dict  # pytree: str -> array (numpy until placed) | q40.QTensor


# DeepSeek-V2's stacks by the layers they cover: attention and block norms
# all L, the dense FFN the leading ``n_dense_layers``, router / experts /
# shared expert the rest.  A stack's leading index is a layer's index WITHIN
# its segment (models/transformer.py run_blocks).
MLA_ATT_KEYS = ("wq_a", "wkv_a", "wqkv_a", "q_a_norm", "wq_b", "kv_a_norm",
                "wkv_b", "wo", "rms_att", "rms_ffn")
DENSE_FFN_KEYS = ("w1", "w2", "w3", "w13")
MOE_FFN_KEYS = ("router", "router_bias", "up", "gate", "down", "shared_w1",
                "shared_w2", "shared_w3", "shared_w13")
# LFM2's stacks by layer KIND: a convolution layer's three tensors over the
# convolution layers, the attention tensors over the attention layers alone (a
# stack's leading index is ``windowed.kind_index``); the block norms stay over
# all L
CONV_KEYS = ("conv_in", "conv_taps", "conv_out")
ATT_KIND_KEYS = ("wq", "wk", "wv", "wqkv", "wo", "q_norm", "k_norm")
# a Falcon-H1 mixer's tensors that stay float32 whatever the weights' type (the
# ``dt`` projection sets the state's decay; the rest are vectors)
SSM_F32 = ("ssm_dt", "ssm_conv_w", "ssm_conv_b", "ssm_a_log", "ssm_dt_bias",
           "ssm_d", "ssm_norm")
# Granite's stacks by layer kind: a mixer layer's tensors over the mixer layers
# (Falcon-H1's, where every block has one, are over all L)
MIXER_KEYS = ("ssm_in", "ssm_out") + SSM_F32


def _mla_param_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    L, D, V, H = cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.n_heads
    Ld, Le = cfg.n_dense_layers, cfg.n_moe_layers
    r, ql = cfg.kv_lora_rank, cfg.q_lora_rank
    shapes = {
        "embedding": (V, D),
        "wq_a": (L, D, ql), "q_a_norm": (L, ql),
        "wq_b": (L, ql, H * cfg.qk_head_dim),
        "wkv_a": (L, D, cfg.latent_dim), "kv_a_norm": (L, r),
        # kept whole: a head's columns are its k_nope then its v
        "wkv_b": (L, r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (L, H * cfg.v_head_dim, D),
        "rms_att": (L, D), "rms_ffn": (L, D),
        "rms_final": (D,), "wcls": (D, V),
    }
    if Ld:
        F = cfg.hidden_dim
        shapes.update({"w1": (Ld, D, F), "w2": (Ld, F, D), "w3": (Ld, D, F)})
    if Le:
        E, F = cfg.n_experts, cfg.expert_dim
        shapes.update({"router": (Le, D, E), "up": (Le, E, D, F),
                       "gate": (Le, E, D, F), "down": (Le, E, F, D)})
        if cfg.n_shared_experts:
            Fs = F * cfg.n_shared_experts
            shapes.update({"shared_w1": (Le, D, Fs), "shared_w2": (Le, Fs, D),
                           "shared_w3": (Le, D, Fs)})
    return shapes


def _exaone_param_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """K-EXAONE's stacks: attention and norms over all layers, the dense FFN
    over the leading ``n_dense_layers``, router / held experts / shared expert
    over the rest (a stack's leading index is a layer's index within its
    segment, as DeepSeek-V2's).  The router has ``n_experts`` columns; the
    expert stacks have ``n_experts_held`` planes a layer.  LFM2's are the same
    with the attention stacks over its attention layers alone and the
    convolution's (``CONV_KEYS``) over the others; Granite's likewise with the
    mixer's (``MIXER_KEYS``), no head norms and no router bias."""
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    Ld, Le, Dh = cfg.n_dense_layers, cfg.n_moe_layers, cfg.head_size
    E, H, F = cfg.n_experts, cfg.n_experts_held, cfg.expert_dim
    Fs = F * cfg.n_shared_experts
    La = cfg.n_full_layers if cfg.kind_stacked else L
    Lc = cfg.n_conv_layers
    # in the order the seeded draws of ``init_params`` have always taken them
    shapes = {
        "embedding": (V, D),
        "wq": (La, D, cfg.q_dim), "wk": (La, D, cfg.kv_dim),
        "wv": (La, D, cfg.kv_dim), "wo": (La, cfg.q_dim, D),
        "q_norm": (La, Dh), "k_norm": (La, Dh),
        "rms_att": (L, D), "rms_ffn": (L, D),
        "rms_final": (D,), "wcls": (D, V),
        "router": (Le, D, E), "router_bias": (Le, E),
        "up": (Le, H, D, F), "gate": (Le, H, D, F), "down": (Le, H, F, D),
    }
    if not cfg.qk_head_norm:
        del shapes["q_norm"], shapes["k_norm"]
    if not cfg.router_sigmoid:
        del shapes["router_bias"]
    if Lc:
        shapes.update({"conv_in": (Lc, D, 3 * D), "conv_taps": (Lc, D, cfg.conv_taps),
                       "conv_out": (Lc, D, D)})
    if cfg.has_ssm:
        shapes.update(_mixer_shapes(cfg))
    if Ld:
        Fd = cfg.hidden_dim
        shapes.update({"w1": (Ld, D, Fd), "w2": (Ld, Fd, D), "w3": (Ld, D, Fd)})
    if Fs:
        shapes.update({"shared_w1": (Le, D, Fs), "shared_w2": (Le, Fs, D),
                       "shared_w3": (Le, D, Fs)})
    return shapes


def _mixer_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """A state-space mixer's stacks, over the layers that have one
    (io/mfile.py _ssm_tensors)."""
    L, D, H, C = cfg.n_ssm_layers, cfg.dim, cfg.ssm_heads, cfg.ssm_channels
    return {"ssm_in": (L, D, cfg.ssm_inner + C), "ssm_dt": (L, D, H),
            "ssm_conv_w": (L, C, cfg.ssm_conv), "ssm_conv_b": (L, C),
            "ssm_a_log": (L, H), "ssm_dt_bias": (L, H),
            "ssm_d": (L, H), "ssm_norm": (L, cfg.ssm_inner),
            "ssm_out": (L, cfg.ssm_inner, D)}


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    if cfg.is_mla:
        return _mla_param_shapes(cfg)
    if cfg.ffn_by_segment:
        return _exaone_param_shapes(cfg)
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.hidden_dim, cfg.vocab_size
    Hq = cfg.n_heads * cfg.head_size       # == D
    Hkv = cfg.n_kv_heads * cfg.head_size   # == kv_dim
    E = cfg.n_experts
    shapes = {
        "embedding": (V, D),
        "wq": (L, D, Hq),
        "wk": (L, D, Hkv),
        "wv": (L, D, Hkv),
        "wo": (L, Hq, D),
        "rms_att": (L, D),
        "rms_ffn": (L, D),
        "rms_final": (D,),
        "wcls": (D, V),
    }
    if cfg.qk_norm:
        shapes.update({"q_norm": (L, Hq), "k_norm": (L, Hkv)})
    if cfg.retention_degree:
        shapes.update({"wg": (L, D, cfg.n_kv_heads), "q_norm": (L, cfg.head_size),
                       "k_norm": (L, cfg.head_size)})
    if cfg.has_ssm:  # the mixer beside attention
        shapes.update(_mixer_shapes(cfg))
    if cfg.is_moe:
        shapes.update({
            "router": (L, D, E),
            "up": (L, E, D, F),
            "gate": (L, E, D, F),
            "down": (L, E, F, D),
        })
    else:
        shapes.update({"w1": (L, D, F), "w2": (L, F, D), "w3": (L, D, F)})
    if cfg.post_block_norms:  # Grok-1's and Ouro's extra norms
        shapes.update({"rms_moe": (L, D), "rms_ffn2": (L, D)})
    return shapes


def init_params(cfg: ModelConfig, seed: int = 0, scale: float = 0.02) -> Params:
    """Deterministic random params — the analogue of the reference's xorshift
    weight fixtures (llama2-tasks-test.cpp:556-562)."""
    rng = np.random.RandomState(seed)
    params: Params = {}
    for name, shape in param_shapes(cfg).items():
        norm = name.startswith("rms") or name.endswith("_norm")
        if norm:
            x = np.ones(shape, dtype=np.float32)
        else:
            x = (rng.standard_normal(shape) * scale).astype(np.float32)
        if name == "conv_taps":  # O(1) taps: the state matters to the logits
            x = (0.5 + 0.5 * rng.standard_normal(shape)).astype(np.float32)
        if name in ("ssm_conv_w", "ssm_d"):
            x = (0.5 + 0.5 * rng.standard_normal(shape)).astype(np.float32)
        if name == "ssm_a_log":  # A = -exp(.) in 0.5..2, dt in 0.01..0.1: a
            x = np.log(rng.uniform(0.5, 2.0, shape)).astype(np.float32)
        if name == "ssm_dt_bias":  # state hundreds of positions deep matters
            x = np.log(np.expm1(rng.uniform(0.01, 0.1, shape))).astype(np.float32)
        f32 = norm or name in SSM_F32 + ("router_bias", "conv_taps", "wg")
        params[name] = jnp.asarray(x, dtype=jnp.float32 if f32 else cfg.dtype)
    return params


def _stack(mf: mfile.MFile, names: list[str], transpose: bool, dtype) -> np.ndarray:
    mats = []
    for name in names:
        t = mf.tensor(name)
        if transpose:
            t = np.ascontiguousarray(t.T)
        mats.append(t)
    return np.stack(mats).astype(dtype)


def _stack_q(mf: mfile.MFile, names: list[str | list[str]], codec=q40):
    """Layer-stack quantized tensors straight from their packed file bytes —
    the weights never touch f32 on host (the reference likewise keeps Q40
    end-to-end on its production path, funcs.cpp:287-386); the repack is a
    byte transpose per tensor (native csrc/q40pack.cpp when built).

    ``codec`` is ``ops.q40`` or ``ops.q8`` — the reference dispatches its
    matmul on the weight file type (funcs.cpp:414-455) and so does the
    loader here.

    An inner list of names concatenates those tensors' output dims into one
    fused weight (e.g. q+k+v), which halves-again the fused kernel's launch
    count per layer."""
    def entry(name):
        t = mf.info(name)
        d = int(np.prod(t.shape[:-1]))
        return (mf.raw(name), d, t.shape[-1])

    groups = [[entry(g) for g in ([name] if isinstance(name, str) else name)]
              for name in names]
    return codec.pack_file_groups(groups)


def quantize_matmuls(params: Params, cfg: ModelConfig,
                     fuse: bool = True) -> Params:
    """Convert the dense matmul weights of a params pytree to packed Q40
    (host-side).  Used by benchmarks/tests to exercise the quantized path
    from randomly-initialized params.  MoE expert stacks quantize too
    (``(L, E, n, d)`` → blocks along the input axis, the reference keeps
    experts Q40 end-to-end, transformer.cpp:299-317); the router and the
    embedding stay dense.

    ``fuse=True`` additionally concatenates q/k/v (and w1/w3) output dims
    into single ``wqkv``/``w13`` tensors — see load_params."""
    out = dict(params)
    if cfg.is_mla:
        return _quantize_mla(out, fuse)
    if cfg.ffn_by_segment:
        # two FFN kinds beside Llama's attention: _quantize_mla's key list
        # covers them (absent keys are skipped), after the q/k/v join
        if fuse:
            out["wqkv"] = q40.quantize(np.concatenate(
                [np.asarray(out.pop(k), np.float32) for k in ("wq", "wk", "wv")],
                axis=-1))
        else:
            for k in ("wq", "wk", "wv"):
                out[k] = q40.quantize(np.asarray(out[k], np.float32))
        return _quantize_mla(out, fuse)
    if fuse:
        out["wqkv"] = q40.quantize(np.concatenate(
            [np.asarray(params[k], np.float32) for k in ("wq", "wk", "wv")], axis=-1))
        del out["wq"], out["wk"], out["wv"]
        keys = ["wo", "wcls"]
        if cfg.has_ssm:
            keys += ["ssm_in", "ssm_out"]
        if not cfg.is_moe:
            out["w13"] = q40.quantize(np.concatenate(
                [np.asarray(params[k], np.float32) for k in ("w1", "w3")], axis=-1))
            del out["w1"], out["w3"]
            keys.append("w2")
    else:
        keys = ["wq", "wk", "wv", "wo", "wcls"]
        if cfg.has_ssm:
            keys += ["ssm_in", "ssm_out"]
        if not cfg.is_moe:
            keys += ["w1", "w2", "w3"]
    if cfg.is_moe:
        keys += ["up", "gate", "down"]
    for k in keys:
        out[k] = q40.quantize(np.asarray(params[k], np.float32))
    return out


def _quantize_mla(out: Params, fuse: bool) -> Params:
    """:func:`quantize_matmuls` for a DeepSeek-V2 pytree: every matrix packs
    but ``wkv_b`` (the absorbed form multiplies it head by head, so it stays
    dense) and the router; ``fuse`` joins the two down-projections from x
    (``wqkv_a``) and each SwiGLU's gate and up."""
    def f32(k):
        return np.asarray(out.pop(k), np.float32)

    if fuse:
        if "wq_a" in out:
            out["wqkv_a"] = q40.quantize(np.concatenate([f32("wq_a"), f32("wkv_a")], -1))
        for pre in ("w", "shared_w"):
            if pre + "1" in out:
                out[pre + "13"] = q40.quantize(
                    np.concatenate([f32(pre + "1"), f32(pre + "3")], -1))
    for k in ("wq_a", "wkv_a", "wq_b", "wo", "wcls", "w1", "w2", "w3", "up",
              "gate", "down", "shared_w1", "shared_w2", "shared_w3", "conv_in",
              "conv_out", "ssm_in", "ssm_out"):
        if k in out:
            out[k] = q40.quantize(np.asarray(out[k], np.float32))
    return out


def _stack_q_experts(mf: mfile.MFile, cfg: ModelConfig, fname: str, codec=q40,
                     layers: range | None = None):
    """Layer×expert-stacked packed expert weights (Q40 or Q80 ``codec``),
    filled tensor by tensor into preallocated host arrays — no f32
    materialization and no transient double-buffering, so host RAM transit
    is bounded by the packed size (~0.69 B/weight for Q40).  Replaces the
    dense f32 expert loading that made Mixtral-8x7B (~90 GB f32 transit)
    unloadable (VERDICT r01)."""
    layers = range(cfg.n_layers) if layers is None else layers
    L, E = len(layers), cfg.n_experts_held
    t0 = mf.info(f"layers.{layers[0]}.experts.0.{fname}")
    d = int(np.prod(t0.shape[:-1]))
    n = t0.shape[-1]
    np_ = codec.padded_n(n)
    qp = codec.alloc_value_plane((L, E), np_, d)
    cls = codec.Tensor
    sc = np.zeros((L, E, np_ // 32, d), np.float16)
    for l, layer in enumerate(layers):
        for e in range(E):
            codec.repack_file_bytes_into(
                mf.raw(f"layers.{layer}.experts.{e}.{fname}"), d, n, qp[l, e], sc[l, e])
    if not np.isfinite(sc).all():  # same loud-failure rule as pack_file_groups
        raise ValueError(f"{fname}: expert scale plane contains inf/NaN f16 "
                         "scales — corrupt or overflowed .m tensor")
    return cls(qp, sc.view(np.uint16), (n, d))


def load_params(mf: mfile.MFile, cfg: ModelConfig | None = None,
                dtype=None, keep_quantized: bool = False,
                fuse: bool = True) -> tuple[ModelConfig, Params]:
    """Load a `.m` file into the runtime layout.

    Mirrors ``Transformer::loadRoot`` (transformer.cpp:428-487) but instead
    of streaming slices to workers, produces **host (numpy) arrays** — the
    leaves of every ``QTensor``/``Q8Tensor`` and the dense tensors alike —
    that the engine places onto the mesh with shardings
    (``parallel/sharding.py place_params``): nothing is committed to a
    device here, so the upload happens once and each chip receives only
    its own shard, straight from host memory over PCIe instead of the
    reference's TCP star.  The host holds the file's stacks (about the
    packed model size) until the engine has placed them.

    ``keep_quantized=True`` keeps Q40/Q80 matmul weights packed for their
    fused dequant-matmuls (ops/q40.py, ops/q8.py — the reference likewise
    dispatches its matmul on the weight ftype, funcs.cpp:414-455).  Q40 is
    the production path (3.5× the decode bandwidth of dense bf16; Q80 is
    ~1.9×).  Norms, the embedding, and the router are dequantized either
    way; F16/F32 files always load dense.

    ``fuse=True`` concatenates q/k/v (and w1/w3) into single ``wqkv``/
    ``w13`` tensors on the quantized path — right for single-chip decode
    (fewer kernel launches); pass ``fuse=False`` under tp>1, where the
    concat axis would be shard-mixed and GSPMD would reshard every step.
    """
    if cfg is None:
        cfg = ModelConfig.from_spec(mf.spec)
    with obs_trace.span("engine.load_read", layers=cfg.n_layers,
                        total=obs_metrics.load_seconds("read")) as sp:
        params = _read_params(mf, cfg, dtype, keep_quantized, fuse)
        # the host's side of the memory account: the resident set with the
        # host stacks built (host_rss_bytes{phase="read"})
        sp.update(rss=obs_memory.ACCOUNT.rss("read"))
        return cfg, params


def _read_params(mf: mfile.MFile, cfg: ModelConfig, dtype,
                 keep_quantized: bool, fuse: bool) -> Params:
    if dtype is None:
        dtype = cfg.dtype
    np_dtype = np.dtype(jnp.dtype(dtype).name) if dtype != jnp.bfloat16 else jnp.bfloat16
    ftype = mf.spec.weights_ftype
    quant = keep_quantized and ftype in (mfile.quants.Q40, mfile.quants.Q80)
    codec = q40 if ftype == mfile.quants.Q40 else q8
    L = cfg.n_layers
    p: Params = {}
    p["embedding"] = mf.tensor("token_embedding").astype(np_dtype)
    if cfg.is_mla or cfg.ffn_by_segment:
        read = _read_mla_layers if cfg.is_mla else _read_exaone_layers
        read(mf, cfg, p, np_dtype, codec if quant else None, fuse)
        return _read_tail(mf, p, np_dtype, codec if quant else None)
    if quant and fuse:
        p["wqkv"] = _stack_q(
            mf, [[f"layers.{i}.wq", f"layers.{i}.wk", f"layers.{i}.wv"]
                 for i in range(L)], codec)
        p["wo"] = _stack_q(mf, [f"layers.{i}.wo" for i in range(L)], codec)
    elif quant:
        for key in ("wq", "wk", "wv", "wo"):
            p[key] = _stack_q(mf, [f"layers.{i}.{key}" for i in range(L)], codec)
    else:
        for key in ("wq", "wk", "wv", "wo"):
            p[key] = _stack(mf, [f"layers.{i}.{key}" for i in range(L)], True, np_dtype)
    p["rms_att"] = _stack(mf, [f"layers.{i}.rms_att" for i in range(L)], False, np.float32)
    p["rms_ffn"] = _stack(mf, [f"layers.{i}.rms_ffn" for i in range(L)], False, np.float32)
    if cfg.qk_norm or cfg.retention_degree:
        for key in ("q_norm", "k_norm"):
            p[key] = _stack(mf, [f"layers.{i}.{key}" for i in range(L)], False, np.float32)
    if cfg.retention_degree:  # the gate stays float32, whatever the weights' type
        p["wg"] = _stack(mf, [f"layers.{i}.wg" for i in range(L)], True, np.float32)
    if cfg.has_ssm:
        _Stacks(mf, p, np_dtype, codec if quant else None).mixers(cfg, range(L))
    if cfg.is_moe:
        p["router"] = _stack(mf, [f"layers.{i}.moe_router" for i in range(L)], True, np_dtype)
        if quant:
            for key in ("up", "gate", "down"):
                p[key] = _stack_q_experts(mf, cfg, key, codec)
        else:
            for key, fname in [("up", "up"), ("gate", "gate"), ("down", "down")]:
                per_layer = []
                for i in range(L):
                    mats = [np.ascontiguousarray(mf.tensor(f"layers.{i}.experts.{e}.{fname}").T)
                            for e in range(cfg.n_experts)]
                    per_layer.append(np.stack(mats))
                p[key] = np.stack(per_layer).astype(np_dtype)
    elif quant and fuse:
        p["w13"] = _stack_q(
            mf, [[f"layers.{i}.w1", f"layers.{i}.w3"] for i in range(L)], codec)
        p["w2"] = _stack_q(mf, [f"layers.{i}.w2" for i in range(L)], codec)
    elif quant:
        for key in ("w1", "w2", "w3"):
            p[key] = _stack_q(mf, [f"layers.{i}.{key}" for i in range(L)], codec)
    else:
        for key in ("w1", "w2", "w3"):
            p[key] = _stack(mf, [f"layers.{i}.{key}" for i in range(L)], True, np_dtype)
    if cfg.post_block_norms:
        for key in ("rms_moe", "rms_ffn2"):
            p[key] = _stack(mf, [f"layers.{i}.{key}" for i in range(L)], False, np.float32)
    return _read_tail(mf, p, np_dtype, codec if quant else None)


class _Stacks:
    """The three ways a segmented file's layer stacks are read into ``p``
    (``codec`` None: dense): matrices, joined matrices, float32 vectors."""

    def __init__(self, mf, p, np_dtype, codec):
        self.mf, self.p, self.np_dtype, self.codec = mf, p, np_dtype, codec

    def mats(self, keys, layers):
        for key in keys:
            fnames = [f"layers.{i}.{key}" for i in layers]
            self.p[key] = (_stack_q(self.mf, fnames, self.codec) if self.codec
                           else _stack(self.mf, fnames, True, self.np_dtype))

    def fused(self, key, parts, layers):
        self.p[key] = _stack_q(
            self.mf, [[f"layers.{i}.{a}" for a in parts] for i in layers],
            self.codec)

    def vecs(self, keys, layers, src=None):
        for key in keys:
            self.p[key] = _stack(
                self.mf, [f"layers.{i}.{src or key}" for i in layers], False,
                np.float32)

    def mixers(self, cfg, layers):
        """The state-space mixers of ``layers`` (``MIXER_KEYS``)."""
        self.mats(("ssm_in", "ssm_out"), layers)
        self.vecs(SSM_F32[1:], layers)
        self.p["ssm_dt"] = _stack(self.mf, [f"layers.{i}.ssm_dt" for i in layers],
                                  True, np.float32)
        self.p["ssm_conv_w"] = self.p["ssm_conv_w"].reshape(
            len(layers), cfg.ssm_channels, cfg.ssm_conv)


def _read_ffn_segments(mf: mfile.MFile, cfg: ModelConfig, p: Params, st: _Stacks,
                       join: bool) -> None:
    """The dense prefix's FFN and the expert layers' router, held experts and
    shared expert of a segmented file (DeepSeek-V2, K-EXAONE)."""
    dense = range(cfg.n_dense_layers)
    moe = range(cfg.n_dense_layers, cfg.n_layers)
    if len(dense):
        if join:
            st.fused("w13", ("w1", "w3"), dense)
            st.mats(("w2",), dense)
        else:
            st.mats(("w1", "w2", "w3"), dense)
    if not len(moe):
        return
    p["router"] = _stack(mf, [f"layers.{i}.moe_router" for i in moe], True,
                         st.np_dtype)
    for key in ("up", "gate", "down"):
        if st.codec:
            p[key] = _stack_q_experts(mf, cfg, key, st.codec, layers=moe)
        else:
            p[key] = np.stack([np.stack([
                np.ascontiguousarray(mf.tensor(f"layers.{i}.experts.{e}.{key}").T)
                for e in range(cfg.n_experts_held)]) for i in moe]).astype(st.np_dtype)
    if cfg.n_shared_experts:
        if join:
            st.fused("shared_w13", ("shared_w1", "shared_w3"), moe)
            st.mats(("shared_w2",), moe)
        else:
            st.mats(("shared_w1", "shared_w2", "shared_w3"), moe)


def _read_exaone_layers(mf: mfile.MFile, cfg: ModelConfig, p: Params, np_dtype,
                        codec, fuse: bool) -> None:
    """A K-EXAONE file's layer stacks into ``p`` (``codec`` None: dense): the
    attention of every layer with its two head norms, then the FFN segments;
    the router's choice bias stays float32.  An LFM2 file's: the attention of
    its attention layers, the convolution of the others (the taps float32,
    ``(Lc, dim, taps)``).  A Granite file's: the mixers of the others."""
    every = range(cfg.n_layers)
    att = [i for i in every if not cfg.kind_stacked
           or i % cfg.window_period == cfg.window_full_at]
    other = [i for i in every if i not in att]
    st = _Stacks(mf, p, np_dtype, codec)
    join = codec is not None and fuse
    if join:
        st.fused("wqkv", ("wq", "wk", "wv"), att)
        st.mats(("wo",), att)
    else:
        st.mats(("wq", "wk", "wv", "wo"), att)
    if cfg.qk_head_norm:
        st.vecs(("q_norm", "k_norm"), att)
    st.vecs(("rms_att", "rms_ffn"), every)
    if other and cfg.conv_taps:
        st.mats(("conv_in", "conv_out"), other)
        st.vecs(("conv_taps",), other)
        p["conv_taps"] = p["conv_taps"].reshape(len(other), cfg.dim, cfg.conv_taps)
    elif other:
        st.mixers(cfg, other)
    _read_ffn_segments(mf, cfg, p, st, join)
    if cfg.router_sigmoid:
        st.vecs(("router_bias",), range(cfg.n_dense_layers, cfg.n_layers),
                src="moe_router_bias")


def _read_mla_layers(mf: mfile.MFile, cfg: ModelConfig, p: Params, np_dtype,
                     codec, fuse: bool) -> None:
    """A DeepSeek-V2 file's layer stacks into ``p`` (``codec`` None: dense).
    ``wkv_b`` is dequantized once, here, whatever the file's type: the
    absorbed form multiplies it head by head, which no packed kernel does."""
    att = range(cfg.n_layers)
    st = _Stacks(mf, p, np_dtype, codec)
    mats, vecs = st.mats, st.vecs
    join = codec is not None and fuse
    if join:
        st.fused("wqkv_a", ("wq_a", "wkv_a"), att)
    else:
        mats(("wq_a", "wkv_a"), att)
    mats(("wq_b", "wo"), att)
    p["wkv_b"] = _stack(mf, [f"layers.{i}.wkv_b" for i in att], True, np_dtype)
    vecs(("q_a_norm", "kv_a_norm", "rms_att", "rms_ffn"), att)
    _read_ffn_segments(mf, cfg, p, st, join)


def _read_tail(mf: mfile.MFile, p: Params, np_dtype, codec) -> Params:
    """The final norm and the head, which every arch ends with."""
    quant = codec is not None
    p["rms_final"] = mf.tensor("rms_final").astype(np.float32)
    if quant:
        tw = mf.info("wcls")
        p["wcls"] = codec.pack_file_groups(
            [[(mf.raw("wcls"), int(np.prod(tw.shape[:-1])), tw.shape[-1])]],
            stacked=False)
    else:
        p["wcls"] = np.ascontiguousarray(mf.tensor("wcls").T).astype(np_dtype)
    return p
