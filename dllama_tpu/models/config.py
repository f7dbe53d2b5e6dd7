"""Model configuration.

Bridges the on-disk ``ModelSpec`` (`.m` header, transformer.cpp:12-125) to
the runtime: adds compute dtype and derives the per-arch structural flags
that the reference encodes as three separate hand-built task lists
(`buildLlamaArch` llama2-tasks.cpp:241-298, `buildGrok1Arch`
grok1-tasks.cpp:275-354, `buildMixtralArch` mixtral-tasks.cpp:5-78), and
those of OLMoE (`ARCH_OLMOE`, beyond the reference).  DeepSeek-V2
(`ARCH_DEEPSEEK2`) states its sizes in the header (`io/mfile.py EXT_KEYS`) and
they are fields here, not properties of the id; so are SmallThinker's
(`ARCH_SMALLTHINKER`) head size, sliding window and layer period, and
K-EXAONE's (`ARCH_EXAONE_MOE`) share of the experts and full layer's place;
that arch's per-head q/k norm and sigmoid router follow from its id.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax.numpy as jnp

from ..io import mfile

# Grok-1 scaling constants (grok1-tasks.cpp:13, :272)
GROK_EMBEDDING_SCALE = 78.38367176906169
GROK_LOGIT_SCALE = 0.5773502691896257
# the float32 product of all experts over a prefill call's rows may take this
# much (``ModelConfig.prefill_chunk``)
PREFILL_PRODUCT_BYTES = 512 << 20


@dataclass(frozen=True)
class ModelConfig:
    arch: int
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    n_experts: int
    n_active_experts: int
    vocab_size: int
    seq_len: int
    hidden_act: int
    rope_theta: float
    dtype: jnp.dtype = jnp.float32
    # matmul implementation for Q40-quantized weights: "pallas" (fused
    # kernel, single-chip), "xla" (partitionable emulation, used under TP
    # sharding and on CPU), or "auto" (pallas on TPU for decode-sized
    # inputs, xla otherwise).  Static so each choice compiles its own
    # program.
    quant_impl: str = "auto"
    # static flag set by the engine for a from-scratch prefill on an sp>1
    # mesh: attention runs blockwise ring attention over the fresh
    # sequence-sharded q/k/v (ops/sp_attention.py) instead of the
    # cache-reading one-round combine — O(T/sp) activation memory
    ring_prefill: bool = False
    # ---- ARCH_DEEPSEEK2 (header keys 14..31); 0 / 1.0 = the arch has none
    q_lora_rank: int = 0
    kv_lora_rank: int = 0           # > 0: latent attention (MLA)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_hidden_dim: int = 0         # one routed expert's width (else hidden_dim)
    n_shared_experts: int = 0       # the shared expert is this many experts wide
    n_groups: int = 0               # > 1: experts chosen by group
    topk_groups: int = 0
    n_dense_layers: int = 0         # leading layers with a dense FFN
    routed_scale: float = 1.0
    rope_factor: float = 1.0        # > 1: YaRN frequencies
    rope_orig_seq_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    norm_eps: float = 1e-5
    # ---- ARCH_SMALLTHINKER (header keys 32..34); 0 = the arch has none
    head_dim: int = 0               # a head's size where it is not dim / n_heads
    window: int = 0                 # > 0: sliding-window layers see this many keys
    window_period: int = 0          # layer l is full and unrotated iff l % period == window_full_at
    # ---- ARCH_EXAONE_MOE (header keys 35..37); 0 = the arch has none
    experts_held: int = 0           # routed experts a layer holds planes for (0: all)
    first_expert: int = 0           # the router's index of the first held expert
    window_full_at: int = 0         # the full layer's place in a period
    # ---- ARCH_LFM2_MOE (header key 38); 0 = the arch has none
    conv_taps: int = 0              # > 0: a period's other layers are short convolutions
    # ---- ARCH_BRUMBY (header key 39); 0 = the arch has none
    retention_degree: int = 0       # > 0: every layer is power retention of this degree
    # ---- ARCH_OURO (header key 40); 0 = the arch has none
    loops: int = 0                  # > 0: the whole stack runs this many times (n_loops)
    # ---- ARCH_FALCON_H1 (header keys 41..60); 0 / 1.0 = the arch has none
    # > 0: Mamba-2 mixers, beside attention in every block, or (with a
    # ``window_period``) as the other layers of a period (Granite)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0              # rows of a head's state matrix (mamba_d_state)
    ssm_groups: int = 0             # heads / groups share one B and one C
    ssm_conv: int = 0               # taps of the mixer's causal depthwise convolution
    # the muP multipliers, scalars of the published config, each applied where
    # ``_hybrid_mixers`` / ``run_blocks`` / ``_head`` say
    mup_embedding: float = 1.0
    mup_head: float = 1.0
    mup_attn_in: float = 1.0
    mup_attn_out: float = 1.0
    mup_ssm_in: float = 1.0
    mup_ssm_out: float = 1.0
    mup_key: float = 1.0
    mup_gate: float = 1.0
    mup_down: float = 1.0
    mup_z: float = 1.0              # ssm_multipliers, in the order of W_in's split
    mup_x: float = 1.0
    mup_b: float = 1.0
    mup_c: float = 1.0
    mup_dt: float = 1.0

    @property
    def cache_kinds(self) -> tuple:
        """The kinds of cache the model leaves behind, rows of
        ``models/cache_kinds.py``, which say what each may do."""
        from . import cache_kinds as kinds
        if self.retention_degree:
            return (kinds.RETENTION,)
        first = (kinds.LATENT if self.is_mla
                 else kinds.LOOPED if self.n_loops > 1 else kinds.FULL)
        beside = (kinds.WINDOW if self.window else kinds.CONV if self.conv_taps
                  else kinds.SSM if self.has_ssm else None)
        return (first, beside) if beside else (first,)

    @property
    def has_ssm(self) -> bool:
        """Some layer runs a Mamba-2 state-space mixer (``ops/ssm.py``): beside
        its attention in every block (Falcon-H1: one layer of one row owns keys
        and values, pages on a paged engine, AND a state matrix a head with its
        rings), or as the other layers of a period (Granite: a layer owns a
        state OR keys and values)."""
        return self.ssm_heads > 0

    @property
    def n_ssm_layers(self) -> int:
        """Layers that keep a mixer's state: how deep its planes are."""
        if not self.has_ssm:
            return 0
        return self.n_layers - self.n_full_layers if self.periodic else self.n_layers

    @property
    def ssm_inner(self) -> int:
        """``mamba_d_ssm``: the mixer's heads times their size."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_channels(self) -> int:
        """Channels of the mixer's convolution: ``x | B | C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def folds_state(self) -> bool:
        """Some layer keeps a state matrix that lags the position clock behind
        a watermark (``ops/retention.py watermark``): retention's, or a
        state-space mixer's."""
        return self.retention_degree > 0 or self.has_ssm

    @property
    def n_loops(self) -> int:
        """Passes of the whole stack of ``n_layers`` weight sets over its own
        output, the final norm closing each (a looped model, Ouro); 1 for every
        other arch.  A token therefore runs ``n_layers * n_loops`` blocks."""
        return self.loops or 1

    @property
    def n_cache_planes(self) -> int:
        """Leading axis of the cache of keys and values: a plane a (pass,
        layer), plane ``u * n_layers + l`` for pass ``u`` of layer ``l``, since a
        pass attends over what the same pass of the same layer wrote at the
        earlier positions.  ``n_layers`` wherever the stack runs once."""
        return self.n_layers * self.n_loops

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.head_size * self.n_heads

    @property
    def periodic(self) -> bool:
        """The layers come in periods of ``window_period`` of which one, at
        ``window_full_at``, is full attention (``models/windowed.py``): the
        others are sliding-window layers (``window``), gated short
        convolutions (``conv_taps``) or state-space mixers (``ssm_heads``)."""
        return self.window > 0 or self.conv_taps > 0 or (
            self.ssm_heads > 0 and self.window_period > 0)

    @property
    def kind_stacked(self) -> bool:
        """A period's other layers are another operator with weights of its own
        (convolutions, mixers): each operator's weights are stacked over the
        layers of its kind (``params.ATT_KIND_KEYS`` / ``CONV_KEYS`` /
        ``MIXER_KEYS``, indexed by ``windowed.kind_index``)."""
        return self.periodic and not self.window

    @property
    def n_full_layers(self) -> int:
        """Layers that cache every position (all of them in a model without
        periods)."""
        return self.n_layers // self.window_period if self.periodic else self.n_layers

    @property
    def n_window_layers(self) -> int:
        return self.n_layers - self.n_full_layers if self.window else 0

    @property
    def n_conv_layers(self) -> int:
        """Layers that keep a convolution state and no keys or values."""
        return self.n_layers - self.n_full_layers if self.conv_taps else 0

    @property
    def full_rotates(self) -> bool:
        """A period's full-attention layer carries rotate-half RoPE (LFM2).  In
        a windowed model it is unrotated (NoPE) and the window layers rotate;
        Granite rotates nothing anywhere (``position_embedding_type`` "nope"):
        its other layers keep a state, as LFM2's, and its full layer is
        position-free, as a windowed model's."""
        return self.arch == mfile.ARCH_LFM2_MOE

    @property
    def router_norm_eps(self) -> float:
        """Added to the sum of the chosen scores before a sigmoid router's
        weights are divided by it: LFM2's ``+ 1e-6``; 0 where the arch has
        none (K-EXAONE)."""
        return 1e-6 if self.arch == mfile.ARCH_LFM2_MOE else 0.0

    @property
    def attention_free(self) -> bool:
        """No layer has keys and values to keep: every layer is power
        retention (``ops/retention.py``), whose state is a matrix a kv head and
        a short ring of recent positions, a fixed size a sequence whatever the
        context's depth.  Such a model has no paged layer: no pages, a cached
        token costs no bytes, and a slot engine admits by slot alone."""
        return self.retention_degree > 0

    @property
    def keeps_state(self) -> bool:
        """Some layer leaves behind a state that is not a row of keys and
        values a position (a convolution's ring of ``z``, a retention layer's
        matrix): the engines keep account of how far the position clock may be
        moved back over it (``runtime/engine.py``, the pos-rewind invariant)."""
        return self.conv_taps > 0 or self.folds_state

    @property
    def n_experts_held(self) -> int:
        """Routed experts a layer holds planes for: all ``n_experts`` unless
        the file is one chip's share of an expert-parallel deployment (the
        router has ``n_experts`` outputs either way)."""
        return self.experts_held or self.n_experts

    def prefill_chunk(self) -> int:
        """Rows of one prefill call: the largest power of two whose float32
        ``(experts held, rows, dim)`` product (``moe_ffn``'s all-experts
        strategy; one expert's for a dense model) stays under
        ``PREFILL_PRODUCT_BYTES``.  512 at 64 experts of 2560, 1024 at 16 held
        of 6144; a prompt up to one chunk takes one call."""
        rows = PREFILL_PRODUCT_BYTES // (4 * max(self.n_experts_held, 1) * self.dim)
        rows = max(16, 1 << (max(rows, 1).bit_length() - 1))
        if self.folds_state:
            # a call's rows all enter the ring of recent positions, and what
            # it folds lies wholly before them (ops/retention.py)
            from ..ops import retention
            rows = min(rows, retention.MAX_ROWS)
        return rows

    def window_ring(self, seq_len: int) -> int:
        """Positions a window layer's contiguous cache holds a row: the window
        plus one prefill chunk (a call's rows are written before they are
        read), or all of ``seq_len`` where that is no more."""
        return min(seq_len, self.window + self.prefill_chunk())

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """What MLA caches of a token in a layer: the normed latent and the
        one rotated key all heads share (512 + 64 for DeepSeek-V2)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def expert_dim(self) -> int:
        return self.moe_hidden_dim or self.hidden_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    @property
    def kv_values_per_token(self) -> int:
        """Values one cached token occupies in one layer."""
        return self.latent_dim if self.is_mla else 2 * self.kv_dim

    @property
    def attn_scale(self) -> float:
        """MLA's softmax scale: ``qk_head_dim^-1/2 * mscale^2`` with YaRN's
        ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``."""
        scale = self.qk_head_dim ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
            scale *= m * m
        return scale

    @property
    def kv_dim(self) -> int:
        return self.head_size * self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def rope_interleaved(self) -> bool:
        """Llama uses adjacent-pair RoPE; Grok-1/Mixtral use the rotate-half
        ("Falcon") convention (transformer.cpp:227-231); a DeepSeek-V2 file
        keeps the published rows, whose rotated part is in adjacent pairs."""
        return self.arch in (mfile.ARCH_LLAMA, mfile.ARCH_DEEPSEEK2)

    @property
    def add_bos(self) -> bool:
        """Whether prompts get a BOS token (reference: dllama.cpp:27 —
        Grok-1 prompts are encoded without BOS; chat mode always adds it)."""
        return self.arch != mfile.ARCH_GROK1

    @property
    def embedding_scale(self) -> float:
        if self.arch == mfile.ARCH_GROK1:
            return GROK_EMBEDDING_SCALE
        return self.mup_embedding

    @property
    def logit_scale(self) -> float:
        return GROK_LOGIT_SCALE if self.arch == mfile.ARCH_GROK1 else self.mup_head

    @property
    def post_block_norms(self) -> bool:
        """Grok-1 normalizes each sub-block's *output* before the residual
        add (grokRmfFfnNorm / grokMoeRmsNormFinal, grok1-tasks.cpp:16-41,
        :245-263); Llama/Mixtral add raw outputs to the residual.  Ouro's
        "sandwich" norms are the same four vectors a layer, around a dense
        FFN."""
        return self.arch in (mfile.ARCH_GROK1, mfile.ARCH_OURO)

    @property
    def qk_head_norm(self) -> bool:
        """K-EXAONE RMS-normalises each head of q and of k over its own
        ``head_size`` values, one weight vector of that size each a layer
        (``q_norm`` / ``k_norm``), before RoPE; so does LFM2 in its attention
        layers, and Brumby (Qwen3's) in every layer."""
        return self.arch in (mfile.ARCH_EXAONE_MOE, mfile.ARCH_LFM2_MOE,
                             mfile.ARCH_BRUMBY)

    @property
    def router_sigmoid(self) -> bool:
        """K-EXAONE's router (DeepSeek-V3's): sigmoid scores, a per-expert
        bias added for the choice only (``router_bias``), the chosen scores
        normalised to sum to 1 (LFM2: over their sum ``+ router_norm_eps``)
        and scaled by ``routed_scale``."""
        return self.arch in (mfile.ARCH_EXAONE_MOE, mfile.ARCH_LFM2_MOE)

    @property
    def ffn_by_segment(self) -> bool:
        """K-EXAONE's and LFM2's files: a dense FFN in the leading layers and
        experts after them, each stacked over its own segment."""
        return self.arch in (mfile.ARCH_EXAONE_MOE, mfile.ARCH_LFM2_MOE,
                             mfile.ARCH_GRANITE_HYBRID)

    @property
    def router_reads_input(self) -> bool:
        """SmallThinker's router reads the layer's input as it arrives, before
        the attention norm, and not the FFN's normed input."""
        return self.arch == mfile.ARCH_SMALLTHINKER

    @property
    def qk_norm(self) -> bool:
        """OLMoE RMS-normalises the whole q and the whole k projection (one
        weight vector each, ``layers.{i}.q_norm`` / ``k_norm``) before the
        split into heads and before RoPE; no other arch has the vectors."""
        return self.arch == mfile.ARCH_OLMOE

    @property
    def norm_topk_prob(self) -> bool:
        """Mixtral and Grok-1 renormalise the chosen experts' probabilities
        to sum to 1 (grok1-tasks.cpp:60-114); OLMoE (``norm_topk_prob:
        false``) uses them as the softmax over all experts gave them, and so
        does DeepSeek-V2, times ``routed_scale``."""
        return self.arch not in (mfile.ARCH_OLMOE, mfile.ARCH_DEEPSEEK2)

    @classmethod
    def from_spec(cls, spec: mfile.ModelSpec, dtype=jnp.float32) -> "ModelConfig":
        return cls(
            arch=spec.arch, dim=spec.dim, hidden_dim=spec.hidden_dim,
            n_layers=spec.n_layers, n_heads=spec.n_heads,
            n_kv_heads=spec.n_kv_heads, n_experts=spec.n_experts,
            n_active_experts=spec.n_active_experts, vocab_size=spec.vocab_size,
            seq_len=spec.seq_len, hidden_act=spec.hidden_act,
            rope_theta=spec.rope_theta, dtype=dtype,
            # key 60 is rope_theta again as a float: read_spec folded it in
            **{name: getattr(spec, name)
               for _, name, _ in mfile.ALL_EXT_KEYS if name != "rope_theta_f32"})

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


def tiny_config(arch=mfile.ARCH_LLAMA, *, dim=64, hidden_dim=96, n_layers=2,
                n_heads=4, n_kv_heads=2, n_experts=0, n_active_experts=0,
                vocab_size=128, seq_len=64, hidden_act=mfile.ACT_SILU,
                rope_theta=10000.0, dtype=jnp.float32, **ext) -> ModelConfig:
    """Small config for tests — the analogue of the reference's hand-sized
    test fixtures (llama2-tasks-test.cpp:528-554)."""
    return ModelConfig(arch=arch, dim=dim, hidden_dim=hidden_dim,
                       n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
                       n_experts=n_experts, n_active_experts=n_active_experts,
                       vocab_size=vocab_size, seq_len=seq_len,
                       hidden_act=hidden_act, rope_theta=rope_theta, dtype=dtype,
                       **ext)


def tiny_smallthinker(**kw) -> ModelConfig:
    """SmallThinker at a toy size that keeps every ratio: periods of one full
    and three window layers, a window shorter than the tests' sequences, 7
    query heads a kv head, a head size that is not dim / n_heads, 64 experts
    of which 6 a token, ReLU."""
    base = dict(arch=mfile.ARCH_SMALLTHINKER, dim=96, hidden_dim=32,
                n_layers=8, n_heads=28, n_kv_heads=4, n_experts=64,
                n_active_experts=6, vocab_size=128, seq_len=96,
                hidden_act=mfile.ACT_RELU, rope_theta=1.5e6, norm_eps=1e-6,
                head_dim=8, window=16, window_period=4)
    base.update(kw)
    return tiny_config(**base)


def tiny_exaone_moe(**kw) -> ModelConfig:
    """K-EXAONE at a toy size that keeps every ratio: periods of three window
    layers and then a full one, a window shorter than the tests' sequences, 8
    query heads a kv head, a head size that is not dim / n_heads, a dense first
    layer, 32 experts of which 8 a token and 4 held here (the second of eight
    shares), one shared expert, a routed scale."""
    base = dict(arch=mfile.ARCH_EXAONE_MOE, dim=64, hidden_dim=96, n_layers=8,
                n_heads=16, n_kv_heads=2, n_experts=32, n_active_experts=8,
                vocab_size=128, seq_len=96, rope_theta=1e6, norm_eps=1e-5,
                head_dim=8, window=16, window_period=4, window_full_at=3,
                moe_hidden_dim=32, n_shared_experts=1, n_groups=1,
                topk_groups=1, n_dense_layers=1, routed_scale=2.5,
                experts_held=4, first_expert=4)
    base.update(kw)
    return tiny_config(**base)


def tiny_lfm2_moe(**kw) -> ModelConfig:
    """LFM2 at a toy size that keeps every ratio: periods ``conv, conv,
    attention, conv``, two leading dense layers of their own width, 3 taps, 4
    query heads a kv head, a head size that is dim / n_heads stated in the
    header, 16 experts of which 4 a token, no shared expert, routed scale 1."""
    base = dict(arch=mfile.ARCH_LFM2_MOE, dim=64, hidden_dim=96, n_layers=8,
                n_heads=8, n_kv_heads=2, n_experts=16, n_active_experts=4,
                vocab_size=128, seq_len=128, rope_theta=1e6, norm_eps=1e-5,
                head_dim=8, window_period=4, window_full_at=2, conv_taps=3,
                moe_hidden_dim=32, n_dense_layers=2, routed_scale=1.0)
    base.update(kw)
    return tiny_config(**base)


def tiny_brumby(**kw) -> ModelConfig:
    """Brumby at a toy size that keeps every ratio: 5 query heads a kv head, a
    head of 16 (eight blocks of 2 in the symmetric square, as 128 has eight of
    16), degree 2, four layers all alike, an untied head, eps 1e-6."""
    base = dict(arch=mfile.ARCH_BRUMBY, dim=160, hidden_dim=224, n_layers=4,
                n_heads=10, n_kv_heads=2, vocab_size=128, seq_len=512,
                rope_theta=1e6, norm_eps=1e-6, retention_degree=2)
    base.update(kw)
    return tiny_config(**base)


def tiny_falcon_h1(**kw) -> ModelConfig:
    """Falcon-H1 at a toy size that keeps every ratio: five query heads a kv
    head at a head size that is not dim / n_heads, a mixer of 4 heads of 16 in
    two groups with a state of 24 rows (not the head size), 4 taps, an odd
    ``W_in`` width (64 + 160 + 4 = 228), every multiplier off 1, an untied
    head, three blocks all alike."""
    base = dict(arch=mfile.ARCH_FALCON_H1, dim=64, hidden_dim=96, n_layers=3,
                n_heads=10, n_kv_heads=2, vocab_size=128, seq_len=512,
                rope_theta=1e11, norm_eps=1e-5, head_dim=16, ssm_heads=4,
                ssm_head_dim=16, ssm_state=24, ssm_groups=2, ssm_conv=4,
                mup_embedding=5.66, mup_head=0.25, mup_attn_in=0.9,
                mup_attn_out=0.6, mup_ssm_in=0.5, mup_ssm_out=0.8,
                mup_key=0.7, mup_gate=0.6, mup_down=0.45, mup_z=0.7,
                mup_x=1.5, mup_b=1.4, mup_c=1.3, mup_dt=0.7)
    base.update(kw)
    return tiny_config(**base)


def tiny_granite_hybrid(**kw) -> ModelConfig:
    """Granite-4.0-H at a toy size that keeps every ratio: periods of four
    mixer layers and one attention layer (at 2) without positions, two periods,
    4 query heads a kv head, a mixer of 8 heads of 8 in ONE group with a state
    of 12 rows (not the head size), 4 taps, 12 experts of which 3 a token
    beside a shared MLP twice an expert's width in every layer, every
    multiplier it has off 1 (the key's is ``attention_multiplier * sqrt(head)``;
    ``residual_multiplier`` stands on each branch's output: ``mup_attn_out``,
    ``mup_ssm_out`` and, on the experts' and the shared MLP's sum,
    ``mup_down``)."""
    base = dict(arch=mfile.ARCH_GRANITE_HYBRID, dim=64, hidden_dim=64,
                n_layers=10, n_heads=8, n_kv_heads=2, n_experts=12,
                n_active_experts=3, vocab_size=128, seq_len=512,
                norm_eps=1e-5, head_dim=8, window_period=5, window_full_at=2,
                moe_hidden_dim=32, n_shared_experts=2, ssm_heads=8,
                ssm_head_dim=8, ssm_state=12, ssm_groups=1, ssm_conv=4,
                mup_embedding=3.0, mup_head=0.25, mup_key=0.3 * 8 ** 0.5,
                mup_attn_out=0.5, mup_ssm_out=0.5, mup_down=0.5)
    base.update(kw)
    return tiny_config(**base)


def tiny_ouro(**kw) -> ModelConfig:
    """Ouro at a toy size: three layers run three times (so that the pass
    count, the layer count and 1 are three numbers), as many kv heads as query
    heads, sandwich norms, an untied head, eps 1e-6."""
    base = dict(arch=mfile.ARCH_OURO, dim=64, hidden_dim=96, n_layers=3,
                n_heads=4, n_kv_heads=4, vocab_size=128, seq_len=64,
                rope_theta=1e6, norm_eps=1e-6, loops=3)
    base.update(kw)
    return tiny_config(**base)


def tiny_deepseek2(**kw) -> ModelConfig:
    """DeepSeek-V2 at a toy size that keeps every ratio: a dense prefix and
    expert layers, 8 groups of which 3 are kept, top-6, two shared experts, a
    rotated part narrower than the head, YaRN past its original length."""
    base = dict(arch=mfile.ARCH_DEEPSEEK2, dim=64, hidden_dim=96, n_layers=3,
                n_heads=4, n_kv_heads=4, n_experts=32, n_active_experts=6,
                vocab_size=128, seq_len=64, q_lora_rank=64, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                moe_hidden_dim=32, n_shared_experts=2, n_groups=8,
                topk_groups=3, n_dense_layers=1, routed_scale=16.0,
                rope_factor=40.0, rope_orig_seq_len=16, rope_beta_fast=32.0,
                rope_beta_slow=1.0, rope_mscale=0.707,
                rope_mscale_all_dim=0.707, norm_eps=1e-6)
    base.update(kw)
    return tiny_config(**base)
