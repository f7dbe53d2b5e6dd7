"""`.m` model-file format: reader + writer.

Byte-compatible with the reference's model format so that files produced by
the reference converters load directly:

* header parse mirrors ``Transformer::loadSpecFromFile``
  (/root/reference/src/transformer.cpp:12-125): magic ``0xA00ABCD``, an i32
  ``headerSize`` (total header bytes incl. magic+size), then (key, value)
  i32 pairs keyed by ``TransformerHeaderKey`` (transformer.hpp:10-25).
  Legacy magics ``0xABCD00``/``0xABCD01`` carry a fixed 9-int struct
  (transformer.cpp:27-42).
* tensor walk mirrors ``Transformer::loadRoot`` (transformer.cpp:428-487):
  embedding, then per layer q/k/v/wo, (olmoe: q_norm, k_norm), (router +
  per-expert up/gate/down | w1/w2/w3), rms_att, rms_ffn, (grok: rms_moe,
  rms_ffn2), then rms_final
  and wcls.  A DeepSeek-V2 file (``ARCH_DEEPSEEK2``) has its own attention
  tensors (``wq_a``, ``q_a_norm``, ``wq_b``, ``wkv_a``, ``kv_a_norm``,
  ``wkv_b`` kept whole, ``wo``), a dense FFN in its first
  ``n_dense_layers`` layers and router + experts + one shared expert
  (``shared_w1/w2/w3``) in the rest, and header keys 14..31 for the sizes no
  older arch has (floats as their IEEE-754 f32 bits).  A SmallThinker file
  (``ARCH_SMALLTHINKER``) has Mixtral's tensors at a query width of
  ``n_heads * head_dim`` (key 32) and keys 31, 33, 34 for the norm's epsilon,
  the sliding window and the layer period.  A K-EXAONE file
  (``ARCH_EXAONE_MOE``) has those keys, DeepSeek-V2's 19..24 (expert width,
  shared expert, groups, dense prefix, routed scale), and 35..37 for the share
  of the experts it holds and the full layer's place in a period; per layer a
  ``q_norm`` / ``k_norm`` of one head's size and, in an expert layer,
  ``moe_router_bias``.  An LFM2 file (``ARCH_LFM2_MOE``) has keys 19, 23, 24,
31, 32, 34, 37 and 38 (the convolution's taps): the layer at a period's place
``window_full_at`` is K-EXAONE's attention (``wq`` .. ``k_norm``) and every
other layer a gated short convolution (``conv_in`` (3 dim, dim), ``conv_taps``
(dim x taps values, f32, channel by channel), ``conv_out`` (dim, dim)); no
shared expert.  A Brumby file (``ARCH_BRUMBY``) has keys 31 and 39 (the
retention's degree) and Llama's layers with, after ``wo``, the gate ``wg``
(n_kv_heads, dim; f32: eight rows are no Q40 matrix) and a ``q_norm`` /
``k_norm`` of one head's size.  A Falcon-H1 file (``ARCH_FALCON_H1``) has keys
31, 32 and 41..60 (the state-space mixer's sizes, the muP multipliers as f32
bits, and ``rope_theta`` as a float: 1e11 passes an i32) and Llama's layers
with, after ``wo``, the mixer's tensors (:func:`_ssm_tensors`).  A Granite-4.0-H
file (``ARCH_GRANITE_HYBRID``) has keys 19, 20, 31, 32, 34, 37, 41..47, 49, 51,
52 and 54: LFM2's walk with the mixer's tensors where that has a convolution's, no head
norms, no router bias, and a shared MLP in every layer.  Matmul weights
are stored row-major
``(d_out, n_in)`` in the
  model's weight float type; norm weights and the embedding are F32
  (transformer.cpp:213-218, 266-278).

Reading is mmap-backed and lazy: ``MFile.tensor(name)`` dequantizes one
tensor on demand, so sharded loading can stream straight to device without
materializing the full f32 model on host.
"""

from __future__ import annotations

import mmap
import os
import struct
from dataclasses import dataclass

import numpy as np

from .. import quants
from .integrity import ArtifactError, load_manifest_for, verify_bytes

MAGIC_V2 = 0xA00ABCD
LEGACY_MAGICS = (0xABCD00, 0xABCD01)

# TransformerArchType (transformer.hpp:39-43)
ARCH_LLAMA = 0xABCD00
ARCH_GROK1 = 0xABCD01
ARCH_MIXTRAL = 0xABCD02
# beyond the reference's three: OLMoE (q/k RMSNorm over the whole projection,
# top-k router probabilities used unnormalised).  An arch id and not a header
# key: the per-arch flags are derived from it (models/config.py)
ARCH_OLMOE = 0xABCD03
# DeepSeek-V2: latent attention (MLA), experts chosen by group, shared
# experts, leading dense layers.  Its sizes are header keys (14..31): eleven
# of them cannot be derived from an id
ARCH_DEEPSEEK2 = 0xABCD04
# SmallThinker: Mixtral's tensors with a head size of its own (key 32), periods
# of one full layer without rotation and ``window_period - 1`` sliding-window
# layers with RoPE (keys 33, 34), a router that reads the layer's input as it
# arrives, ReLU experts
ARCH_SMALLTHINKER = 0xABCD05
# K-EXAONE (``exaone_moe``): window (RoPE) and full (unrotated) layers in
# periods whose full layer stands where key 37 says, a per-head RMSNorm of q
# and k, a leading dense layer, then expert layers with a sigmoid router (a
# bias for the choice only, the chosen scores normalised and scaled) and one
# shared expert.  A file may hold a share of every layer's routed experts
# (keys 35, 36): one chip's part of an expert-parallel deployment
ARCH_EXAONE_MOE = 0xABCD06
# LFM2 (``lfm2_moe``): periods in which one layer is attention (per-head q/k
# RMSNorm, rotate-half RoPE, at the place key 37 says) and the others are gated
# short convolutions of ``conv_taps`` taps (key 38) that keep a state, not keys
# and values; leading dense layers, then a sigmoid router with a choice bias
# and a ``+ 1e-6`` in the normalisation, no shared expert
ARCH_LFM2_MOE = 0xABCD07
# Brumby (``brumby``): Qwen3's dense block (per-head q/k RMSNorm, rotate-half
# RoPE, SwiGLU) with every attention layer replaced by power retention of
# degree ``retention_degree`` (key 39): a gate a kv head (``wg``) and, in place
# of keys and values, a state matrix a kv head (``ops/retention.py``)
ARCH_BRUMBY = 0xABCD08
# Ouro (``ouro``): a looped model.  Llama's dense block (rotate-half RoPE, no
# bias) with both branches normed again before the residual add (the four norm
# vectors of a Grok-1 layer, in its slots), and the whole stack of ``n_layers``
# weight sets applied ``n_loops`` times (key 40) over its own output, the final
# norm closing every pass; pass ``u`` of layer ``l`` keeps keys and values of
# its own (cache plane ``u * n_layers + l``)
ARCH_OURO = 0xABCD09
# Falcon-H1 (``falcon_h1``): a hybrid-head model.  EVERY block runs grouped-query
# attention (rotate-half RoPE, a head size of its own, key 32) and a Mamba-2
# state-space mixer (keys 41..45; ``ops/ssm.py``) side by side on one normed
# input and adds both to the residual, then a SwiGLU; every branch carries muP
# multipliers, scalars of the published config (keys 46..59)
ARCH_FALCON_H1 = 0xABCD0A
# Granite-4.0-H (``granitemoehybrid``): periods (keys 34, 37) in which one layer
# is grouped-query attention WITHOUT positions (no RoPE anywhere) and the others
# are Mamba-2 mixers (keys 41..45): a layer keeps a state OR keys and values.
# Every layer has a softmax router renormalised over the chosen experts
# (Mixtral's), experts of ``moe_hidden_dim`` (key 19) and a shared gated MLP
# ``n_shared_experts`` experts wide (key 20; ``hidden_dim`` is its width).  Four
# scalars of the published config, each under the key that already means it: on
# the embedding (46), on the logits (47), ``residual_multiplier`` on each
# branch's output (49 attention's, 51 the mixer's, 54 the experts' and the
# shared MLP's sum) and, in place of the scores' ``head^-1/2``,
# ``attention_multiplier`` as the key's multiplier (52) ``* head^1/2``
ARCH_GRANITE_HYBRID = 0xABCD0B
ARCH_NAMES = {ARCH_LLAMA: "llama", ARCH_GROK1: "grok1", ARCH_MIXTRAL: "mixtral",
              ARCH_OLMOE: "olmoe", ARCH_DEEPSEEK2: "deepseek2",
              ARCH_SMALLTHINKER: "smallthinker",
              ARCH_EXAONE_MOE: "exaone_moe", ARCH_LFM2_MOE: "lfm2_moe",
              ARCH_BRUMBY: "brumby", ARCH_OURO: "ouro",
              ARCH_FALCON_H1: "falcon_h1",
              ARCH_GRANITE_HYBRID: "granitemoehybrid"}

# TransformerHiddenAct (transformer.hpp:45-48), and beyond it ReLU
ACT_GELU = 0
ACT_SILU = 1
ACT_RELU = 2

# TransformerHeaderKey (transformer.hpp:10-25)
KEY_VERSION = 0
KEY_ARCH_TYPE = 1
KEY_DIM = 2
KEY_HIDDEN_DIM = 3
KEY_N_LAYERS = 4
KEY_N_HEADS = 5
KEY_N_KV_HEADS = 6
KEY_N_EXPERTS = 7
KEY_N_ACTIVE_EXPERTS = 8
KEY_VOCAB_SIZE = 9
KEY_SEQ_LEN = 10
KEY_HIDDEN_ACT = 11
KEY_ROPE_THETA = 12
KEY_WEIGHTS_FLOAT_TYPE = 13
# beyond the reference's fourteen: DeepSeek-V2's (``EXT_KEYS``, 14..31),
# SmallThinker's own (``WINDOW_KEYS``, 32..34; its file also carries key 31),
# K-EXAONE's (``SHARE_KEYS``, 35..37; its file carries some of each),
# LFM2's one (``CONV_KEYS``, 38; its file carries some of each) and Brumby's
# one (``RETENTION_KEYS``, 39; its file also carries key 31) and Ouro's one
# (``LOOP_KEYS``, 40; its file also carries key 31) and Falcon-H1's
# (``SSM_KEYS``, 41..60; its file also carries keys 31 and 32; Granite's file
# carries some of each and no key of its own).
# ``(key, field, is_float)``: a float travels as the bits of its IEEE-754 f32
# in the i32
EXT_KEYS = (
    (14, "q_lora_rank", False),
    (15, "kv_lora_rank", False),
    (16, "qk_nope_head_dim", False),
    (17, "qk_rope_head_dim", False),
    (18, "v_head_dim", False),
    (19, "moe_hidden_dim", False),      # one routed expert's width
    (20, "n_shared_experts", False),    # the shared expert is this many wide
    (21, "n_groups", False),
    (22, "topk_groups", False),
    (23, "n_dense_layers", False),      # leading layers with a dense FFN
    (24, "routed_scale", True),
    (25, "rope_factor", True),          # YaRN; 1.0 = plain RoPE
    (26, "rope_orig_seq_len", False),
    (27, "rope_beta_fast", True),
    (28, "rope_beta_slow", True),
    (29, "rope_mscale", True),
    (30, "rope_mscale_all_dim", True),
    (31, "norm_eps", True),
)
WINDOW_KEYS = (
    (32, "head_dim", False),            # a head's size where it is not dim / n_heads
    (33, "window", False),              # keys a sliding-window layer sees, the query's own included
    (34, "window_period", False),       # layer l is full (and unrotated) iff l % period == 0
)
SHARE_KEYS = (
    (35, "experts_held", False),        # routed experts a layer of this file holds (0: all)
    (36, "first_expert", False),        # the router's index of the first held one
    (37, "window_full_at", False),      # layer l is full iff l % period == this
)
CONV_KEYS = (
    (38, "conv_taps", False),           # taps of a short-convolution layer (conv_L_cache)
)
RETENTION_KEYS = (
    (39, "retention_degree", False),    # p of a power-retention layer's (q . k)^p
)
LOOP_KEYS = (
    (40, "loops", False),               # passes of the whole stack over its own output (n_loops)
)
# the five multipliers of ``ssm_multipliers``, in the order of ``W_in``'s split
SSM_MUP = ("mup_z", "mup_x", "mup_b", "mup_c", "mup_dt")
SSM_KEYS = (
    (41, "ssm_heads", False),           # mamba_n_heads
    (42, "ssm_head_dim", False),        # mamba_d_head
    (43, "ssm_state", False),           # mamba_d_state: rows of a head's state matrix
    (44, "ssm_groups", False),          # mamba_n_groups: heads / groups share one B and one C
    (45, "ssm_conv", False),            # mamba_d_conv: taps of the causal depthwise convolution
    (46, "mup_embedding", True),        # embedding_multiplier
    (47, "mup_head", True),             # lm_head_multiplier
    (48, "mup_attn_in", True),          # attention_in_multiplier
    (49, "mup_attn_out", True),         # attention_out_multiplier
    (50, "mup_ssm_in", True),           # ssm_in_multiplier
    (51, "mup_ssm_out", True),          # ssm_out_multiplier
    (52, "mup_key", True),              # key_multiplier
    (53, "mup_gate", True),             # mlp_multipliers[0]: on the gate's projection
    (54, "mup_down", True),             # mlp_multipliers[1]: on the down projection
) + tuple((55 + i, name, True) for i, name in enumerate(SSM_MUP)) + (
    (60, "rope_theta_f32", True),       # rope_theta where key 12's i32 cannot hold it
)
ALL_EXT_KEYS = (EXT_KEYS + WINDOW_KEYS + SHARE_KEYS + CONV_KEYS + RETENTION_KEYS
                + LOOP_KEYS + SSM_KEYS)
# the keys a file of an arch carries past the fourteen
ARCH_EXT_KEYS = {ARCH_DEEPSEEK2: tuple(range(14, 32)),
                 ARCH_SMALLTHINKER: (31, 32, 33, 34),
                 ARCH_EXAONE_MOE: (19, 20, 21, 22, 23, 24) + tuple(range(31, 38)),
                 # which layer of a period is attention: keys 34 and 37 as
                 # K-EXAONE's (a period and a place in it; no second pair of
                 # keys for the same two numbers); no window, so no key 33
                 ARCH_LFM2_MOE: (19, 23, 24, 31, 32, 34, 37, 38),
                 ARCH_BRUMBY: (31, 39),
                 ARCH_OURO: (31, 40),
                 ARCH_FALCON_H1: (31, 32) + tuple(range(41, 61)),
                 # the period as LFM2's (34, 37), the mixer's sizes as
                 # Falcon-H1's (41..45) and the multipliers that mean what its
                 # four scalars mean (46, 47, 52; 49, 51, 54 on a branch's output)
                 ARCH_GRANITE_HYBRID: (19, 20, 31, 32, 34, 37, 41, 42, 43, 44,
                                       45, 46, 47, 49, 51, 52, 54)}
_EXT_BY_KEY = {k: (name, is_f) for k, name, is_f in ALL_EXT_KEYS}
KEY_MAX = SSM_KEYS[-1][0]


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def _bits_f32(v: int) -> float:
    return struct.unpack("<f", struct.pack("<i", int(v)))[0]


@dataclass
class ModelSpec:
    """Model hyperparameters — the reference's ``TransformerSpec``."""

    arch: int = ARCH_LLAMA
    dim: int = 0
    hidden_dim: int = 0
    n_layers: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    n_experts: int = 0
    n_active_experts: int = 0
    vocab_size: int = 0
    seq_len: int = 0
    hidden_act: int = ACT_SILU
    rope_theta: float = 10000.0
    weights_ftype: int = quants.F32
    version: int = 1
    header_size: int = 0
    # ARCH_DEEPSEEK2's sizes (EXT_KEYS); 0 / 1.0 / 1e-5 where the arch has none
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_hidden_dim: int = 0
    n_shared_experts: int = 0
    n_groups: int = 0
    topk_groups: int = 0
    n_dense_layers: int = 0
    routed_scale: float = 1.0
    rope_factor: float = 1.0
    rope_orig_seq_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    norm_eps: float = 1e-5
    # ARCH_SMALLTHINKER's; 0 where the arch has none
    head_dim: int = 0
    window: int = 0
    window_period: int = 0
    # ARCH_EXAONE_MOE's; 0 where the arch has none
    experts_held: int = 0
    first_expert: int = 0
    window_full_at: int = 0
    # ARCH_LFM2_MOE's; 0 where the arch has none
    conv_taps: int = 0
    # ARCH_BRUMBY's; 0 where the arch has none
    retention_degree: int = 0
    # ARCH_OURO's; 0 where the arch has none (the stack runs once)
    loops: int = 0
    # ARCH_FALCON_H1's; 0 / 1.0 where the arch has none
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv: int = 0
    mup_embedding: float = 1.0
    mup_head: float = 1.0
    mup_attn_in: float = 1.0
    mup_attn_out: float = 1.0
    mup_ssm_in: float = 1.0
    mup_ssm_out: float = 1.0
    mup_key: float = 1.0
    mup_gate: float = 1.0
    mup_down: float = 1.0
    mup_z: float = 1.0
    mup_x: float = 1.0
    mup_b: float = 1.0
    mup_c: float = 1.0
    mup_dt: float = 1.0
    rope_theta_f32: float = 0.0

    @property
    def ssm_inner(self) -> int:
        """``mamba_d_ssm``: the mixer's heads times their size."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_channels(self) -> int:
        """Channels of the mixer's convolution: ``x | B | C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def n_experts_held(self) -> int:
        """Routed experts a layer of this file has planes for (the router
        always has ``n_experts`` outputs)."""
        return self.experts_held or self.n_experts

    @property
    def q_dim(self) -> int:
        """Width of the query projection (``dim`` unless the header states a
        head size)."""
        return self.head_size * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_size * self.n_kv_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def arch_name(self) -> str:
        return ARCH_NAMES.get(self.arch, hex(self.arch))

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


@dataclass
class TensorInfo:
    name: str
    shape: tuple[int, ...]  # logical row-major shape; matmuls are (d_out, n_in)
    ftype: int
    offset: int  # absolute byte offset in the file
    nbytes: int


def tensor_plan(spec: ModelSpec) -> list[TensorInfo]:
    """The fixed tensor order of a `.m` file (transformer.cpp:440-478).

    Offsets start right after the header.
    """
    w = spec.weights_ftype
    plan: list[TensorInfo] = []
    pos = spec.header_size

    def add(name: str, shape: tuple[int, ...], ftype: int):
        nonlocal pos
        d = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        n = shape[-1]
        nbytes = quants.batch_bytes(ftype, n, d)
        plan.append(TensorInfo(name, shape, ftype, pos, nbytes))
        pos += nbytes

    add("token_embedding", (spec.vocab_size, spec.dim), quants.F32)
    if spec.arch == ARCH_DEEPSEEK2:
        _deepseek2_layers(spec, add)
    if spec.arch in (ARCH_EXAONE_MOE, ARCH_LFM2_MOE, ARCH_GRANITE_HYBRID):
        _exaone_moe_layers(spec, add)
    own_layers = spec.arch in (ARCH_DEEPSEEK2, ARCH_EXAONE_MOE, ARCH_LFM2_MOE,
                               ARCH_GRANITE_HYBRID)
    for i in range(0 if own_layers else spec.n_layers):
        add(f"layers.{i}.wq", (spec.q_dim, spec.dim), w)
        add(f"layers.{i}.wk", (spec.kv_dim, spec.dim), w)
        add(f"layers.{i}.wv", (spec.kv_dim, spec.dim), w)
        add(f"layers.{i}.wo", (spec.dim, spec.q_dim), w)
        if spec.arch == ARCH_OLMOE:
            add(f"layers.{i}.q_norm", (spec.dim,), quants.F32)
            add(f"layers.{i}.k_norm", (spec.kv_dim,), quants.F32)
        if spec.arch == ARCH_BRUMBY:
            add(f"layers.{i}.wg", (spec.n_kv_heads, spec.dim), quants.F32)
            add(f"layers.{i}.q_norm", (spec.head_size,), quants.F32)
            add(f"layers.{i}.k_norm", (spec.head_size,), quants.F32)
        if spec.arch == ARCH_FALCON_H1:
            _ssm_tensors(spec, f"layers.{i}.", add)
        if spec.n_experts > 0:
            add(f"layers.{i}.moe_router", (spec.n_experts, spec.dim), w)
            for e in range(spec.n_experts):
                add(f"layers.{i}.experts.{e}.up", (spec.hidden_dim, spec.dim), w)
                add(f"layers.{i}.experts.{e}.gate", (spec.hidden_dim, spec.dim), w)
                add(f"layers.{i}.experts.{e}.down", (spec.dim, spec.hidden_dim), w)
        else:
            add(f"layers.{i}.w1", (spec.hidden_dim, spec.dim), w)
            add(f"layers.{i}.w2", (spec.dim, spec.hidden_dim), w)
            add(f"layers.{i}.w3", (spec.hidden_dim, spec.dim), w)
        add(f"layers.{i}.rms_att", (spec.dim,), quants.F32)
        add(f"layers.{i}.rms_ffn", (spec.dim,), quants.F32)
        if spec.arch in (ARCH_GROK1, ARCH_OURO):
            # the norm before the FFN and the one that closes it: ``rms_ffn``
            # closes the attention branch in a file of these two archs
            add(f"layers.{i}.rms_moe", (spec.dim,), quants.F32)
            add(f"layers.{i}.rms_ffn2", (spec.dim,), quants.F32)
    add("rms_final", (spec.dim,), quants.F32)
    add("wcls", (spec.vocab_size, spec.dim), w)
    return plan


def _ssm_tensors(spec: ModelSpec, p: str, add) -> None:
    """A Falcon-H1 layer's state-space mixer.  The published ``in_proj`` (rows
    ``z | x | B | C | dt``) is two tensors here: ``ssm_in``, its ``z | xBC``
    rows (Q40), and ``ssm_dt``, its last ``ssm_heads`` rows, float32 as
    Brumby's ``wg``: 32 rows are no Q40 matrix (9248 = 289 x 32 is no multiple
    of the 128 lanes) and ``dt`` sets the state's decay.  ``ssm_conv_w`` flat,
    channel by channel (value ``c * taps + j`` weighs position ``t - (taps - 1)
    + j`` in channel ``c``)."""
    w, d, h = spec.weights_ftype, spec.dim, spec.ssm_heads
    add(p + "ssm_in", (spec.ssm_inner + spec.ssm_channels, d), w)
    add(p + "ssm_dt", (h, d), quants.F32)
    add(p + "ssm_conv_w", (spec.ssm_channels * spec.ssm_conv,), quants.F32)
    add(p + "ssm_conv_b", (spec.ssm_channels,), quants.F32)
    add(p + "ssm_a_log", (h,), quants.F32)
    add(p + "ssm_dt_bias", (h,), quants.F32)
    add(p + "ssm_d", (h,), quants.F32)
    add(p + "ssm_norm", (spec.ssm_inner,), quants.F32)
    add(p + "ssm_out", (d, spec.ssm_inner), w)


def _deepseek2_layers(spec: ModelSpec, add) -> None:
    """A DeepSeek-V2 layer: the MLA projections (``wkv_b`` whole, its rows
    head by head ``k_nope`` then ``v``, as published), the dense FFN in the
    first ``n_dense_layers`` layers and router, experts and the shared expert
    in the others, then the two block norms."""
    w, d, h = spec.weights_ftype, spec.dim, spec.n_heads
    qk = spec.qk_nope_head_dim + spec.qk_rope_head_dim
    for i in range(spec.n_layers):
        p = f"layers.{i}."
        add(p + "wq_a", (spec.q_lora_rank, d), w)
        add(p + "q_a_norm", (spec.q_lora_rank,), quants.F32)
        add(p + "wq_b", (h * qk, spec.q_lora_rank), w)
        add(p + "wkv_a", (spec.kv_lora_rank + spec.qk_rope_head_dim, d), w)
        add(p + "kv_a_norm", (spec.kv_lora_rank,), quants.F32)
        add(p + "wkv_b", (h * (spec.qk_nope_head_dim + spec.v_head_dim),
                          spec.kv_lora_rank), w)
        add(p + "wo", (d, h * spec.v_head_dim), w)
        if i < spec.n_dense_layers:
            add(p + "w1", (spec.hidden_dim, d), w)
            add(p + "w2", (d, spec.hidden_dim), w)
            add(p + "w3", (spec.hidden_dim, d), w)
        else:
            f = spec.moe_hidden_dim
            add(p + "moe_router", (spec.n_experts, d), w)
            for e in range(spec.n_experts):
                add(f"{p}experts.{e}.up", (f, d), w)
                add(f"{p}experts.{e}.gate", (f, d), w)
                add(f"{p}experts.{e}.down", (d, f), w)
            fs = f * spec.n_shared_experts
            add(p + "shared_w1", (fs, d), w)
            add(p + "shared_w2", (d, fs), w)
            add(p + "shared_w3", (fs, d), w)
        add(p + "rms_att", (d,), quants.F32)
        add(p + "rms_ffn", (d,), quants.F32)


def _exaone_moe_layers(spec: ModelSpec, add) -> None:
    """A K-EXAONE layer: q, k, v, o and one head's ``q_norm`` / ``k_norm``;
    the dense FFN in the first ``n_dense_layers`` layers and, in the others,
    the router over all ``n_experts`` with its choice bias, the
    ``n_experts_held`` experts this file holds (file index ``e`` is the
    router's ``first_expert + e``) and the shared expert; then the two block
    norms.  An LFM2 file is the same walk with, in every layer that is not its
    period's attention layer, the convolution's three tensors in the attention
    tensors' place (``conv_taps`` flat, channel by channel: value ``c * taps +
    j`` weighs ``z[t - (taps - 1) + j]`` in channel ``c``), and no shared
    expert.  A Granite file is that walk with a mixer's tensors
    (:func:`_ssm_tensors`) where LFM2 has a convolution's, neither head norm
    nor router bias (its attention and its router have none), and the shared
    MLP in every layer."""
    w, d, f = spec.weights_ftype, spec.dim, spec.moe_hidden_dim
    granite = spec.arch == ARCH_GRANITE_HYBRID
    for i in range(spec.n_layers):
        p = f"layers.{i}."
        other = (spec.window_period
                 and i % spec.window_period != spec.window_full_at)
        if spec.conv_taps and other:
            add(p + "conv_in", (3 * d, d), w)
            add(p + "conv_taps", (d * spec.conv_taps,), quants.F32)
            add(p + "conv_out", (d, d), w)
        elif spec.ssm_heads and other:
            _ssm_tensors(spec, p, add)
        else:
            add(p + "wq", (spec.q_dim, d), w)
            add(p + "wk", (spec.kv_dim, d), w)
            add(p + "wv", (spec.kv_dim, d), w)
            add(p + "wo", (d, spec.q_dim), w)
            if not granite:
                add(p + "q_norm", (spec.head_size,), quants.F32)
                add(p + "k_norm", (spec.head_size,), quants.F32)
        if i < spec.n_dense_layers:
            add(p + "w1", (spec.hidden_dim, d), w)
            add(p + "w2", (d, spec.hidden_dim), w)
            add(p + "w3", (spec.hidden_dim, d), w)
        else:
            add(p + "moe_router", (spec.n_experts, d), w)
            if not granite:
                add(p + "moe_router_bias", (spec.n_experts,), quants.F32)
            for e in range(spec.n_experts_held):
                add(f"{p}experts.{e}.up", (f, d), w)
                add(f"{p}experts.{e}.gate", (f, d), w)
                add(f"{p}experts.{e}.down", (d, f), w)
            fs = f * spec.n_shared_experts
            if fs:
                add(p + "shared_w1", (fs, d), w)
                add(p + "shared_w2", (d, fs), w)
                add(p + "shared_w3", (fs, d), w)
        add(p + "rms_att", (d,), quants.F32)
        add(p + "rms_ffn", (d,), quants.F32)


def _read_exact(f, n: int, path, field: str) -> tuple[bytes, int]:
    """Read exactly ``n`` bytes or raise ArtifactError naming the offset —
    the loader-level replacement for letting ``struct.error`` escape on a
    truncated file."""
    off = f.tell()
    data = f.read(n)
    if len(data) != n:
        raise ArtifactError(path, field,
                            "file truncated mid-field",
                            offset=off, expected=f"{n} bytes",
                            got=f"{len(data)} bytes")
    return data, off


#: sanity ceilings for header-declared sizes.  A bit flip in a size field
#: must fail the parse, not drive a multi-minute tensor-plan walk or a
#: giant allocation; every bound sits far above any real model.
_SPEC_BOUNDS = {
    "dim": (1, 1 << 20),
    "hidden_dim": (1, 1 << 24),
    "n_layers": (1, 4096),
    "n_heads": (1, 4096),
    "n_kv_heads": (1, 4096),
    "n_experts": (0, 512),
    "n_active_experts": (0, 512),
    "vocab_size": (1, 1 << 24),
    "seq_len": (1, 1 << 24),
}


def validate_spec(spec: ModelSpec, path) -> ModelSpec:
    """Structural validation of a parsed header: range-check every field
    and the cross-field divisibility invariants the runtime assumes.
    Raises :class:`ArtifactError` naming the offending field."""
    for field, (lo, hi) in _SPEC_BOUNDS.items():
        v = getattr(spec, field)
        if not (lo <= v <= hi):
            raise ArtifactError(path, f"header field {field}",
                                "value out of range — corrupt header",
                                expected=f"{lo}..{hi}", got=v)
    if spec.arch not in ARCH_NAMES:
        raise ArtifactError(path, "header field arch",
                            "unknown architecture id",
                            expected=sorted(hex(a) for a in ARCH_NAMES),
                            got=hex(spec.arch))
    if spec.hidden_act not in (ACT_GELU, ACT_SILU, ACT_RELU):
        raise ArtifactError(path, "header field hidden_act",
                            "unknown activation id", expected="0|1|2",
                            got=spec.hidden_act)
    if spec.weights_ftype not in quants.FLOAT_TYPE_NAMES:
        raise ArtifactError(path, "header field weights_ftype",
                            "unknown weights float type",
                            expected=sorted(quants.FLOAT_TYPE_NAMES),
                            got=spec.weights_ftype)
    if not spec.rope_theta > 0:
        raise ArtifactError(path, "header field rope_theta",
                            "must be positive", got=spec.rope_theta)
    if spec.n_kv_heads > spec.n_heads:
        raise ArtifactError(path, "header field n_kv_heads",
                            "more KV heads than attention heads",
                            expected=f"<= {spec.n_heads}", got=spec.n_kv_heads)
    if spec.dim % spec.n_heads and not spec.head_dim:
        raise ArtifactError(path, "header field n_heads",
                            "dim not divisible by n_heads",
                            expected=f"divisor of dim={spec.dim}",
                            got=spec.n_heads)
    if spec.n_heads % spec.n_kv_heads:
        raise ArtifactError(path, "header field n_kv_heads",
                            "n_heads not divisible by n_kv_heads (GQA)",
                            expected=f"divisor of n_heads={spec.n_heads}",
                            got=spec.n_kv_heads)
    if spec.n_active_experts > spec.n_experts:
        raise ArtifactError(path, "header field n_active_experts",
                            "more active experts than experts",
                            expected=f"<= {spec.n_experts}",
                            got=spec.n_active_experts)
    if spec.arch in (ARCH_SMALLTHINKER, ARCH_EXAONE_MOE):
        _validate_smallthinker(spec, path)
    elif spec.arch == ARCH_LFM2_MOE:
        _validate_lfm2_moe(spec, path)
    elif spec.arch == ARCH_FALCON_H1:
        _validate_falcon_h1(spec, path)
    elif spec.arch == ARCH_GRANITE_HYBRID:
        _validate_granite_hybrid(spec, path)
    elif spec.head_dim or spec.window or spec.window_period:
        raise ArtifactError(path, "header key",
                            "keys 32..34 describe a smallthinker file (or an "
                            "exaone_moe or lfm2_moe one)",
                            expected=hex(ARCH_SMALLTHINKER), got=hex(spec.arch))
    if spec.arch != ARCH_LFM2_MOE and spec.conv_taps:
        raise ArtifactError(path, "header key",
                            "key 38 describes an lfm2_moe file",
                            expected=hex(ARCH_LFM2_MOE), got=hex(spec.arch))
    if (spec.arch == ARCH_BRUMBY) != bool(spec.retention_degree):
        raise ArtifactError(path, "header key",
                            "key 39 (the retention's degree) describes a brumby "
                            "file, and a brumby file states it",
                            expected=hex(ARCH_BRUMBY), got=hex(spec.arch))
    if spec.arch == ARCH_BRUMBY:
        _validate_brumby(spec, path)
    if (spec.arch == ARCH_OURO) != bool(spec.loops):
        raise ArtifactError(path, "header key",
                            "key 40 (the passes of a looped stack) describes an "
                            "ouro file, and an ouro file states it",
                            expected=hex(ARCH_OURO), got=hex(spec.arch))
    if spec.arch == ARCH_OURO:
        _validate_ouro(spec, path)
    if spec.arch not in (ARCH_FALCON_H1, ARCH_GRANITE_HYBRID) and any(
            getattr(spec, name) != getattr(ModelSpec, name)
            for _, name, _ in SSM_KEYS):
        raise ArtifactError(path, "header key",
                            "keys 41..60 describe a falcon_h1 file (or a "
                            "granitemoehybrid one)",
                            expected=hex(ARCH_FALCON_H1), got=hex(spec.arch))
    if spec.arch == ARCH_EXAONE_MOE:
        _validate_exaone_moe(spec, path)
    elif spec.experts_held or spec.first_expert or (
            spec.window_full_at
            and spec.arch not in (ARCH_LFM2_MOE, ARCH_GRANITE_HYBRID)):
        raise ArtifactError(path, "header key",
                            "keys 35..37 describe an exaone_moe file",
                            expected=hex(ARCH_EXAONE_MOE), got=hex(spec.arch))
    if spec.arch == ARCH_DEEPSEEK2:
        _validate_deepseek2(spec, path)
    elif spec.arch not in (ARCH_EXAONE_MOE, ARCH_LFM2_MOE,
                           ARCH_GRANITE_HYBRID) and (  # which carry some of those keys
            spec.is_mla or spec.n_dense_layers or spec.n_shared_experts
            or spec.n_groups or spec.moe_hidden_dim):
        raise ArtifactError(path, "header key",
                            "keys 14..30 describe a deepseek2 file",
                            expected=hex(ARCH_DEEPSEEK2), got=hex(spec.arch))
    if not 0 < spec.norm_eps < 1e-2:
        raise ArtifactError(path, "header field norm_eps",
                            "value out of range — corrupt header",
                            expected="0..1e-2", got=spec.norm_eps)
    if spec.arch == ARCH_OLMOE and not spec.n_active_experts:
        raise ArtifactError(path, "header field n_active_experts",
                            "an olmoe file has experts and a top-k",
                            expected=">= 1", got=spec.n_active_experts)
    return spec


def _validate_smallthinker(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_SMALLTHINKER`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 2 <= spec.head_dim <= 4096 or spec.head_dim % 2:
        bad("head_dim", "a smallthinker file states its head size, and RoPE "
            "rotates halves of it", "even, 2..4096", spec.head_dim)
    if not 1 <= spec.window <= 1 << 24:
        bad("window", "a smallthinker file states its sliding window",
            "1..2^24", spec.window)
    if spec.window_period < 2 or spec.n_layers % spec.window_period:
        bad("window_period", "the layers are whole periods of one full layer "
            "and window_period - 1 window layers",
            f">= 2, a divisor of n_layers={spec.n_layers}", spec.window_period)
    if not spec.n_experts or not spec.n_active_experts:
        bad("n_experts", "every smallthinker layer has experts and a top-k",
            ">= 1", spec.n_experts)


def _validate_exaone_moe(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_EXAONE_MOE`` header past the window
    keys' (:func:`_validate_smallthinker`)."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 0 <= spec.window_full_at < spec.window_period:
        bad("window_full_at", "the full layer's place in a period",
            f"0..{spec.window_period - 1}", spec.window_full_at)
    if not 1 <= spec.moe_hidden_dim <= 1 << 24:
        bad("moe_hidden_dim", "an exaone_moe file states its experts' width",
            "1..2^24", spec.moe_hidden_dim)
    if not 0 <= spec.n_shared_experts <= 64:
        bad("n_shared_experts", "value out of range — corrupt header",
            "0..64", spec.n_shared_experts)
    if not 0 <= spec.n_dense_layers < spec.n_layers:
        bad("n_dense_layers", "the dense layers lead and expert layers follow",
            f"0..{spec.n_layers - 1}", spec.n_dense_layers)
    if spec.n_groups != 1 or spec.topk_groups != 1:
        bad("n_groups", "an exaone_moe router chooses over all experts at "
            "once (one group)", 1, (spec.n_groups, spec.topk_groups))
    if not spec.routed_scale > 0:
        bad("routed_scale", "must be positive", "> 0", spec.routed_scale)
    held = spec.n_experts_held
    if not (1 <= held <= spec.n_experts
            and 0 <= spec.first_expert <= spec.n_experts - held):
        bad("experts_held", "the held experts are a run of the router's",
            f"first_expert + experts_held <= n_experts={spec.n_experts}",
            (spec.first_expert, spec.experts_held))
    if spec.n_active_experts > spec.n_experts:
        bad("n_active_experts", "more experts a token than the router has",
            f"<= {spec.n_experts}", spec.n_active_experts)


def _validate_brumby(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_BRUMBY`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if spec.retention_degree != 2:
        bad("retention_degree", "power retention is implemented for the "
            "released degree (the symmetric square of a head)", 2,
            spec.retention_degree)
    if spec.head_size % 2:
        bad("n_heads", "RoPE rotates halves of a head", "an even dim / n_heads",
            spec.head_size)
    if spec.n_experts:
        bad("n_experts", "a brumby layer has a dense SwiGLU", 0, spec.n_experts)


def _validate_falcon_h1(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_FALCON_H1`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 2 <= spec.head_dim <= 4096 or spec.head_dim % 2:
        bad("head_dim", "a falcon_h1 file states its attention head size, and "
            "RoPE rotates halves of it", "even, 2..4096", spec.head_dim)
    if spec.window or spec.window_period:
        bad("window", "a falcon_h1 file has no sliding window and no periods: "
            "every block is alike", 0, (spec.window, spec.window_period))
    for field, hi in (("ssm_heads", 4096), ("ssm_head_dim", 4096),
                      ("ssm_state", 4096), ("ssm_groups", 4096)):
        if not 1 <= getattr(spec, field) <= hi:
            bad(field, "a falcon_h1 file states its state-space mixer's sizes",
                f"1..{hi}", getattr(spec, field))
    if spec.ssm_heads % spec.ssm_groups:
        bad("ssm_groups", "the mixer's heads share B and C in whole groups",
            f"a divisor of ssm_heads={spec.ssm_heads}", spec.ssm_groups)
    if not 2 <= spec.ssm_conv <= 64:
        bad("ssm_conv", "a falcon_h1 file states its convolution's taps "
            "(mamba_d_conv)", "2..64", spec.ssm_conv)
    if spec.n_experts:
        bad("n_experts", "a falcon_h1 block has a dense SwiGLU", 0,
            spec.n_experts)
    for _, name, is_f in SSM_KEYS[5:]:
        v = getattr(spec, name)
        if not (v > 0 and np.isfinite(v)):
            bad(name, "a multiplier (and rope_theta) is a positive float",
                "> 0", v)


def _validate_granite_hybrid(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_GRANITE_HYBRID`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 2 <= spec.head_dim <= 4096:
        bad("head_dim", "a granitemoehybrid file states its attention head "
            "size", "2..4096", spec.head_dim)
    if spec.window or spec.conv_taps:
        bad("window", "a granitemoehybrid file has no sliding window and no "
            "short convolution: the layers beside a period's attention layer "
            "are state-space mixers", 0, (spec.window, spec.conv_taps))
    if spec.window_period < 2 or spec.n_layers % spec.window_period:
        bad("window_period", "the layers are whole periods of one attention "
            "layer and window_period - 1 mixer layers",
            f">= 2, a divisor of n_layers={spec.n_layers}", spec.window_period)
    if not 0 <= spec.window_full_at < spec.window_period:
        bad("window_full_at", "the attention layer's place in a period",
            f"0..{spec.window_period - 1}", spec.window_full_at)
    for field, hi in (("ssm_heads", 4096), ("ssm_head_dim", 4096),
                      ("ssm_state", 4096), ("ssm_groups", 4096)):
        if not 1 <= getattr(spec, field) <= hi:
            bad(field, "a granitemoehybrid file states its state-space mixer's "
                "sizes", f"1..{hi}", getattr(spec, field))
    if spec.ssm_groups != 1:
        bad("ssm_groups", "the gated norm before W_out is one RMSNorm over all "
            "of the mixer's channels only where the heads share one B and one "
            "C (a per-group norm is Falcon-H1's)", 1, spec.ssm_groups)
    if not 2 <= spec.ssm_conv <= 64:
        bad("ssm_conv", "a granitemoehybrid file states its convolution's taps "
            "(mamba_d_conv)", "2..64", spec.ssm_conv)
    if not spec.n_experts or not spec.n_active_experts:
        bad("n_experts", "every granitemoehybrid layer has experts and a top-k",
            ">= 1", spec.n_experts)
    if not 1 <= spec.moe_hidden_dim <= 1 << 24:
        bad("moe_hidden_dim", "a granitemoehybrid file states its experts' "
            "width", "1..2^24", spec.moe_hidden_dim)
    if not 0 <= spec.n_shared_experts <= 64 or (
            spec.hidden_dim != spec.moe_hidden_dim * max(spec.n_shared_experts, 1)):
        bad("n_shared_experts", "the shared MLP is a whole number of experts "
            "wide and hidden_dim is its width", "hidden_dim / moe_hidden_dim",
            (spec.n_shared_experts, spec.hidden_dim, spec.moe_hidden_dim))
    own = ("mup_embedding", "mup_head", "mup_key", "mup_attn_out",
           "mup_ssm_out", "mup_down")
    for name in own:
        v = getattr(spec, name)
        if not (v > 0 and np.isfinite(v)):
            bad(name, "a multiplier is a positive float", "> 0", v)
    for _, name, is_f in SSM_KEYS[5:]:
        if name not in own and getattr(spec, name) != getattr(ModelSpec, name):
            bad(name, "a granitemoehybrid file carries the embedding's, the "
                "head's, the key's and the three branch outputs' multipliers "
                "alone", getattr(ModelSpec, name), getattr(spec, name))


def _validate_lfm2_moe(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_LFM2_MOE`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 2 <= spec.head_dim <= 4096 or spec.head_dim % 2:
        bad("head_dim", "an lfm2_moe file states its head size, and RoPE "
            "rotates halves of it", "even, 2..4096", spec.head_dim)
    if spec.window:
        bad("window", "an lfm2_moe file has no sliding window: the layers "
            "beside a period's attention layer are convolutions", 0, spec.window)
    if spec.window_period < 2 or spec.n_layers % spec.window_period:
        bad("window_period", "the layers are whole periods of one attention "
            "layer and window_period - 1 convolution layers",
            f">= 2, a divisor of n_layers={spec.n_layers}", spec.window_period)
    if not 0 <= spec.window_full_at < spec.window_period:
        bad("window_full_at", "the attention layer's place in a period",
            f"0..{spec.window_period - 1}", spec.window_full_at)
    if not 2 <= spec.conv_taps <= 64:
        bad("conv_taps", "an lfm2_moe file states its convolution's taps "
            "(conv_L_cache)", "2..64", spec.conv_taps)
    if not 1 <= spec.moe_hidden_dim <= 1 << 24:
        bad("moe_hidden_dim", "an lfm2_moe file states its experts' width",
            "1..2^24", spec.moe_hidden_dim)
    if not 0 <= spec.n_dense_layers < spec.n_layers:
        bad("n_dense_layers", "the dense layers lead and expert layers follow",
            f"0..{spec.n_layers - 1}", spec.n_dense_layers)
    if not spec.n_experts or not spec.n_active_experts:
        bad("n_experts", "the layers past the dense ones have experts and a "
            "top-k", ">= 1", spec.n_experts)
    if not spec.routed_scale > 0:
        bad("routed_scale", "must be positive", "> 0", spec.routed_scale)
    if spec.n_shared_experts or spec.n_groups or spec.topk_groups:
        bad("n_shared_experts", "an lfm2_moe layer has no shared expert and "
            "one group of experts (keys 20..22 are not its own)", 0,
            (spec.n_shared_experts, spec.n_groups, spec.topk_groups))


def _validate_ouro(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_OURO`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    if not 1 <= spec.loops <= 64:
        bad("loops", "an ouro file states how many times its stack runs",
            "1..64", spec.loops)
    if spec.n_experts:
        bad("n_experts", "an ouro layer has a dense FFN", 0, spec.n_experts)
    if spec.dim // spec.n_heads % 2:
        bad("n_heads", "RoPE rotates halves of a head", "an even head size",
            spec.dim // spec.n_heads)


def _validate_deepseek2(spec: ModelSpec, path) -> None:
    """The cross-field rules of an ``ARCH_DEEPSEEK2`` header."""
    def bad(field, why, expected, got):
        raise ArtifactError(path, f"header field {field}", why,
                            expected=expected, got=got)

    for field, hi in (("q_lora_rank", 1 << 16), ("kv_lora_rank", 1 << 16),
                      ("qk_nope_head_dim", 4096), ("qk_rope_head_dim", 4096),
                      ("v_head_dim", 4096), ("moe_hidden_dim", 1 << 24),
                      ("n_groups", 512), ("topk_groups", 512)):
        v = getattr(spec, field)
        if not 1 <= v <= hi:
            bad(field, "a deepseek2 file states this size", f"1..{hi}", v)
    if spec.qk_rope_head_dim % 2:
        bad("qk_rope_head_dim", "RoPE rotates pairs", "even",
            spec.qk_rope_head_dim)
    if not 0 <= spec.n_shared_experts <= 64:
        bad("n_shared_experts", "value out of range — corrupt header",
            "0..64", spec.n_shared_experts)
    if not 0 <= spec.n_dense_layers <= spec.n_layers:
        bad("n_dense_layers", "more dense layers than layers",
            f"0..{spec.n_layers}", spec.n_dense_layers)
    if spec.n_dense_layers < spec.n_layers:
        if not spec.n_experts or not spec.n_active_experts:
            bad("n_experts", "the layers past the dense ones have experts "
                "and a top-k", ">= 1", spec.n_experts)
        if spec.n_experts % spec.n_groups:
            bad("n_groups", "experts not divisible into groups",
                f"divisor of n_experts={spec.n_experts}", spec.n_groups)
        if spec.topk_groups > spec.n_groups:
            bad("topk_groups", "more groups kept than groups",
                f"<= {spec.n_groups}", spec.topk_groups)
        if spec.n_active_experts > spec.topk_groups * (spec.n_experts
                                                       // spec.n_groups):
            bad("n_active_experts", "more experts a token than the kept "
                "groups hold", f"<= {spec.topk_groups} groups of "
                f"{spec.n_experts // spec.n_groups}", spec.n_active_experts)
    if spec.n_kv_heads != spec.n_heads:
        bad("n_kv_heads", "latent attention has one latent for all heads; "
            "the header repeats n_heads", spec.n_heads, spec.n_kv_heads)
    if not (spec.routed_scale > 0 and spec.rope_factor >= 1.0):
        bad("routed_scale", "routed_scale must be positive and rope_factor "
            ">= 1", "> 0, >= 1", (spec.routed_scale, spec.rope_factor))
    if spec.rope_factor > 1.0 and spec.rope_orig_seq_len < 1:
        bad("rope_orig_seq_len", "YaRN needs the original context length",
            ">= 1", spec.rope_orig_seq_len)


def read_spec(path: str | os.PathLike, weights_ftype: int | None = None) -> ModelSpec:
    """Parse + validate a `.m` header (transformer.cpp:12-125).

    Fully bounds-checked (beyond reference — ``loadSpecFromFile`` trusts
    its input): every read is length-checked, the declared header size is
    checked against the file, keys/values are range-checked, and any
    violation raises :class:`ArtifactError` with the file offset and field
    name — never ``struct.error``.

    ``weights_ftype`` mirrors the reference's mandatory
    ``--weights-float-type`` flag: legacy-magic files don't carry the weight
    float type, and v2 files may omit the key; the reference refuses to load
    in that case (`FUNK` check, transformer.cpp:80-81).
    """
    spec = ModelSpec()
    found_wft = False
    file_size = os.path.getsize(path)
    with open(path, "rb") as f:
        raw, _ = _read_exact(f, 4, path, "magic")
        (magic,) = struct.unpack("<i", raw)
        if magic in LEGACY_MAGICS:
            raw, off = _read_exact(f, 36, path, "legacy header")
            vals = struct.unpack("<9i", raw)
            spec.arch = magic
            (spec.dim, spec.hidden_dim, spec.n_layers, spec.n_heads,
             spec.n_kv_heads, spec.n_experts, spec.n_active_experts,
             spec.vocab_size, spec.seq_len) = vals
            spec.header_size = 4 + 36
        elif magic == MAGIC_V2:
            raw, off = _read_exact(f, 4, path, "headerSize")
            (header_size,) = struct.unpack("<i", raw)
            if header_size < 8 or (header_size - 8) % 8:
                raise ArtifactError(
                    path, "headerSize",
                    "must be 8 + a whole number of (key, value) i32 pairs",
                    offset=off, expected="8 + 8k", got=header_size)
            if header_size > file_size:
                raise ArtifactError(path, "headerSize",
                                    "header extends past end of file",
                                    offset=off, expected=f"<= {file_size}",
                                    got=header_size)
            spec.header_size = header_size
            body, body_off = _read_exact(f, header_size - 8, path, "header body")
            kv = struct.unpack(f"<{len(body) // 4}i", body)
            for i, (k, v) in enumerate(zip(kv[::2], kv[1::2])):
                pair_off = body_off + 8 * i
                if k == KEY_VERSION:
                    spec.version = v
                elif k == KEY_ARCH_TYPE:
                    spec.arch = v
                elif k == KEY_DIM:
                    spec.dim = v
                elif k == KEY_HIDDEN_DIM:
                    spec.hidden_dim = v
                elif k == KEY_N_LAYERS:
                    spec.n_layers = v
                elif k == KEY_N_HEADS:
                    spec.n_heads = v
                elif k == KEY_N_KV_HEADS:
                    spec.n_kv_heads = v
                elif k == KEY_N_EXPERTS:
                    spec.n_experts = v
                elif k == KEY_N_ACTIVE_EXPERTS:
                    spec.n_active_experts = v
                elif k == KEY_VOCAB_SIZE:
                    spec.vocab_size = v
                elif k == KEY_SEQ_LEN:
                    spec.seq_len = v
                elif k == KEY_HIDDEN_ACT:
                    spec.hidden_act = v
                elif k == KEY_ROPE_THETA:
                    spec.rope_theta = float(v)
                elif k == KEY_WEIGHTS_FLOAT_TYPE:
                    spec.weights_ftype = v
                    found_wft = True
                elif k in _EXT_BY_KEY:
                    name, is_f = _EXT_BY_KEY[k]
                    setattr(spec, name, _bits_f32(v) if is_f else v)
                else:
                    raise ArtifactError(path, "header key",
                                        "unsupported .m header key",
                                        offset=pair_off,
                                        expected=f"0..{KEY_MAX}",
                                        got=k)
        else:
            raise ArtifactError(path, "magic",
                                "unsupported model file magic",
                                offset=0,
                                expected=[hex(MAGIC_V2)] + [hex(m) for m in LEGACY_MAGICS],
                                got=hex(magic & 0xFFFFFFFF))
    # Precedence mirrors the reference: the header's WEIGHTS_FLOAT_TYPE key
    # overwrites the caller/CLI value (transformer.cpp:66-74 loop overwrites
    # the argument); the explicit argument only covers files lacking the key.
    if not found_wft:
        if weights_ftype is None:
            raise ArtifactError(
                path, "header field weights_ftype",
                "model file does not specify weights float type; pass weights_ftype "
                "(reference: 'Not specified weights float type', transformer.cpp:80-81)")
        spec.weights_ftype = weights_ftype
    if spec.rope_theta_f32:  # the float form wins: key 12 then holds a clipped i32
        spec.rope_theta = spec.rope_theta_f32
    return validate_spec(spec, path)


class MFile:
    """mmap-backed lazy `.m` reader with integrity checking.

    When a sidecar checksum manifest (``<path>.sum``, io/integrity.py,
    written by ``tools/checksum_model.py``) exists, the header digest is
    verified at open **always**, and each tensor's digest is verified on
    first read when ``verify=True`` (the CLI's ``--verify-weights``) —
    lazy, so sharded loading still streams without a full pre-pass, yet
    every byte the runtime consumes was checksummed.  ``verify=True``
    with no manifest is an error: silently skipping requested
    verification would defeat its purpose.
    """

    def __init__(self, path: str | os.PathLike, weights_ftype: int | None = None,
                 verify: bool = False):
        self.path = os.fspath(path)
        self.spec = read_spec(path, weights_ftype)
        self.verify_weights = verify
        self.manifest = load_manifest_for(self.path)
        self._verified: set[str] = set()
        if verify and self.manifest is None:
            raise ArtifactError(
                self.path, "manifest",
                "weight verification requested but no checksum manifest "
                f"found at {self.path}.sum (generate one with "
                "tools/checksum_model.py write)")
        self._f = open(self.path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        if self.manifest is not None:
            # header digest is always-on, and it runs BEFORE the tensor
            # plan is derived: every plan offset/size below comes from
            # header fields, so a flipped header must be caught here, not
            # surface as a downstream shape error
            if self.manifest["file_size"] != len(self._mm):
                raise ArtifactError(self.path, "file size",
                                    "size mismatch vs manifest",
                                    expected=self.manifest["file_size"],
                                    got=len(self._mm))
            verify_bytes(self.manifest["header"],
                         self._mm[:self.spec.header_size], self.path, "header")
        try:
            self.plan = tensor_plan(self.spec)
        except ValueError as e:
            # spec fields were individually in range but jointly impossible
            # (e.g. a flipped vocab_size that breaks quant block alignment)
            raise ArtifactError(
                self.path, "header",
                f"header describes an impossible tensor plan: {e}") from e
        self.by_name = {t.name: t for t in self.plan}
        end = self.plan[-1].offset + self.plan[-1].nbytes
        if len(self._mm) != end:
            raise ArtifactError(
                self.path, "file size",
                f"model file size mismatch: file={len(self._mm)} expected={end} "
                f"(reference errors the same way, transformer.cpp:480-484)",
                expected=end, got=len(self._mm))

    def close(self):
        try:
            self._mm.close()
        except BufferError:
            # zero-copy views handed out by raw() still reference the map;
            # it closes when the last view is collected
            pass
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def info(self, name: str) -> TensorInfo:
        """Plan entry for ``name``; unknown names raise ArtifactError
        listing what the file actually contains (never a bare KeyError)."""
        t = self.by_name.get(name)
        if t is None:
            sample = ", ".join(sorted(self.by_name)[:6])
            raise ArtifactError(
                self.path, f"tensor {name!r}",
                f"unknown tensor name; this {self.spec.arch_name} file has "
                f"{len(self.by_name)} tensors ({sample}, ...)")
        return t

    def raw(self, name: str) -> np.ndarray:
        """One tensor's packed file bytes (checksum-verified on first read
        under ``verify=True``).  The ``io.read_tensor`` fault point's
        ``corrupt`` action flips a byte of the returned buffer — the
        deterministic stand-in for storage corruption that lets drills
        prove the manifest catches it (runtime/faults.py)."""
        from ..runtime.faults import FAULTS
        t = self.info(name)
        buf = np.frombuffer(self._mm, dtype=np.uint8, count=t.nbytes,
                            offset=t.offset)
        if "corrupt" in FAULTS.fire("io.read_tensor"):
            buf = buf.copy()
            buf[0] ^= 0xFF
        if self.verify_weights and name not in self._verified:
            ent = self.manifest["tensors"].get(name)
            if ent is None:
                raise ArtifactError(self.path, f"tensor {name!r}",
                                    "tensor missing from checksum manifest "
                                    "(stale manifest? regenerate it)")
            if (ent["offset"], ent["nbytes"]) != (t.offset, t.nbytes):
                raise ArtifactError(
                    self.path, f"tensor {name!r}",
                    "manifest byte range disagrees with the file's tensor "
                    "plan (stale manifest? regenerate it)",
                    offset=t.offset,
                    expected=(ent["offset"], ent["nbytes"]),
                    got=(t.offset, t.nbytes))
            verify_bytes(ent, buf, self.path, f"tensor {name!r}")
            self._verified.add(name)
        return buf

    def tensor(self, name: str) -> np.ndarray:
        """Dequantize one tensor to f32 in its logical row-major shape."""
        t = self.info(name)
        n = int(np.prod(t.shape))
        return quants.dequantize_tensor(self.raw(name), t.ftype, n).reshape(t.shape)

    def q40_planes(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """Unpacked int8 values + per-block scales for a Q40 matmul tensor."""
        t = self.info(name)
        if t.ftype != quants.Q40:
            raise ValueError(f"{name} is not Q40")
        d = int(np.prod(t.shape[:-1]))
        return quants.q40_planes(self.raw(name), (d, t.shape[-1]))


def write_header(f, spec: ModelSpec) -> int:
    """Write a v2 `.m` header; returns its byte count
    (converter/writer.py:113-143 layout)."""
    pairs = [
        (KEY_VERSION, spec.version),
        (KEY_ARCH_TYPE, spec.arch),
        (KEY_DIM, spec.dim),
        (KEY_HIDDEN_DIM, spec.hidden_dim),
        (KEY_N_LAYERS, spec.n_layers),
        (KEY_N_HEADS, spec.n_heads),
        (KEY_N_KV_HEADS, spec.n_kv_heads),
        (KEY_N_EXPERTS, spec.n_experts),
        (KEY_N_ACTIVE_EXPERTS, spec.n_active_experts),
        (KEY_VOCAB_SIZE, spec.vocab_size),
        (KEY_SEQ_LEN, spec.seq_len),
        (KEY_HIDDEN_ACT, spec.hidden_act),
        (KEY_ROPE_THETA, int(min(spec.rope_theta, 2 ** 31 - 1))),
        (KEY_WEIGHTS_FLOAT_TYPE, spec.weights_ftype),
    ]
    # the older archs keep the reference's fourteen keys, byte for byte
    own = ARCH_EXT_KEYS.get(spec.arch, ())

    def value(name):  # key 60 states rope_theta again, as a float
        return getattr(spec, "rope_theta" if name == "rope_theta_f32" else name)

    pairs += [(k, _f32_bits(value(name)) if is_f else value(name))
              for k, name, is_f in ALL_EXT_KEYS if k in own]
    data = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    f.write(struct.pack("<ii", MAGIC_V2, 8 + len(data)))
    f.write(data)
    return 8 + len(data)


class MFileWriter:
    """Streams tensors into a `.m` file in the canonical order."""

    def __init__(self, path: str | os.PathLike, spec: ModelSpec):
        self.spec = spec
        self._i = 0
        self._f = open(path, "wb")
        spec.header_size = write_header(self._f, spec)
        self.plan = tensor_plan(spec)

    def write_tensor(self, name: str, x: np.ndarray) -> None:
        expect = self.plan[self._i]
        if name != expect.name:
            raise ValueError(f"tensor order mismatch: got {name}, want {expect.name}")
        if tuple(x.shape) != tuple(expect.shape):
            raise ValueError(f"{name}: shape {x.shape} != {expect.shape}")
        self._f.write(quants.quantize_tensor(x, expect.ftype))
        self._i += 1

    def write_raw(self, name: str, raw: np.ndarray | bytes) -> None:
        """Write a tensor's already-encoded bytes (size-checked against the
        plan).  Lets large fixtures/benchmark models be synthesized at
        packed size with no f32 transit — the quantized analogue of the
        reference's direct block writes (writer.py:29-78)."""
        expect = self.plan[self._i]
        if name != expect.name:
            raise ValueError(f"tensor order mismatch: got {name}, want {expect.name}")
        n = int(np.prod(expect.shape))
        want = quants.batch_bytes(expect.ftype, n)
        raw = np.asarray(raw, np.uint8) if not isinstance(raw, bytes) else raw
        got = raw.nbytes if isinstance(raw, np.ndarray) else len(raw)
        if got != want:
            raise ValueError(f"{name}: raw payload {got} B != expected {want} B")
        self._f.write(raw.tobytes() if isinstance(raw, np.ndarray) else raw)
        self._i += 1

    def close(self):
        if self._i != len(self.plan):
            raise ValueError(f"file incomplete: {self._i}/{len(self.plan)} tensors written")
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is None:
            self.close()
        else:
            self._f.close()
