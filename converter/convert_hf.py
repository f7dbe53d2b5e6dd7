"""HF safetensors → `.m` converter.

Re-implements `/root/reference/converter/convert-hf.py`: llama / mistral /
mixtral folders with ``config.json`` + ``*.safetensors`` become a `.m` file
in the canonical tensor order; beyond the reference, deepseek_v2 folders
(MLA, ``kv_b_proj`` kept whole, header keys 14..31), olmoe folders
(``ARCH_OLMOE``), smallthinker folders (``ARCH_SMALLTHINKER``: header keys
31..34, rows not permuted) and exaone_moe folders (``ARCH_EXAONE_MOE``: K-EXAONE;
``--experts-held N --first-expert I`` write one chip's share of every layer's
routed experts; ``mtp.*`` tensors are skipped) and lfm2_moe folders
(``ARCH_LFM2_MOE``: LFM2's gated short-convolution and attention layers,
header keys 19, 23, 24, 31, 32, 34, 37, 38; ``conv.conv.weight`` (D, 1, L)
becomes the flat ``conv_taps``; a tied head is written from the embedding's
rows) and brumby folders (``ARCH_BRUMBY``: Qwen3's names with a gate
``self_attn.g_proj``, header keys 31 and 39; the names are ASSUMED, one table,
``_BRUMBY_LEAVES``, unverified until the published files are in the
repository) and ouro folders (``ARCH_OURO``: a looped model, Llama's names with
the two closing norms ``input_layernorm_2`` / ``post_attention_layernorm_2``,
header keys 31 and 40; ASSUMED likewise, ``_OURO_LEAVES``;
``early_exit_gate.*`` is skipped by name) and falcon_h1 folders
(``ARCH_FALCON_H1``: attention and a Mamba-2 mixer in every block, header keys
31, 32, 41..60; ``mamba.in_proj.weight`` becomes ``ssm_in`` (its ``z | x | B |
C`` rows) and the float32 ``ssm_dt`` (its ``dt`` rows), ``conv1d.weight`` (C, 1,
K) the flat ``ssm_conv_w``; ASSUMED likewise, ``_FALCON_H1_LEAVES``) and
granitemoehybrid folders (``ARCH_GRANITE_HYBRID``: Granite-4.0-H's mixer layers
and position-free attention layers, header keys 19, 20, 31, 32, 34, 37, 41..47,
49, 51, 52, 54; the experts' stacked ``input_linear`` (E, 2 F, D) is cut into each
expert's ``gate`` and ``up`` halves and ``output_linear`` (E, D, F) into its
``down``; the tied head is written from the embedding's rows; ASSUMED likewise,
``_GRANITE_LEAVES``).  Key
semantics preserved:

* q/k head permutation (convert-hf.py:12-15): HF stores RoPE in rotate-half
  layout; the `.m` format expects the interleaved-pair layout, so q and k
  rows are permuted ``(h, 2, hs/2) → (h, hs/2, 2)``.  The reference applies
  this to every arch (including Mixtral, whose runtime then rotates
  neox-style — a reference quirk preserved for file-format parity).
  OLMoE rows are NOT permuted: its runtime rotates halves as HF does, and a
  permuted q or k could not share the order of its ``q_norm``/``k_norm``
  weight, which spans the whole projection.
* dense FFN file order gate/down/up = w1/w2/w3 (convert-hf.py:77-83);
  MoE per-expert order up(w3)/gate(w1)/down(w2) (convert-hf.py:68-75).

Usage: python convert_hf.py <sourceFolderPath> <weightsFloatType> <name>
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dllama_tpu import quants  # noqa: E402
from dllama_tpu.io import mfile  # noqa: E402

ARCH_BY_MODEL_TYPE = {
    "llama": mfile.ARCH_LLAMA,
    "mistral": mfile.ARCH_LLAMA,
    "mixtral": mfile.ARCH_MIXTRAL,
    "olmoe": mfile.ARCH_OLMOE,
    "deepseek_v2": mfile.ARCH_DEEPSEEK2,
    "smallthinker": mfile.ARCH_SMALLTHINKER,
    "granitemoehybrid": mfile.ARCH_GRANITE_HYBRID,
    "exaone_moe": mfile.ARCH_EXAONE_MOE,
    "lfm2_moe": mfile.ARCH_LFM2_MOE,
    "brumby": mfile.ARCH_BRUMBY,
    "ouro": mfile.ARCH_OURO,
    "falcon_h1": mfile.ARCH_FALCON_H1,
}
HIDDEN_ACT = {"gelu": mfile.ACT_GELU, "silu": mfile.ACT_SILU,
              "relu": mfile.ACT_RELU}


def permute(t: np.ndarray, n_heads: int, n_kv_heads: int) -> np.ndarray:
    """Rotate-half → interleaved head layout (convert-hf.py:12-15)."""
    if n_heads != n_kv_heads:
        n_heads = n_kv_heads
    return (t.reshape(n_heads, 2, t.shape[0] // n_heads // 2, *t.shape[1:])
             .swapaxes(1, 2).reshape(t.shape))


def _refuse_olmoe_variants(config: dict) -> None:
    """``ARCH_OLMOE`` is one block: the settings the published OLMoE-1B-7B
    has.  A config that departs from them would convert to a file the
    runtime computes silently wrong, so it is refused here."""
    if config.get("norm_topk_prob", False):
        raise SystemExit("olmoe: norm_topk_prob is true; the runtime uses the "
                         "top-k router probabilities unnormalised for this arch")
    if config.get("clip_qkv") is not None:
        raise SystemExit(f"olmoe: clip_qkv is {config['clip_qkv']}; the runtime "
                         "does not clip q, k or v")
    if config.get("attention_bias", False):
        raise SystemExit("olmoe: attention_bias is true; the .m format has no "
                         "bias tensors")
    if config.get("rope_scaling") is not None:
        raise SystemExit(f"olmoe: rope_scaling is {config['rope_scaling']}; the "
                         "runtime's RoPE is unscaled")


def _refuse_deepseek2_variants(config: dict) -> None:
    """``ARCH_DEEPSEEK2`` is DeepSeek-V2's block (softmax router scores, experts
    chosen greedily or by group, the chosen probabilities scaled and not
    renormalised, an expert FFN in every layer past the dense ones, YaRN or
    plain RoPE, no biases, q through its latent).  What the runtime does not
    compute is refused by name (V3's sigmoid scores and correction bias, V2-
    Lite's direct q projection)."""
    def no(why):
        raise SystemExit(f"deepseek_v2: {why}")

    if config.get("scoring_func") not in (None, "softmax"):
        no(f"scoring_func is {config['scoring_func']!r}; the runtime scores "
           "experts by a softmax")
    if config.get("topk_method", "greedy") not in ("greedy", "group_limited_greedy"):
        no(f"topk_method is {config['topk_method']!r}; the runtime chooses "
           "experts by greedy or group_limited_greedy (no correction bias)")
    if config.get("norm_topk_prob", False):
        no("norm_topk_prob is true; the runtime scales the chosen "
           "probabilities by routed_scaling_factor and does not renormalise them")
    if config.get("moe_layer_freq") not in (None, 1):
        no(f"moe_layer_freq is {config['moe_layer_freq']}; the runtime has an "
           "expert FFN in every layer past first_k_dense_replace")
    if config.get("attention_bias", False):
        no("attention_bias is true; the .m format has no bias tensors")
    if config.get("q_lora_rank") is None:
        no("q_lora_rank is null; the runtime projects q through its latent "
           "(wq_a, q_a_norm, wq_b)")
    scaling = config.get("rope_scaling")
    if scaling is not None and scaling.get("type", scaling.get("rope_type")) != "yarn":
        no(f"rope_scaling type is {scaling.get('type', scaling.get('rope_type'))!r}; "
           "the runtime computes yarn or unscaled RoPE")


def _deepseek2_fields(config: dict) -> dict:
    """The header's keys 14..31 from a ``deepseek_v2`` config.json."""
    _refuse_deepseek2_variants(config)
    scaling = config.get("rope_scaling") or {}
    grouped = config.get("topk_method", "greedy") == "group_limited_greedy"
    return dict(
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config.get("n_shared_experts") or 0,
        n_groups=config["n_group"] if grouped else 1,
        topk_groups=config["topk_group"] if grouped else 1,
        n_dense_layers=config.get("first_k_dense_replace", 0),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        rope_factor=float(scaling.get("factor", 1.0)),
        rope_orig_seq_len=int(scaling.get(
            "original_max_position_embeddings", 0)),
        rope_beta_fast=float(scaling.get("beta_fast", 32)),
        rope_beta_slow=float(scaling.get("beta_slow", 1)),
        rope_mscale=float(scaling.get("mscale", 1.0)),
        rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 0.0)),
        norm_eps=float(config.get("rms_norm_eps", 1e-6)))


def _smallthinker_fields(config: dict) -> dict:
    """The header's keys 31..34 from a ``smallthinker`` config.json.
    ``ARCH_SMALLTHINKER`` is one block: a softmax over the chosen primary
    experts' logits, whole periods of one full (unrotated) layer and then
    window layers that are exactly the rotated ones, ReLU, unscaled RoPE.  What
    the runtime does not compute is refused by name."""
    def no(why):
        raise SystemExit(f"smallthinker: {why}")

    if not config.get("moe_primary_router_apply_softmax", False):
        no("moe_primary_router_apply_softmax is false (a sigmoid router); the "
           "runtime softmaxes the chosen experts' logits")
    if not config.get("norm_topk_prob", True):
        no("norm_topk_prob is false; the runtime's chosen weights sum to 1")
    if config.get("moe_enable_secondary_experts") or config.get(
            "moe_num_secondary_experts") or config.get("moe_secondary_ffn_hidden_size"):
        no("secondary experts are configured; the runtime has primary experts only")
    if config.get("rope_scaling") is not None:
        no(f"rope_scaling is {config['rope_scaling']}; the runtime's RoPE is unscaled")
    if config.get("tie_word_embeddings", False):
        no("tie_word_embeddings is true; the .m format has a head of its own")
    layers = config["num_hidden_layers"]
    layout = [int(v) for v in config["sliding_window_layout"]]
    if [int(v) for v in config.get("rope_layout", layout)] != layout:
        no("rope_layout differs from sliding_window_layout; the runtime rotates "
           "exactly its window layers")
    period = layout[1:].index(0) + 1 if 0 in layout[1:] else 0
    if period < 2 or layers % period or layout != ([0] + [1] * (period - 1)) * (
            layers // period):
        no(f"sliding_window_layout {layout} is not whole periods of one full "
           "layer and then window layers")
    return dict(norm_eps=float(config.get("rms_norm_eps", 1e-6)),
                head_dim=int(config["head_dim"]),
                window=int(config["sliding_window_size"]), window_period=period)


def _exaone_moe_fields(config: dict, experts_held: int, first_expert: int) -> dict:
    """The header's keys past the fourteen from an ``exaone_moe`` config.json
    (K-EXAONE) and the share asked for.  ``ARCH_EXAONE_MOE`` is one block: a
    sigmoid router over one group with a choice bias, the chosen scores
    normalised and scaled, whole periods of window layers with one full layer,
    a dense prefix, unscaled RoPE.  What the runtime does not compute is
    refused by name."""
    def no(why):
        raise SystemExit(f"exaone_moe: {why}")

    if config.get("scoring_func", "sigmoid") != "sigmoid":
        no(f"scoring_func is {config['scoring_func']!r}; the runtime's router "
           "for this architecture is a sigmoid")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        no(f"n_group is {config.get('n_group')} and topk_group "
           f"{config.get('topk_group')}; the runtime chooses over all experts "
           "at once (one group)")
    if not config.get("norm_topk_prob", True):
        no("norm_topk_prob is false; the runtime normalises the chosen scores")
    if config.get("tie_word_embeddings", False):
        no("tie_word_embeddings is true; the .m format has a head of its own")
    if config.get("hidden_act", "silu") != "silu":
        no(f"hidden_act is {config['hidden_act']!r}; the runtime's experts are SwiGLU")
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        no(f"rope_type is {rope.get('rope_type')!r}; the runtime's RoPE is unscaled")
    layers = config["num_hidden_layers"]
    kinds = [t == "full_attention" for t in config["layer_types"]]
    at = kinds.index(True) if True in kinds else -1
    period = kinds[at + 1:].index(True) + 1 if True in kinds[at + 1:] else 0
    if len(kinds) != layers or period < 2 or layers % period or kinds != [
            j == at for j in range(period)] * (layers // period):
        no(f"layer_types {config['layer_types']} is not whole periods of "
           "sliding_attention layers with one full_attention layer")
    window = int(config["sliding_window"])
    if "sliding_windows" in config and list(config["sliding_windows"]) != [
            0 if k else window for k in kinds]:
        no("sliding_windows is not sliding_window in the window layers and 0 "
           "in the full ones")
    dense = int(config.get("first_k_dense_replace", 0))
    if "mlp_layer_types" in config and [
            m == "dense" for m in config["mlp_layer_types"]] != [
            i < dense for i in range(layers)]:
        no("mlp_layer_types is not first_k_dense_replace dense layers and "
           "then sparse ones")
    n = int(config["num_experts"])
    held = experts_held or n
    if not (1 <= held <= n and 0 <= first_expert <= n - held):
        no(f"--experts-held {experts_held} --first-expert {first_expert} is not "
           f"a run of the {n} experts")
    return dict(moe_hidden_dim=config["moe_intermediate_size"],
                n_shared_experts=config.get("num_shared_experts") or 0,
                n_groups=1, topk_groups=1, n_dense_layers=dense,
                routed_scale=float(config.get("routed_scaling_factor", 1.0)),
                norm_eps=float(config.get("rms_norm_eps", 1e-5)),
                head_dim=int(config["head_dim"]), window=window,
                window_period=period, window_full_at=at,
                experts_held=0 if held == n else held, first_expert=first_expert)


def _lfm2_moe_fields(config: dict) -> dict:
    """The header's keys past the fourteen from an ``lfm2_moe`` config.json.
    ``ARCH_LFM2_MOE`` is one block: whole periods of ``conv`` layers with one
    ``full_attention`` layer, a bias-free convolution, a sigmoid router with a
    choice bias whose chosen scores are normalised, a dense prefix, unscaled
    RoPE.  What the runtime does not compute is refused by name."""
    def no(why):
        raise SystemExit(f"lfm2_moe: {why}")

    if config.get("conv_bias", False):
        no("conv_bias is true; the runtime's convolution and its projections "
           "have no bias")
    if not config.get("norm_topk_prob", True):
        no("norm_topk_prob is false; the runtime normalises the chosen scores")
    if not config.get("use_expert_bias", True):
        no("use_expert_bias is false; the runtime's router for this "
           "architecture adds a choice bias (the .m file carries one)")
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        no(f"rope_type is {rope.get('rope_type')!r}; the runtime's RoPE is unscaled")
    layers = config["num_hidden_layers"]
    types = list(config["layer_types"])
    kinds = [t == "full_attention" for t in types]
    at = kinds.index(True) if True in kinds else -1
    period = kinds[at + 1:].index(True) + 1 if True in kinds[at + 1:] else 0
    if set(types) - {"conv", "full_attention"} or len(kinds) != layers \
            or period < 2 or layers % period or kinds != [
            j == at for j in range(period)] * (layers // period):
        no(f"layer_types {types} is not whole periods of conv layers with one "
           "full_attention layer")
    n, k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    if not 1 <= k <= n:
        no(f"num_experts_per_tok {k} is more than num_experts {n}")
    dense = int(config.get("num_dense_layers", 0))
    if not 0 <= dense < layers:
        no(f"num_dense_layers {dense} leaves no expert layer of {layers}")
    if config["hidden_size"] % config["num_attention_heads"]:
        no("hidden_size is not a multiple of num_attention_heads")
    return dict(moe_hidden_dim=config["moe_intermediate_size"],
                n_dense_layers=dense,
                routed_scale=float(config.get("routed_scaling_factor", 1.0)),
                norm_eps=float(config.get("norm_eps", 1e-5)),
                head_dim=config["hidden_size"] // config["num_attention_heads"],
                window_period=period, window_full_at=at,
                conv_taps=int(config["conv_L_cache"]))


def _brumby_fields(config: dict) -> dict:
    """The header's keys past the fourteen from a ``brumby`` config.json.
    ``ARCH_BRUMBY`` is one block: Qwen3's bias-free projections with a head
    size of ``hidden_size / num_attention_heads``, unscaled RoPE, no sliding
    window, an untied head, power retention of degree 2 (the release's; no key
    of config.json states it).  What the file cannot carry is refused by name."""
    def no(why):
        raise SystemExit(f"brumby: {why}")

    if config.get("attention_bias", False):
        no("attention_bias is true; the .m file has no projection bias")
    if config.get("use_sliding_window", False):
        no("use_sliding_window is true; a retention layer has no window")
    if config.get("rope_scaling"):
        no(f"rope_scaling is {config['rope_scaling']!r}; the runtime's RoPE is unscaled")
    if config.get("tie_word_embeddings", False):
        no("tie_word_embeddings is true; the published head is its own tensor")
    heads = config["num_attention_heads"]
    if config.get("head_dim", config["hidden_size"] // heads) * heads \
            != config["hidden_size"]:
        no("head_dim * num_attention_heads is not hidden_size; a brumby .m "
           "file states no head size")
    return dict(norm_eps=float(config.get("rms_norm_eps", 1e-6)),
                retention_degree=2)


def _ouro_fields(config: dict) -> dict:
    """The header's keys past the fourteen from an ``ouro`` config.json: the
    norm's eps and the passes (``total_ut_steps``).  The exit gate is not in the
    file: at ``early_exit_threshold`` 1 every token runs every pass, and another
    threshold is another function (a scheduler's and a sampler's), refused by
    name, as is what the block does not have."""
    def no(why):
        raise SystemExit(f"ouro: {why}")

    if config.get("early_exit_threshold", 1) != 1:
        no(f"early_exit_threshold is {config['early_exit_threshold']!r}; the "
           "exit gate is not computed, so only the published threshold of 1 "
           "(every token runs every pass) is the same function")
    if config.get("use_sliding_window", False) or config.get("rope_scaling") \
            or config.get("tie_word_embeddings", False) \
            or config.get("attention_bias", False):
        no("a sliding window, rope_scaling, a tied head and a projection bias "
           "are not part of this block")
    heads = config["num_attention_heads"]
    if config.get("head_dim", config["hidden_size"] // heads) * heads \
            != config["hidden_size"]:
        no("head_dim * num_attention_heads is not hidden_size; an ouro .m "
           "file states no head size")
    return dict(norm_eps=float(config.get("rms_norm_eps", 1e-6)),
                loops=int(config["total_ut_steps"]))


def _falcon_h1_fields(config: dict) -> dict:
    """The header's keys past the fourteen from a ``falcon_h1`` config.json:
    the attention head size, the mixer's sizes, every multiplier, and
    ``rope_theta`` as a float.  What the file cannot carry is refused by name."""
    def no(why):
        raise SystemExit(f"falcon_h1: {why}")

    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias", "rope_scaling", "tie_word_embeddings",
                "mamba_norm_before_gate", "attn_layer_indices"):
        if config.get(key):
            no(f"{key} is {config[key]!r}; this block has no such thing")
    for key in ("mamba_conv_bias", "mamba_rms_norm", "mamba_use_mlp"):
        if not config.get(key, True):
            no(f"{key} is false; the .m file always has the tensors")
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * dh != config["mamba_d_ssm"]:
        no("mamba_n_heads * mamba_d_head is not mamba_d_ssm")
    mup = dict(zip(mfile.SSM_MUP, config["ssm_multipliers"]))
    gate, down = config["mlp_multipliers"]
    return dict(
        norm_eps=float(config.get("rms_norm_eps", 1e-5)),
        head_dim=int(config["head_dim"]), ssm_heads=heads, ssm_head_dim=dh,
        ssm_state=int(config["mamba_d_state"]),
        ssm_groups=int(config["mamba_n_groups"]),
        ssm_conv=int(config["mamba_d_conv"]),
        mup_embedding=config["embedding_multiplier"],
        mup_head=config["lm_head_multiplier"],
        mup_attn_in=config["attention_in_multiplier"],
        mup_attn_out=config["attention_out_multiplier"],
        mup_ssm_in=config["ssm_in_multiplier"],
        mup_ssm_out=config["ssm_out_multiplier"],
        mup_key=config["key_multiplier"], mup_gate=gate, mup_down=down, **mup)


def _granite_hybrid_fields(config: dict) -> dict:
    """The header's keys past the fourteen from a ``granitemoehybrid``
    config.json: the expert's and the shared MLP's widths, the period and the
    attention layer's place in it, the mixer's sizes and the four scalars
    (``attention_multiplier`` as the key's multiplier under the usual
    ``head^-1/2``; ``logits_scaling`` as its inverse).  What the file cannot
    carry is refused by name."""
    def no(why):
        raise SystemExit(f"granitemoehybrid: {why}")

    if config.get("position_embedding_type", "nope") != "nope":
        no(f"position_embedding_type is {config['position_embedding_type']!r}; "
           "the runtime rotates nothing in this architecture")
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling"):
        if config.get(key):
            no(f"{key} is {config[key]!r}; the .m file has no projection bias "
               "and the runtime no scaled positions")
    if not config.get("mamba_conv_bias", True):
        no("mamba_conv_bias is false; the .m file always has the tensor")
    if int(config.get("mamba_n_groups", 1)) != 1:
        no(f"mamba_n_groups is {config['mamba_n_groups']}; more than one mixer "
           "group has a norm a group, and this file's gated norm is ONE over "
           "all of the mixer's channels")
    if config.get("normalization_function", "rmsnorm") != "rmsnorm" \
            or config.get("hidden_act", "silu") != "silu":
        no("the layer is RMSNorm and silu")
    layers = config["num_hidden_layers"]
    types = list(config["layer_types"])
    kinds = [t == "attention" for t in types]
    at = kinds.index(True) if True in kinds else -1
    period = kinds[at + 1:].index(True) + 1 if True in kinds[at + 1:] else layers
    if set(types) - {"mamba", "attention"} or len(kinds) != layers or at < 0 \
            or period < 2 or layers % period or kinds != [
            j == at for j in range(period)] * (layers // period):
        no(f"layer_types {types} is not whole periods of mamba layers with one "
           "attention layer")
    heads, dh = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if heads * dh != int(config.get("mamba_expand", 2)) * config["hidden_size"]:
        no("mamba_n_heads * mamba_d_head is not mamba_expand * hidden_size")
    f, fs = int(config["intermediate_size"]), int(config["shared_intermediate_size"])
    if fs % f:
        no(f"shared_intermediate_size {fs} is not a whole number of experts' "
           f"widths ({f})")
    if config["hidden_size"] % config["num_attention_heads"]:
        no("hidden_size is not a multiple of num_attention_heads")
    head = config["hidden_size"] // config["num_attention_heads"]
    return dict(
        moe_hidden_dim=f, n_shared_experts=fs // f,
        norm_eps=float(config.get("rms_norm_eps", 1e-5)), head_dim=head,
        window_period=period, window_full_at=at, ssm_heads=heads,
        ssm_head_dim=dh, ssm_state=int(config["mamba_d_state"]), ssm_groups=1,
        ssm_conv=int(config["mamba_d_conv"]),
        mup_embedding=float(config["embedding_multiplier"]),
        mup_head=1.0 / float(config["logits_scaling"]),
        mup_key=float(config["attention_multiplier"]) * float(np.sqrt(head)),
        # residual_multiplier stands on each branch's output
        **dict.fromkeys(("mup_attn_out", "mup_ssm_out", "mup_down"),
                        float(config["residual_multiplier"])))


def load_spec(folder: str, weights_ftype: int, experts_held: int = 0,
              first_expert: int = 0) -> mfile.ModelSpec:
    with open(os.path.join(folder, "config.json")) as f:
        config = json.load(f)
    arch = ARCH_BY_MODEL_TYPE.get(config["model_type"])
    if arch is None:
        raise SystemExit(f"Unsupported arch type: {config['model_type']}")
    if arch == mfile.ARCH_OLMOE:
        _refuse_olmoe_variants(config)
    ext = _deepseek2_fields(config) if arch == mfile.ARCH_DEEPSEEK2 else {}
    if arch == mfile.ARCH_SMALLTHINKER:
        ext = _smallthinker_fields(config)
        config = dict(config, intermediate_size=config["moe_ffn_hidden_size"],
                      hidden_act="relu")
    if arch == mfile.ARCH_LFM2_MOE and not (experts_held or first_expert):
        ext = _lfm2_moe_fields(config)
    if arch == mfile.ARCH_BRUMBY:
        ext = _brumby_fields(config)
    if arch == mfile.ARCH_OURO:
        ext = _ouro_fields(config)
    if arch == mfile.ARCH_FALCON_H1:
        ext = _falcon_h1_fields(config)
    if arch == mfile.ARCH_GRANITE_HYBRID:
        ext = _granite_hybrid_fields(config)
        # the header's hidden_dim is the shared MLP's width
        config = dict(config, intermediate_size=config["shared_intermediate_size"])
    if arch in (mfile.ARCH_EXAONE_MOE, mfile.ARCH_LFM2_MOE):
        config = dict(config, rope_theta=(config.get("rope_parameters") or {}).get(
            "rope_theta", config.get("rope_theta", 10000.0)))
    if arch == mfile.ARCH_EXAONE_MOE:
        ext = _exaone_moe_fields(config, experts_held, first_expert)
    elif experts_held or first_expert:
        raise SystemExit("--experts-held / --first-expert write a share of an "
                         "exaone_moe model's experts; this is "
                         f"{config['model_type']}")
    # Mixtral's key, then OLMoE's, then DeepSeek-V2's, then SmallThinker's
    n_experts = (config.get("num_local_experts") or config.get("num_experts")
                 or config.get("n_routed_experts")
                 or config.get("moe_num_primary_experts") or 0)
    n_active = (config.get("num_active_local_experts")
                or config.get("num_experts_per_tok")
                or config.get("moe_num_active_primary_experts") or 0)
    return mfile.ModelSpec(
        arch=arch,
        dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_experts=int(n_experts),
        n_active_experts=int(n_active),
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        hidden_act=HIDDEN_ACT[config.get("hidden_act", "silu")],
        rope_theta=float(config.get("rope_theta", 10000.0)),
        weights_ftype=weights_ftype, **ext)


class SafetensorsStore:
    """Lazy multi-file tensor lookup over a model folder."""

    def __init__(self, folder: str):
        from safetensors import safe_open
        self._handles = {}
        self._index: dict[str, str] = {}
        for name in sorted(os.listdir(folder)):
            if name.endswith(".safetensors"):
                path = os.path.join(folder, name)
                h = safe_open(path, framework="np", device="cpu")
                self._handles[path] = h
                for key in h.keys():
                    self._index[key] = path
        if not self._handles:
            raise SystemExit("Not found any model file")

    def has(self, suffix: str) -> bool:
        return any(k.endswith(suffix) for k in self._index)

    def get(self, key: str) -> np.ndarray:
        path = self._index.get(key)
        if path is None:
            raise SystemExit(f"Layer {key} not found")
        t = self._handles[path].get_tensor(key)
        if t.dtype == np.uint16:  # bfloat16 stored raw
            import jax.numpy as jnp
            t = np.asarray(jnp.asarray(t.view(jnp.bfloat16), jnp.float32))
        return np.asarray(t, dtype=np.float32)


# an lfm2_moe layer's tensors under ``model.layers.N.`` (transformers'
# Lfm2Moe names; the experts' w1 / w3 / w2 are gate / up / down)
_LFM2_LEAVES = {
    "conv_in": "conv.in_proj", "conv_taps": "conv.conv", "conv_out": "conv.out_proj",
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
    "wo": "self_attn.out_proj", "q_norm": "self_attn.q_layernorm",
    "k_norm": "self_attn.k_layernorm", "rms_att": "operator_norm",
    "rms_ffn": "ffn_norm", "w1": "feed_forward.w1", "w2": "feed_forward.w2",
    "w3": "feed_forward.w3", "moe_router": "feed_forward.gate",
}
# ASSUMED: a brumby layer's tensors under ``model.layers.N.``: Qwen3's names
# (its per-head norms and MLP) with the gate beside the projections.  Unverified
# until the published files are in the repository: a name that differs shows
# as "Layer ... not found" on the first tensor it concerns
_BRUMBY_LEAVES = {
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
    "wo": "self_attn.o_proj", "wg": "self_attn.g_proj",
    "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
    "rms_att": "input_layernorm", "rms_ffn": "post_attention_layernorm",
    "w1": "mlp.gate_proj", "w2": "mlp.down_proj", "w3": "mlp.up_proj",
}
# ASSUMED: an ouro layer's tensors under ``model.layers.N.``: Llama's names and
# the two norms that close a branch.  Unverified until the published files are
# in the repository: a name that differs shows as "Layer ... not found" on the
# first tensor it concerns.  The four norms land in a Grok-1 file's slots
_OURO_LEAVES = {
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
    "wo": "self_attn.o_proj", "w1": "mlp.gate_proj", "w2": "mlp.down_proj",
    "w3": "mlp.up_proj", "rms_att": "input_layernorm",
    "rms_ffn": "input_layernorm_2", "rms_moe": "post_attention_layernorm",
    "rms_ffn2": "post_attention_layernorm_2",
}
_DEEPSEEK2_LEAVES = {
    "wq_a": "self_attn.q_a_proj", "q_a_norm": "self_attn.q_a_layernorm",
    "wq_b": "self_attn.q_b_proj", "wkv_a": "self_attn.kv_a_proj_with_mqa",
    "kv_a_norm": "self_attn.kv_a_layernorm", "wkv_b": "self_attn.kv_b_proj",
    "shared_w1": "mlp.shared_experts.gate_proj",
    "shared_w2": "mlp.shared_experts.down_proj",
    "shared_w3": "mlp.shared_experts.up_proj",
}


# ASSUMED: a falcon_h1 layer's tensors under ``model.layers.N.`` (the published
# ``modeling_falcon_h1.py``'s module names as the builder knows them; the
# mixer's vectors carry no ``.weight``).  ``ssm_in`` and ``ssm_dt`` are both
# rows of ``mamba.in_proj.weight`` (:func:`_falcon_h1_rows`).  Unverified until
# the published files are in the repository
_FALCON_H1_LEAVES = {
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "ssm_in": "mamba.in_proj.weight", "ssm_dt": "mamba.in_proj.weight",
    "ssm_conv_w": "mamba.conv1d.weight", "ssm_conv_b": "mamba.conv1d.bias",
    "ssm_a_log": "mamba.A_log", "ssm_dt_bias": "mamba.dt_bias",
    "ssm_d": "mamba.D", "ssm_norm": "mamba.norm.weight",
    "ssm_out": "mamba.out_proj.weight",
    "w1": "feed_forward.gate_proj.weight", "w2": "feed_forward.down_proj.weight",
    "w3": "feed_forward.up_proj.weight",
    "rms_att": "input_layernorm.weight", "rms_ffn": "pre_ff_layernorm.weight",
}


# ASSUMED: a granitemoehybrid layer's tensors under ``model.layers.N.``
# (transformers' GraniteMoeHybrid module names as the builder knows them; the
# mixer's as Falcon-H1's).  The experts are ONE stacked tensor a projection,
# ``input_linear`` (E, 2 F, D) with each expert's rows ``gate | up`` and
# ``output_linear`` (E, D, F); the shared MLP's ``input_linear`` (2 Fs, D) is
# ``gate | up`` likewise (:func:`_granite_rows`).  Unverified until the
# published files are in the repository
_GRANITE_LEAVES = dict(
    {k: v for k, v in _FALCON_H1_LEAVES.items() if k.startswith(("w", "ssm_"))
     and k not in ("w1", "w2", "w3")},
    moe_router="block_sparse_moe.router.layer.weight",
    gate="block_sparse_moe.input_linear.weight",
    up="block_sparse_moe.input_linear.weight",
    down="block_sparse_moe.output_linear.weight",
    shared_w1="shared_mlp.input_linear.weight",
    shared_w3="shared_mlp.input_linear.weight",
    shared_w2="shared_mlp.output_linear.weight",
    rms_att="input_layernorm.weight", rms_ffn="post_attention_layernorm.weight")


def _granite_rows(name: str, t, spec: mfile.ModelSpec):
    """The rows of a published tensor that the plan's tensor ``name`` holds:
    ``in_proj``'s as Falcon-H1's, an expert's plane of the stacked experts and
    the ``gate | up`` halves of an ``input_linear``."""
    parts = name.split(".")
    leaf = parts[-1]
    if len(parts) > 3 and parts[2] == "experts":
        t = t[int(parts[3])]
        f = spec.moe_hidden_dim
        return {"gate": t[:f], "up": t[f:]}.get(leaf, t)
    fs = spec.hidden_dim
    return {"shared_w1": t[:fs], "shared_w3": t[fs:]}.get(
        leaf, _falcon_h1_rows(leaf, t, spec))


def _falcon_h1_rows(leaf: str, t, spec: mfile.ModelSpec):
    """``in_proj``'s rows ``z | x | B | C`` for ``ssm_in``, its last
    ``ssm_heads`` rows (``dt``) for ``ssm_dt``; every other tensor whole."""
    cut = spec.ssm_inner + spec.ssm_channels
    return {"ssm_in": t[:cut], "ssm_dt": t[cut:]}.get(leaf, t)


def hf_source_name(our_name: str, spec: mfile.ModelSpec) -> tuple[str, bool]:
    """Map a `.m` plan tensor name to its HF key; returns (key, permute?)."""
    if our_name == "token_embedding":
        return "model.embed_tokens.weight", False
    if our_name == "rms_final":
        return {mfile.ARCH_LFM2_MOE: "model.embedding_norm.weight",
                mfile.ARCH_FALCON_H1: "model.final_layernorm.weight"}.get(
                    spec.arch, "model.norm.weight"), False
    if our_name == "wcls":
        return "lm_head.weight", False
    parts = our_name.split(".")
    li = parts[1]
    leaf = parts[-1]
    base = f"model.layers.{li}"
    if spec.arch == mfile.ARCH_LFM2_MOE:  # rows as published: halves rotate
        if leaf == "moe_router_bias":
            return f"{base}.feed_forward.expert_bias", False
        if parts[2] == "experts":
            hf_leaf = {"up": "w3", "gate": "w1", "down": "w2"}[leaf]
            return f"{base}.feed_forward.experts.{parts[3]}.{hf_leaf}.weight", False
        return f"{base}.{_LFM2_LEAVES[leaf]}.weight", False
    if spec.arch == mfile.ARCH_BRUMBY:  # rows as published: halves rotate
        return f"{base}.{_BRUMBY_LEAVES[leaf]}.weight", False
    if spec.arch == mfile.ARCH_OURO:    # rows as published: halves rotate
        return f"{base}.{_OURO_LEAVES[leaf]}.weight", False
    if spec.arch == mfile.ARCH_FALCON_H1:  # rows as published: halves rotate
        return f"{base}.{_FALCON_H1_LEAVES[leaf]}", False
    if spec.arch == mfile.ARCH_GRANITE_HYBRID:  # rows as published: no rotation
        return f"{base}.{_GRANITE_LEAVES[leaf]}", False
    # rows as published: these runtimes rotate halves, as HF does
    olmoe = spec.arch in (mfile.ARCH_OLMOE, mfile.ARCH_SMALLTHINKER,
                          mfile.ARCH_EXAONE_MOE)
    # the arch ids whose HF experts are mlp.experts.N.{gate,up,down}_proj
    mlp_experts = spec.arch in (mfile.ARCH_OLMOE, mfile.ARCH_DEEPSEEK2,
                                mfile.ARCH_EXAONE_MOE)
    if leaf in _DEEPSEEK2_LEAVES:  # kv_b_proj whole, rows as published
        return f"{base}.{_DEEPSEEK2_LEAVES[leaf]}.weight", False
    if leaf == "wq":
        return f"{base}.self_attn.q_proj.weight", not olmoe
    if leaf == "wk":
        return f"{base}.self_attn.k_proj.weight", not olmoe
    if leaf in ("q_norm", "k_norm"):
        return f"{base}.self_attn.{leaf}.weight", False
    if leaf == "wv":
        return f"{base}.self_attn.v_proj.weight", False
    if leaf == "wo":
        return f"{base}.self_attn.o_proj.weight", False
    if leaf == "rms_att":
        return f"{base}.input_layernorm.weight", False
    if leaf == "rms_ffn":
        return f"{base}.post_attention_layernorm.weight", False
    # dense FFN: w1=gate w2=down w3=up (convert-hf.py:77-83)
    if leaf == "w1":
        return f"{base}.mlp.gate_proj.weight", False
    if leaf == "w2":
        return f"{base}.mlp.down_proj.weight", False
    if leaf == "w3":
        return f"{base}.mlp.up_proj.weight", False
    if spec.arch == mfile.ARCH_SMALLTHINKER:
        moe = f"{base}.block_sparse_moe"
        return (f"{moe}.primary_router.weight" if leaf == "moe_router"
                else f"{moe}.experts.{parts[3]}.{leaf}.weight"), False
    if leaf == "moe_router_bias":
        return f"{base}.mlp.gate.e_score_correction_bias", False
    if parts[2] == "experts":
        # a share's file index e is the router's (and HF's) first_expert + e
        e = int(parts[3]) + spec.first_expert
        if mlp_experts:
            return f"{base}.mlp.experts.{e}.{leaf}_proj.weight", False
        hf_leaf = {"up": "w3", "gate": "w1", "down": "w2"}[leaf]
        return f"{base}.block_sparse_moe.experts.{e}.{hf_leaf}.weight", False
    if leaf == "moe_router":
        return (f"{base}.mlp.gate.weight" if mlp_experts
                else f"{base}.block_sparse_moe.gate.weight"), False
    raise SystemExit(f"no HF mapping for {our_name}")


def convert(folder: str, weights_ftype: int, out_path: str,
            experts_held: int = 0, first_expert: int = 0) -> None:
    spec = load_spec(folder, weights_ftype, experts_held, first_expert)
    store = SafetensorsStore(folder)
    if spec.arch == mfile.ARCH_EXAONE_MOE:
        mtp = sorted(k for k in store._index if k.startswith(("mtp.", "model.mtp.")))
        if mtp:
            print(f"⏭️  skipping {len(mtp)} mtp.* tensors (the multi-token-"
                  "prediction block is not computed; next-token logits do not "
                  "depend on it)")
    if spec.arch == mfile.ARCH_OURO:
        gate = sorted(k for k in store._index if "early_exit_gate" in k)
        if gate:
            print(f"⏭️  skipping {len(gate)} early_exit_gate.* tensors (at "
                  "early_exit_threshold 1 every token runs every pass; the "
                  "gate changes no logit)")
    if spec.arch == mfile.ARCH_DEEPSEEK2 and store.has("e_score_correction_bias"):
        raise SystemExit("deepseek_v2: the checkpoint has e_score_correction_bias "
                         "(V3's router); the runtime has no correction bias")
    tied = (mfile.ARCH_LFM2_MOE, mfile.ARCH_GRANITE_HYBRID)
    held = (None, None)  # the last published tensor read: a stacked one feeds many
    with mfile.MFileWriter(out_path, spec) as w:
        for item in w.plan:
            key, do_permute = hf_source_name(item.name, spec)
            if item.name == "wcls" and spec.arch in tied and not store.has(key):
                # the published model ties its head to the embedding
                key = "model.embed_tokens.weight"
            if held[0] != key:
                held = (key, store.get(key))
            t = held[1]
            if spec.arch == mfile.ARCH_FALCON_H1:
                t = _falcon_h1_rows(item.name.split(".")[-1], t, spec)
            if spec.arch == mfile.ARCH_GRANITE_HYBRID:
                t = _granite_rows(item.name, t, spec)
            if do_permute:
                heads = spec.n_heads if item.name.endswith("wq") else spec.n_kv_heads
                t = permute(t, spec.n_heads, heads)
            print(f"🔶 Writing tensor {key} {tuple(t.shape)} -> {item.name}")
            w.write_tensor(item.name, t.reshape(item.shape))
    print(f"✅ {out_path} created successfully")


def main(argv):
    if len(argv) < 3:
        print("Usage: python convert_hf.py <sourceFolderPath> <weightsFloatType> "
              "<name> [--experts-held N --first-expert I]")
        raise SystemExit(1)
    folder, ftype_name, name = argv[0], argv[1], argv[2]
    share = {"--experts-held": 0, "--first-expert": 0}
    rest = argv[3:]
    if len(rest) % 2 or any(k not in share for k in rest[::2]):
        raise SystemExit(f"unknown arguments {rest}; the flags are "
                         "--experts-held N and --first-expert I")
    share.update({k: int(v) for k, v in zip(rest[::2], rest[1::2])})
    ftype = quants.FLOAT_TYPE_BY_NAME[ftype_name]
    out = f"dllama_model_{name}_{ftype_name}.m"
    print(f"Output file: {out}")
    convert(folder, ftype, out, share["--experts-held"], share["--first-expert"])


if __name__ == "__main__":
    main(sys.argv[1:])
