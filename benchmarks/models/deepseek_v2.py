"""The DeepSeek-V2 decoder as the yardstick knows it (``harness/models.py`` has
the interface): the block the program loads as ``ARCH_DEEPSEEK2`` (0xABCD04).

Architecture (deepseek-ai/DeepSeek-V2, ``model_type: deepseek_v2``), the
equations ``last_logits`` follows, per layer, ``x`` the residual stream::

    n    = RMSNorm_att(x)
    c_q  = RMSNorm_qa(W_qa n)                      (q_lora_rank)
    q    = W_qb c_q  ->  H heads of  q_nope (nope) ‖ q_pe (rope)
    W_kva n = c_kv' (kv_lora_rank) ‖ k_pe' (rope)
    c_kv = RMSNorm_kva(c_kv');  k_pe = RoPE(k_pe'), one for all heads
    q_pe = RoPE(q_pe)
           RoPE on ADJACENT pairs (2j, 2j + 1) of the rope columns, as the
           published rows have them, at YaRN's frequencies
           inv = inter (1 - m) + extra m,  extra = theta^(-2i/rope),
           inter = extra / factor,  m = 1 - clip((i - low) / (high - low), 0, 1),
           low = floor(c(beta_fast)), high = ceil(c(beta_slow)) clamped to
           0 .. rope - 1,  c(r) = rope ln(orig / (2 pi r)) / (2 ln theta);
           cos and sin times mscale(factor, mscale) / mscale(factor,
           mscale_all_dim) (= 1 for the published 0.707 / 0.707)
    W_kvb c_kv -> H heads of  k_nope (nope) ‖ v (v_head)
    score_h = (q_nope . k_nope + q_pe . k_pe) s,
              s = (nope + rope)^-1/2 mscale^2,  mscale = 0.1 mscale_all_dim ln(factor) + 1
    h    = x + W_o concat_h softmax_causal(score_h) v_h
    m    = RMSNorm_ffn(h)
    layer < first_k_dense_replace:  out = h + W_2 (silu(W_1 m) * W_3 m)
    else:  p = softmax over ALL router logits (float32)
           a group's score = its largest p;  keep the topk_group best of the
           n_group groups, set every other expert's p to 0;  top-k of what is
           left;  weights = those p x routed_scaling_factor, NOT renormalised
           out = h + sum_e w_e expert_e(m) + shared(m)
           experts SwiGLU of moe_intermediate_size, shared one SwiGLU of
           n_shared_experts x moe_intermediate_size
    logits = W_cls RMSNorm_final(out)

RMSNorm eps ``rms_norm_eps`` (1e-6), no biases, SiLU, untied head.  Only the
expanded form is computed here: per-head keys and values from the latent, a
full-sequence causal softmax, no cache, no absorbed product.

File layout (``dllama_tpu/io/mfile.py tensor_plan`` for this arch id): in a
layer ``wq_a``, ``q_a_norm`` (f32), ``wq_b``, ``wkv_a``, ``kv_a_norm`` (f32),
``wkv_b`` whole (a head's rows: k_nope then v), ``wo``; then ``w1, w2, w3`` of
a dense layer, or ``moe_router``, each expert's ``up, gate, down`` and
``shared_w1, shared_w2, shared_w3``; then the two block norms.  The header has
the format's fourteen keys and eighteen more (14..31: the sizes below, floats
as the bits of their IEEE-754 f32); ``mformat.pack_header`` / ``read_header``
stop at key 13, so this module packs and reads its own.
"""

from __future__ import annotations

import math
import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_DEEPSEEK2 = 0xABCD04
# toy widths for --rehearse; the 160 experts in 8 groups, the 3 kept and the 6
# a token stay, as do the two layer kinds (1 dense + 2 expert layers)
REHEARSE = dict(dim=256, hidden_dim=384, n_layers=3, n_heads=8, n_kv_heads=8,
                vocab_size=2048, q_lora_rank=128, kv_lora_rank=64,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                moe_hidden_dim=64, seq_len=4096, rope_orig_seq_len=64)
# A position is margin-steady where its routing margin (``routing_margins``:
# the smaller of the group stage's and the expert stage's) exceeds this at
# every expert layer.  OLMoE's unit, not its 0.015: read against the chip's
# sweep (tools/check_routing.py, PERF.md section 6, PR 33), the 17 positions
# with a margin between 0.015 and 0.02 hold one that is 0.90 sigma off, while
# the 304 of 392 above 0.02 are within 0.12 max / 0.026 rms (the dense limits
# are 0.2 / 0.04); a chosen expert's weight is its probability x 16, so a
# flip moves the logits by whole tenths of a sigma here, not by hundredths.
MARGIN_STEADY = 0.02
# (key, name, is_float) of the header's pairs past the format's fourteen
EXT_KEYS = (
    (14, "q_lora_rank", False), (15, "kv_lora_rank", False),
    (16, "qk_nope_head_dim", False), (17, "qk_rope_head_dim", False),
    (18, "v_head_dim", False), (19, "moe_hidden_dim", False),
    (20, "n_shared_experts", False), (21, "n_groups", False),
    (22, "topk_groups", False), (23, "n_dense_layers", False),
    (24, "routed_scale", True), (25, "rope_factor", True),
    (26, "rope_orig_seq_len", False), (27, "rope_beta_fast", True),
    (28, "rope_beta_slow", True), (29, "rope_mscale", True),
    (30, "rope_mscale_all_dim", True), (31, "norm_eps", True),
)
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta") + tuple(name for _, name, _ in EXT_KEYS)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD04 (its format
    module names it).  A text probe, not an import: a checkout that lacks the
    architecture fails here, at once, before an 11.6 GB file is written for
    it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd04" in f.read().lower()
    except OSError:
        return False


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from a DeepSeek-V2 ``config.json``'s keys.
    Refuses the settings the block above does not have (they would be computed
    silently wrong), and a checkout whose program lacks the arch id."""
    def no(why):
        raise SystemExit(f"deepseek_v2: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD04 (unknown "
           "architecture): it cannot load a DeepSeek-V2 file")
    if config.get("scoring_func", "softmax") != "softmax":
        no("scoring_func is not softmax")
    if config.get("topk_method") != "group_limited_greedy":
        no("topk_method is not group_limited_greedy")
    if config.get("norm_topk_prob", False):
        no("norm_topk_prob is true: this block scales the chosen "
           "probabilities and does not renormalise them")
    if config.get("moe_layer_freq", 1) != 1:
        no("moe_layer_freq is not 1")
    if config.get("attention_bias", False):
        no("attention_bias is true: this block has no biases")
    if config.get("hidden_act", "silu") != "silu" or config.get("tie_word_embeddings"):
        no("hidden_act is not silu, or the head is tied")
    sc = config.get("rope_scaling") or {}
    if sc and sc.get("type") != "yarn":
        no("rope_scaling is not yarn")
    shp = dict(
        dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_experts=config["n_routed_experts"],
        n_active_experts=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        rope_theta=config["rope_theta"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        n_groups=config["n_group"], topk_groups=config["topk_group"],
        n_dense_layers=config["first_k_dense_replace"],
        routed_scale=float(config["routed_scaling_factor"]),
        rope_factor=float(sc.get("factor", 1.0)),
        rope_orig_seq_len=int(sc.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(sc.get("beta_fast", 32)),
        rope_beta_slow=float(sc.get("beta_slow", 1)),
        rope_mscale=float(sc.get("mscale", 1.0)),
        rope_mscale_all_dim=float(sc.get("mscale_all_dim", 0.0)),
        norm_eps=float(config["rms_norm_eps"]))
    if shp["n_kv_heads"] != shp["n_heads"]:
        no("num_key_value_heads is not num_attention_heads")
    if shp["n_experts"] % shp["n_groups"] or not (
            0 < shp["n_active_experts"]
            <= shp["topk_groups"] * (shp["n_experts"] // shp["n_groups"])):
        no("the experts do not divide into the groups, or the kept groups "
           "hold fewer than num_experts_per_tok")
    if not 0 < shp["n_dense_layers"] < shp["n_layers"]:
        no("this block wants a dense prefix and at least one expert layer")
    return shp


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_DEEPSEEK2, hidden_act=1,
                weights_ftype=Q40, rope_theta=int(shape["rope_theta"]))
    pairs = [(k, int(vals[name])) for k, name in enumerate(mformat.HEADER_KEYS)]
    pairs += [(k, _f32_bits(shape[name]) if is_f else int(shape[name]))
              for k, name, is_f in EXT_KEYS]
    data = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    return struct.pack("<ii", mformat.MAGIC, 8 + len(data)) + data


def read_header(path: str) -> dict:
    """Every key of a file this module wrote, the floats decoded."""
    with open(path, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        if magic != mformat.MAGIC:
            raise ValueError(f"{path}: not a v2 .m file")
        kv = struct.unpack(f"<{(size - 8) // 4}i", f.read(size - 8))
    ext = {k: (name, is_f) for k, name, is_f in EXT_KEYS}
    out = {}
    for k, v in zip(kv[::2], kv[1::2]):
        if k < len(mformat.HEADER_KEYS):
            out[mformat.HEADER_KEYS[k]] = v
        else:
            name, is_f = ext[k]
            out[name] = struct.unpack("<f", struct.pack("<i", v))[0] if is_f else v
    return out


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, h = shape["dim"], shape["vocab_size"], shape["n_heads"]
    r, ql = shape["kv_lora_rank"], shape["q_lora_rank"]
    dn, dr, dv = (shape["qk_nope_head_dim"], shape["qk_rope_head_dim"],
                  shape["v_head_dim"])
    f, fe = shape["hidden_dim"], shape["moe_hidden_dim"]
    fs = fe * shape["n_shared_experts"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq_a", (ql, dim), Q40), (p + "q_a_norm", (ql,), F32),
                  (p + "wq_b", (h * (dn + dr), ql), Q40),
                  (p + "wkv_a", (r + dr, dim), Q40), (p + "kv_a_norm", (r,), F32),
                  (p + "wkv_b", (h * (dn + dv), r), Q40),
                  (p + "wo", (dim, h * dv), Q40)]
        if i < shape["n_dense_layers"]:
            names += [(p + "w1", (f, dim), Q40), (p + "w2", (dim, f), Q40),
                      (p + "w3", (f, dim), Q40)]
        else:
            names.append((p + "moe_router", (shape["n_experts"], dim), Q40))
            for e in range(shape["n_experts"]):
                q = f"{p}experts.{e}."
                names += [(q + "up", (fe, dim), Q40), (q + "gate", (fe, dim), Q40),
                          (q + "down", (dim, fe), Q40)]
            names += [(p + "shared_w1", (fs, dim), Q40),
                      (p + "shared_w2", (dim, fs), Q40),
                      (p + "shared_w3", (fs, dim), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def yarn_inv_freq(hd: dict) -> np.ndarray:
    """The rope/2 rotation frequencies (float64) from a header's numbers."""
    rope, theta, factor = hd["qk_rope_head_dim"], float(hd["rope_theta"]), hd["rope_factor"]
    i = np.arange(rope // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / rope)
    if factor <= 1.0:
        return extra

    def c(rot):
        return rope * math.log(hd["rope_orig_seq_len"] / (2 * math.pi * rot)) / (
            2 * math.log(theta))

    low = max(math.floor(c(hd["rope_beta_fast"])), 0)
    high = min(math.ceil(c(hd["rope_beta_slow"])), rope - 1)
    high = high + 0.001 if high == low else high
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * (1.0 - m) + extra * m


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def _forward(model_path: str, prompts: list[list[int]], every_position: bool):
    """``(logits, margins)``: float32 logits, ``(n, vocab)`` after each
    prompt's last token or ``(n, T, vocab)`` at every position, and the
    routing margin ``(n, T, layers)``.  At an expert layer it is the smaller of
    two gaps, each a log-ratio over the standard deviation of the row's router
    logits (the unit of ``models/olmoe.py``): between the last kept group's
    score and the first dropped group's, and between the last chosen expert's
    probability and the first unchosen one's among the kept groups.  A dense
    layer routes nothing: its margin is 1e9."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in SHAPE_KEYS}))
    dim, h, eps = hd["dim"], hd["n_heads"], hd["norm_eps"]
    r, dn, dr, dv = (hd["kv_lora_rank"], hd["qk_nope_head_dim"],
                     hd["qk_rope_head_dim"], hd["v_head_dim"])
    n_exp, k_act = hd["n_experts"], hd["n_active_experts"]
    n_grp, k_grp = hd["n_groups"], hd["topk_groups"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]
    inv = yarn_inv_freq(hd)
    amp = _mscale(hd["rope_factor"], hd["rope_mscale"]) / _mscale(
        hd["rope_factor"], hd["rope_mscale_all_dim"])
    s_att = (dn + dr) ** -0.5 * _mscale(hd["rope_factor"],
                                        hd["rope_mscale_all_dim"]) ** 2
    ang = np.arange(t_len, dtype=np.float64)[:, None] * inv
    cos = jnp.asarray(np.cos(ang) * amp, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * amp, jnp.float32)

    def rms(x, g):
        return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x):  # (B, T, H, dr): adjacent pairs
        x0, x1 = x[..., 0::2], x[..., 1::2]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], -1).reshape(x.shape)

    @jax.jit
    def attention(x, wqa, gqa, wqb, wkva, gkva, wkvb, wo, g):
        b, t, _ = x.shape
        xb = rms(x, g)
        q = (rms(xb @ wqa.T, gqa) @ wqb.T).reshape(b, t, h, dn + dr)
        ckv = xb @ wkva.T
        c_kv = rms(ckv[..., :r], gkva)
        k_pe = rope(ckv[:, :, None, r:])                       # (B, T, 1, dr)
        q_pe = rope(q[..., dn:])
        kv = (c_kv @ wkvb.T).reshape(b, t, h, dn + dv)
        s = (jnp.einsum("bthd,bshd->bhts", q[..., :dn], kv[..., :dn])
             + jnp.einsum("bthd,bsd->bhts", q_pe, k_pe[:, :, 0])) * s_att
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        att = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kv[..., dn:])
        return x + att.reshape(b, t, h * dv) @ wo.T

    @jax.jit
    def dense(x, g, w1, w2, w3):
        xb = rms(x, g)
        return x + (jax.nn.silu(xb @ w1.T) * (xb @ w3.T)) @ w2.T

    @jax.jit
    def route(x, g, router):
        """The normed rows, each row's weight for every expert (B, T, E): its
        probability times the scale for the k chosen, 0 for the others; and
        the row's margin."""
        xb = rms(x, g)
        scores = xb @ router.T
        probs = jax.nn.softmax(scores, -1)
        spread = jnp.std(scores, -1)
        best = probs.reshape(*probs.shape[:-1], n_grp, n_exp // n_grp).max(-1)
        gtop, gidx = jax.lax.top_k(best, k_grp + 1)
        g_margin = (jnp.log(gtop[..., k_grp - 1]) - jnp.log(gtop[..., k_grp])) / spread
        kept = jnp.sum(jax.nn.one_hot(gidx[..., :k_grp], n_grp), -2) > 0
        masked = jnp.where(jnp.repeat(kept, n_exp // n_grp, -1), probs, 0.0)
        top, idx = jax.lax.top_k(masked, k_act + 1)
        e_margin = (jnp.log(top[..., k_act - 1]) - jnp.log(top[..., k_act])) / spread
        shares = jnp.sum(jax.nn.one_hot(idx[..., :k_act], n_exp)
                         * top[..., :k_act, None], -2) * hd["routed_scale"]
        return xb, shares, jnp.minimum(g_margin, e_margin)

    @jax.jit
    def expert(acc, xb, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.silu(xb @ gate.T) * (xb @ up.T)) @ down.T)

    @jax.jit
    def head(x, g, wcls):
        return rms(x, g) @ wcls.T

    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attention(x, w.weight(p + "wq_a"), w.vec(p + "q_a_norm"),
                          w.weight(p + "wq_b"), w.weight(p + "wkv_a"),
                          w.vec(p + "kv_a_norm"), w.weight(p + "wkv_b"),
                          w.weight(p + "wo"), w.vec(p + "rms_att"))
            if i < hd["n_dense_layers"]:
                x = dense(x, w.vec(p + "rms_ffn"), w.weight(p + "w1"),
                          w.weight(p + "w2"), w.weight(p + "w3"))
                margins.append(np.full(toks.shape, 1e9, np.float32))
                continue
            xb, shares, margin = route(x, w.vec(p + "rms_ffn"),
                                       w.weight(p + "moe_router"))
            margins.append(np.asarray(margin, np.float32))
            # one expert at a time: a layer's 160 experts are 15 GB in float32
            for e in range(n_exp):
                q = f"{p}experts.{e}."
                x = expert(x, xb, shares[..., e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
            x = expert(x, xb, jnp.ones(toks.shape, jnp.float32),
                       w.weight(p + "shared_w3"), w.weight(p + "shared_w1"),
                       w.weight(p + "shared_w2"))
        logits = head(x if every_position else x[:, -1], w.vec("rms_final"),
                      w.weight("wcls"))
        return np.asarray(logits, np.float32), np.stack(margins, -1)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: no kernels, no cache, no absorbed product, weights
    read from the same ``.m`` file the server loads, one tensor at a time;
    every expert runs over every row and a row's unchosen experts get weight
    0.  Departures from the published DeepSeek-V2: none in the block (the
    module's docstring has its equations); the depth is the file's (the
    configuration cuts it to 5 of 60); the router is read from its Q40 bytes,
    as the file stores every matrix, where the published model keeps it
    unquantised.
    """
    return _forward(model_path, prompts, every_position=False)[0]


def routing_margins(model_path: str, prompts: list[list[int]]):
    """``(logits (n, T, vocab), margins (n, T, layers))`` of the same reference
    in one pass over every position, for ``tools/check_routing.py`` and the
    CPU tests."""
    return _forward(model_path, prompts, every_position=True)


# ---- cost arithmetic: what the algorithm needs, every matrix at its Q40 bytes

def _sizes(cfg: dict) -> dict:
    """Values of: a layer's attention matrices (and of them ``W_kvb``), the
    dense FFN, a layer's router, one expert, the shared expert, the head; and
    the layer counts."""
    dim, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    kvb = r * h * (dn + dv)
    dense = cfg["first_k_dense_replace"]
    return dict(
        att=dim * ql + ql * h * (dn + dr) + dim * (r + dr) + kvb + h * dv * dim,
        kvb=kvb, dense_ffn=3 * dim * cfg["intermediate_size"],
        router=cfg["n_routed_experts"] * dim, expert=3 * dim * fe,
        shared=3 * dim * fe * cfg["n_shared_experts"],
        head=cfg["vocab_size"] * dim, layers=cfg["num_hidden_layers"],
        dense_layers=dense, moe_layers=cfg["num_hidden_layers"] - dense)


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each taking k
    of E: the expectation under uniform, independent routing (the groups are
    symmetric, so an expert is a row's with probability k/E):
    ``E (1 - (1 - k/E)^rows)``, 73.2 of 160 at 16 rows of 6."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the expert layers of a step of ``rows`` rows need, per
    chip: every expert layer's router, the experts its rows hit and the shared
    expert.  What ``serve_moe_roof_pct`` divides by the time under scope
    ``moe``."""
    s = _sizes(cfg)
    return s["moe_layers"] * (s["router"] + experts_read(cfg, rows) * s["expert"]
                              + s["shared"]) * 18 / 32 / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes a step of ``rows`` rows streams, per chip: attention of
    every layer, the dense FFN, the head, and the expert layers' share."""
    s = _sizes(cfg)
    return ((s["layers"] * s["att"] + s["dense_layers"] * s["dense_ffn"]
             + s["head"]) * 18 / 32 / chips + moe_bytes(cfg, chips, rows))


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes one cached position holds over all layers: the latent and the one
    rotated key, nothing per head (5 x 576 x 2 = 5760)."""
    return (cfg["num_hidden_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * elem_bytes / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    return (weight_bytes(cfg, chips, rows)
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def _pair_flops(cfg: dict) -> float:
    """Multiply-adds x 2 of one (query, cached position) pair in one layer, in
    the cheaper (absorbed) form: H heads score over the 576 values of a row
    and weigh its 512."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * (2 * r + dr)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    attention, the dense FFN or the router, its k experts and the shared
    expert, and the head; and scores its live context."""
    s = _sizes(cfg)
    mat = (s["layers"] * s["att"] + s["dense_layers"] * s["dense_ffn"]
           + s["moe_layers"] * (s["router"] + cfg["num_experts_per_tok"] * s["expert"]
                                + s["shared"]) + s["head"])
    return (2.0 * mat * rows
            + s["layers"] * _pair_flops(cfg) * live_context_tokens) / chips


def mla_bytes(cfg: dict, rows: float, context: float, elem_bytes: int = 2) -> float:
    """HBM bytes the latent walk and the absorb of one pure-decode step need:
    in every layer each row's ``context`` cached rows once, ``W_kvb`` once
    (Q40), and the rows' queries in and results out (bf16)."""
    s = _sizes(cfg)
    c = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    h = cfg["num_attention_heads"]
    io = rows * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                     + cfg["v_head_dim"]) * 2
    return s["layers"] * (rows * context * c * elem_bytes + s["kvb"] * 18 / 32 + io)


def mla_flops(cfg: dict, rows: float, context: float) -> float:
    """Multiply-adds x 2 of the same work: the pairs, and ``W_uk`` into each
    row's query and ``W_uv`` out of its result."""
    s = _sizes(cfg)
    return s["layers"] * rows * (_pair_flops(cfg) * context + 2.0 * s["kvb"])
