"""Ouro (``"model": "ouro"``): a looped language model (Zhu et al., "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741).  The ``.m``
layout, the plain reference and the cost arithmetic of ``ByteDance/Ouro-2.6B``.

The model as the reference computes it (``N(x; g)`` RMSNorm with weight ``g``
and eps ``rms_norm_eps``; the published tensor names in brackets)::

    h = E[token]
    for u in 0 .. total_ut_steps - 1:            # the pass
        for l in 0 .. num_hidden_layers - 1:     # the same weight sets every pass
            x = N(h; g1_l)                                   [input_layernorm]
            a = Wo_l Attn(RoPE(Wq_l x), RoPE(Wk_l x), Wv_l x)    causal, this pass's own keys
            h = h + N(a; g2_l)                               [input_layernorm_2]
            y = N(h; g3_l)                                   [post_attention_layernorm]
            h = h + N(W2_l (silu(W1_l y) * W3_l y); g4_l)    [post_attention_layernorm_2]
        h = N(h; g_final)                        # the final norm closes EVERY pass
    logits = Wcls h                              # of the last pass

The ``.m`` file: header keys 0..13, 31 (``norm_eps``) and 40 (``loops``);
``token_embedding`` (f32); per layer ``wq``, ``wk``, ``wv``, ``wo``, ``w1``,
``w2``, ``w3`` (Q40) and the four norm vectors in a Grok-1 file's slots
(``rms_att`` = g1, ``rms_ffn`` = g2, ``rms_moe`` = g3, ``rms_ffn2`` = g4); then
``rms_final`` and ``wcls`` (Q40, untied).  RoPE rotates lanes ``(j, j + 64)`` of
a head (rotate-half, as published), with the token's one position in every
pass.

Departures of the reference from the published model (``last_logits``,
``logits_at``): the weights are the seeded Q40 file's, dequantized to float32;
the whole sequence is computed in every pass, with no cache, so "pass ``u`` of
layer ``l`` attends over what pass ``u`` of layer ``l`` wrote at the earlier
positions" (the published cache index ``u * 48 + l``) is plain causal attention
over the pass's own keys; the exit gate (``early_exit_gate``, a ``Linear(2048 ->
1)``) is left out: at the published ``early_exit_threshold`` of 1 every token
runs all passes and the head reads the last, so the gate changes no logit.
"""

from __future__ import annotations

import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_OURO = 0xABCD09
ACT_SILU = 1
# toy widths for --rehearse; the four passes and the sandwich norms stay
REHEARSE = dict(dim=256, hidden_dim=512, n_layers=3, n_heads=2, n_kv_heads=2,
                vocab_size=2048)
EXT_KEYS = ((31, "norm_eps", True), (40, "loops", False))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "vocab_size", "seq_len", "rope_theta") + tuple(
                  name for _, name, _ in EXT_KEYS)


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's published keys."""
    def no(why):
        raise SystemExit(f"ouro: {why}")

    if config.get("use_sliding_window") or config.get("rope_scaling") \
            or config.get("tie_word_embeddings") or config.get("sliding_window"):
        no("a sliding window, rope_scaling and tied embeddings are not part "
           "of this block")
    if any(k != "full_attention" for k in config["layer_types"]) \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        no("every layer is full attention, one entry of layer_types a layer")
    if config["early_exit_threshold"] != 1:
        no("an early_exit_threshold under 1 lets a token leave before the last "
           "pass: the exit gate is left out of this file (threshold 1 only)")
    heads, dh = config["num_attention_heads"], config["head_dim"]
    if heads * dh != config["hidden_size"]:
        no("num_attention_heads * head_dim is not hidden_size (the .m file "
           "of this arch states no head size)")
    if heads % config["num_key_value_heads"] or config["hidden_act"] != "silu":
        no("num_attention_heads is not a multiple of num_key_value_heads, or "
           "the activation is not silu")
    return dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
                n_layers=config["num_hidden_layers"], n_heads=heads,
                n_kv_heads=config["num_key_value_heads"],
                vocab_size=config["vocab_size"],
                seq_len=config["max_position_embeddings"],
                rope_theta=config["rope_theta"],
                norm_eps=float(config["rms_norm_eps"]),
                loops=config["total_ut_steps"])


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_OURO, hidden_act=ACT_SILU,
                n_experts=0, n_active_experts=0, weights_ftype=Q40,
                rope_theta=int(shape["rope_theta"]))
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
    dim, hid, voc = shape["dim"], shape["hidden_dim"], shape["vocab_size"]
    kv = dim // shape["n_heads"] * shape["n_kv_heads"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (dim, dim), Q40), (p + "wk", (kv, dim), Q40),
                  (p + "wv", (kv, dim), Q40), (p + "wo", (dim, dim), Q40),
                  (p + "w1", (hid, dim), Q40), (p + "w2", (dim, hid), Q40),
                  (p + "w3", (hid, dim), Q40), (p + "rms_att", (dim,), F32),
                  (p + "rms_ffn", (dim,), F32), (p + "rms_moe", (dim,), F32),
                  (p + "rms_ffn2", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """The plain reference, departures in the module docstring: float32
    ``jax.numpy`` at ``default_matmul_precision("highest")``, the loop of the
    docstring over the whole sequence, no cache; weights read from the same
    ``.m`` file the server loads, one tensor at a time, every pass anew."""
    return logits_at(model_path, prompts, [len(prompts[0]) - 1])[:, 0]


def logits_at(model_path: str, prompts: list[list[int]], positions,
              act_dtype=None, passes=None) -> np.ndarray:
    """``(n, len(positions), vocab)`` of the same reference in one forward:
    the logits after the tokens at ``positions`` (the model is causal, so
    position ``j``'s are ``last_logits`` of the prompt cut after token ``j``).
    ``act_dtype``: round the residual stream and every matmul operand to this
    dtype (for the reading "the reference in the precision below": bfloat16).
    ``passes``: run this many passes where the file says ``loops`` (a WRONG
    computation, for ``check_loops.py --control``: what the comparison must
    see)."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in SHAPE_KEYS}))
    dim, hq, hkv = hd["dim"], hd["n_heads"], hd["n_kv_heads"]
    dh, eps = dim // hq, float(hd["norm_eps"])
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]
    at = jnp.asarray(list(positions), jnp.int32)

    def cut(x):  # the activation precision under test; float32 is the identity
        return x if act_dtype is None else x.astype(act_dtype).astype(jnp.float32)

    def rms(x, g):
        return cut(g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))

    def rope(x, cos, sin):  # x (B, T, H, dh); lanes (j, j + dh / 2)
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def attention(h, wq, wk, wv, wo, g1, g2):
        b, t, _ = h.shape
        x = rms(h, g1)
        q = cut(x @ wq.T).reshape(b, t, hq, dh)
        k = cut(x @ wk.T).reshape(b, t, hkv, dh)
        v = cut(x @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        q, k = cut(rope(q, cos, sin)), cut(rope(k, cos, sin))
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dh)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        att = cut(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v))
        return cut(h + rms(cut(att.reshape(b, t, hq * dh) @ wo.T), g2))

    @jax.jit
    def ffn(h, w1, w2, w3, g3, g4):
        y = rms(h, g3)
        f = cut(cut(jax.nn.silu(cut(y @ w1.T)) * cut(y @ w3.T)) @ w2.T)
        return cut(h + rms(f, g4))

    @jax.jit
    def head(x_at, g, wcls):
        return rms(x_at, g) @ wcls.T

    with jax.default_matmul_precision("highest"):
        h = cut(jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim)))
        g_final = w.vec("rms_final")
        loops = hd["loops"] if passes is None else passes
        for u in range(loops):
            for i in range(hd["n_layers"]):
                p = f"layers.{i}."
                h = attention(h, w.weight(p + "wq"), w.weight(p + "wk"),
                              w.weight(p + "wv"), w.weight(p + "wo"),
                              w.vec(p + "rms_att"), w.vec(p + "rms_ffn"))
                h = ffn(h, w.weight(p + "w1"), w.weight(p + "w2"),
                        w.weight(p + "w3"), w.vec(p + "rms_moe"),
                        w.vec(p + "rms_ffn2"))
            if u < loops - 1:
                h = rms(h, g_final)
        logits = head(h[:, at], g_final, w.weight("wcls"))
        return np.asarray(logits, np.float32)


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def _sizes(cfg: dict) -> dict:
    dim, hid = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["head_dim"] * cfg["num_key_value_heads"]
    return dict(layer=2 * dim * dim + 2 * dim * kv + 3 * dim * hid,
                head=cfg["vocab_size"] * dim, layers=cfg["num_hidden_layers"],
                loops=cfg["total_ut_steps"], kv=kv,
                att=2 * cfg["num_attention_heads"] * cfg["head_dim"])


def loop_weight_bytes(cfg: dict, chips: int = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) of the matrices one step streams,
    per chip: every layer's seven matrices ``total_ut_steps`` times (a pass
    runs every weight set again, and 1.39 GB of them do not stay in any cache
    between passes) and the head once.  What ``serve_loop_weight_roof_pct``
    divides by the time under the matmul scopes a step."""
    z = _sizes(cfg)
    return (z["loops"] * z["layers"] * z["layer"] + z["head"]) * 18 / 32 / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Bytes of weights a step streams, per chip; the same for any ``rows``."""
    return loop_weight_bytes(cfg, chips)


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds, per chip: a plane a (pass,
    layer), ``total_ut_steps * num_hidden_layers`` of them (1,572,864 B for
    Ouro-2.6B in bfloat16)."""
    z = _sizes(cfg)
    return 2 * z["loops"] * z["layers"] * z["kv"] * elem_bytes / chips


def kv_read_bytes(cfg: dict, context: float, chips: int = 1,
                  elem_bytes: int = 2, rows: float = 1) -> float:
    """Bytes of live keys and values ``rows`` decoded tokens, each at
    ``context`` positions, must read: every live position in every one of the
    ``total_ut_steps * num_hidden_layers`` planes.  What
    ``serve_attn_kv_roof_pct`` divides by the time under scope ``attn`` a
    step."""
    return kv_bytes_per_token(cfg, chips, elem_bytes) * context * rows


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: every weight
    ``total_ut_steps`` times and the head once, plus the live context of every
    row (``live_context_tokens`` summed over rows) in every plane."""
    return (loop_weight_bytes(cfg, chips)
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    every layer ``total_ut_steps`` times and through the head once, and scores
    its live context in every plane."""
    z = _sizes(cfg)
    mat = z["loops"] * z["layers"] * z["layer"] + z["head"]
    att = z["loops"] * z["layers"] * z["att"]
    return 2.0 * (mat * rows + att * live_context_tokens) / chips
