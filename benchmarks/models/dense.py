"""The dense llama-style decoder (Mistral-7B, Yi-34B and every other model of
this shape) as the yardstick knows it: the ``.m`` file's layout, the plain
reference and the cost arithmetic (``harness/models.py`` has the interface).

Architecture: pre-norm residual blocks; RMSNorm with eps 1e-5 after the mean;
grouped-query attention with 1/sqrt(head) scaling and a causal mask; SwiGLU
feed-forward ``w2(silu(w1 x) * w3 x)``; final RMSNorm and an untied output
head.
"""

from __future__ import annotations

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_LLAMA = 0xABCD00
# toy widths for --rehearse (the CPU cannot hold or time the published ones)
REHEARSE = dict(dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=4,
                vocab_size=2048)


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration file's (published) keys."""
    shp = dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
               n_layers=config["num_hidden_layers"],
               n_heads=config["num_attention_heads"],
               n_kv_heads=config["num_key_value_heads"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=config["rope_theta"])
    if shp["dim"] // shp["n_heads"] != config["head_dim"]:
        raise SystemExit("head_dim is not hidden_size / num_attention_heads")
    return shp


def header(shape: dict) -> bytes:
    return mformat.pack_header(dict(
        shape, version=1, arch=ARCH_LLAMA, n_experts=0, n_active_experts=0,
        hidden_act=1, weights_ftype=Q40, rope_theta=int(shape["rope_theta"])))


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
                  (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: no kernels, no cache, no batching tricks, weights
    read from the same ``.m`` file the server loads.  One departure from the
    published models, forced by the file format and not by this benchmark: a
    ``.m`` file stores wq/wk with their rows permuted so that rotary embedding
    pairs *adjacent* lanes (2j, 2j+1) of a head instead of lanes
    (j, j + head/2).  The two are the same function of the published weights;
    for seeded random weights the file *is* the model, so the reference
    rotates adjacent pairs as the format defines.
    """
    import jax
    import jax.numpy as jnp

    from harness import reference
    from harness.reference import rms

    hd = mformat.read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in (
        "dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads", "vocab_size",
        "seq_len", "rope_theta")}))
    dim, hq, hkv = hd["dim"], hd["n_heads"], hd["n_kv_heads"]
    dh = dim // hq
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def rope(x, cos, sin):  # x (B, T, H, dh); adjacent pairs
        x0, x1 = x[..., 0::2], x[..., 1::2]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], -1).reshape(x.shape)

    @jax.jit
    def attention(x, wq, wk, wv, wo, g):
        b, t, _ = x.shape
        xb = rms(x, g)
        q = (xb @ wq.T).reshape(b, t, hq, dh)
        k = (xb @ wk.T).reshape(b, t, hkv, dh)
        v = (xb @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        q, k = rope(q, jnp.cos(ang), jnp.sin(ang)), rope(k, jnp.cos(ang), jnp.sin(ang))
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dh)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        att = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        return x + att.reshape(b, t, hq * dh) @ wo.T

    @jax.jit
    def ffn(x, w1, w2, w3, g):
        xb = rms(x, g)
        return x + (jax.nn.silu(xb @ w1.T) * (xb @ w3.T)) @ w2.T

    @jax.jit
    def head(x_last, g, wcls):
        return rms(x_last, g) @ wcls.T

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attention(x, w.weight(p + "wq"), w.weight(p + "wk"),
                          w.weight(p + "wv"), w.weight(p + "wo"), w.vec(p + "rms_att"))
            x = ffn(x, w.weight(p + "w1"), w.weight(p + "w2"), w.weight(p + "w3"),
                    w.vec(p + "rms_ffn"))
        logits = head(x[:, -1], w.vec("rms_final"), w.weight("wcls"))
        return np.asarray(logits, np.float32)


def _matrix_values(cfg: dict) -> int:
    """Values of the matrices a token multiplies through: a step streams each
    once whatever the batch (the f32 embedding contributes only the rows
    looked up, which nothing here counts)."""
    dim, hid, voc = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["head_dim"] * cfg["num_key_value_heads"]
    per_layer = 2 * dim * dim + 2 * dim * kv + 3 * dim * hid
    return cfg["num_hidden_layers"] * per_layer + voc * dim


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) of the matrices a step streams, per
    chip; the same for any ``rows``."""
    return _matrix_values(cfg) * 18 / 32 / chips


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * elem_bytes / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights once, plus the
    live context of every row (``live_context_tokens`` summed over rows)."""
    return (weight_bytes(cfg, chips, rows)
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip."""
    att = 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * (_matrix_values(cfg) * rows + att * live_context_tokens) / chips
