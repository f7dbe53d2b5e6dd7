"""The plain reference: the dense llama-style decoder block in straightforward
float32 ``jax.numpy``: no kernels, no cache, no batching tricks, matmuls at
``jax.default_matmul_precision("highest")``.  Weights are read from the same
``.m`` file the server loads, through the benchmark's own reader
(``mformat``), and dequantized to float32 one tensor at a time.

Architecture (Mistral-7B, Yi-34B and every other model of this shape):
pre-norm residual blocks; RMSNorm with eps 1e-5 after the mean; grouped-query
attention with 1/sqrt(head) scaling and a causal mask; SwiGLU feed-forward
``w2(silu(w1 x) * w3 x)``; final RMSNorm and an untied output head.

One departure from the published models, forced by the file format and not by
this benchmark: a ``.m`` file stores wq/wk with their rows permuted so that
rotary embedding pairs *adjacent* lanes (2j, 2j+1) of a head instead of lanes
(j, j + head/2).  The two are the same function of the published weights; for
seeded random weights the file *is* the model, so the reference rotates
adjacent pairs as the format defines.
"""

from __future__ import annotations

import numpy as np

from . import mformat

RMS_EPS = 1e-5


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length."""
    import jax
    import jax.numpy as jnp

    hd = mformat.read_header(model_path)
    shape = {k: hd[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                                "n_kv_heads", "vocab_size", "seq_len",
                                "rope_theta")}
    tensors = {t[0]: t for t in mformat.plan(shape)}
    mm = np.memmap(model_path, np.uint8, "r")
    dim, hq, hkv = hd["dim"], hd["n_heads"], hd["n_kv_heads"]
    dh = dim // hq
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def raw(name):
        _, _, _, off, nbytes = tensors[name]
        return np.asarray(mm[off:off + nbytes])

    @jax.jit
    def deq(blocks):  # (n_blocks, 18) uint8 -> (n_blocks, 32) float32
        scale = jax.lax.bitcast_convert_type(blocks[:, :2], jnp.float16)
        q = blocks[:, 2:]
        vals = jnp.concatenate([(q & 0xF).astype(jnp.int8) - 8,
                                (q >> 4).astype(jnp.int8) - 8], axis=1)
        return vals.astype(jnp.float32) * scale.astype(jnp.float32)[:, None]

    def weight(name):  # float32 (d_out, n_in) on the device
        shp = tensors[name][1]
        return deq(jnp.asarray(raw(name).reshape(-1, mformat.Q40_BLOCK))
                   ).reshape(shp)

    def vec(name):
        return jnp.asarray(raw(name).view(np.float32))

    def rms(x, w):
        return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)

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

    emb = tensors["token_embedding"]
    table = np.memmap(model_path, np.float32, "r", offset=emb[3], shape=emb[1])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(table[toks.reshape(-1)]).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attention(x, weight(p + "wq"), weight(p + "wk"),
                          weight(p + "wv"), weight(p + "wo"), vec(p + "rms_att"))
            x = ffn(x, weight(p + "w1"), weight(p + "w2"), weight(p + "w3"),
                    vec(p + "rms_ffn"))
        logits = head(x[:, -1], vec("rms_final"), weight("wcls"))
        return np.asarray(logits, np.float32)
