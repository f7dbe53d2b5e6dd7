"""Independent numpy implementation of the model semantics, used as the
golden oracle — the analogue of the reference's hardcoded golden floats
(llama2-tasks-test.cpp:12-525) but computed, not pasted.

Written directly from the reference task handlers' math
(llama2-tasks.cpp / grok1-tasks.cpp), with no JAX: full-sequence causal
attention, no KV cache, loops over layers/heads.  Any agreement bug between
this and dllama_tpu.models.transformer is a real finding in one of them.
"""

from __future__ import annotations

import numpy as np

RMS_EPS = 1e-5


def rmsnorm(x, w):
    ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
    return (w * (x / np.sqrt(ms + RMS_EPS))).astype(np.float32)


def softmax(x):
    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return e / e.sum(axis=-1, keepdims=True)


def silu(x):
    return x / (1.0 + np.exp(-x))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + np.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def rope_rotate(x, pos, theta, interleaved):
    """x: (T, H, D). Rotate per the convention (commands.cpp:160-229)."""
    t, h, d = x.shape
    half = d // 2
    j = np.arange(half, dtype=np.float64)
    freqs = theta ** (-2.0 * j / d)
    ang = np.asarray(pos, np.float64)[:, None] * freqs  # (T, half)
    cos, sin = np.cos(ang), np.sin(ang)
    out = np.empty_like(x)
    if interleaved:
        x0, x1 = x[..., 0::2], x[..., 1::2]
        out[..., 0::2] = x0 * cos[:, None] - x1 * sin[:, None]
        out[..., 1::2] = x0 * sin[:, None] + x1 * cos[:, None]
    else:
        x0, x1 = x[..., :half], x[..., half:]
        out[..., :half] = x0 * cos[:, None] - x1 * sin[:, None]
        out[..., half:] = x0 * sin[:, None] + x1 * cos[:, None]
    return out.astype(np.float32)


def moe(xb, router, up, gate, down, n_active, act, norm_topk_prob=True):
    """xb: (T, D). Reference routing: softmax over all experts, top-k,
    renormalize (grok1-tasks.cpp:60-114); OLMoE (``norm_topk_prob`` false)
    uses the chosen probabilities as they are."""
    t, d = xb.shape
    probs = softmax(xb @ router)  # (T, E)
    out = np.zeros_like(xb)
    for i in range(t):
        idx = np.argsort(-probs[i], kind="stable")[:n_active]
        w = probs[i, idx]
        if norm_topk_prob:
            w = w / w.sum()
        for j, e in enumerate(idx):
            h = act(xb[i] @ gate[e]) * (xb[i] @ up[e])
            out[i] += w[j] * (h @ down[e])
    return out


def np_forward(params, cfg, tokens):
    """Full-sequence forward. params: numpy dict in the runtime layout
    (input-dim-first, layer-stacked). tokens: (T,). Returns (T, V) logits."""
    from dllama_tpu.io import mfile
    act = {0: gelu_tanh, 1: silu}[cfg.hidden_act]
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    pos = np.arange(t)

    x = params["embedding"][tokens].astype(np.float32) * cfg.embedding_scale

    for li in range(cfg.n_layers):
        lp = {k: np.asarray(v[li]) for k, v in params.items()
              if k not in ("embedding", "rms_final", "wcls")}
        xb = rmsnorm(x, lp["rms_att"])
        q, k = xb @ lp["wq"], xb @ lp["wk"]
        if cfg.qk_norm:  # OLMoE: over the whole projection, before the heads
            q, k = rmsnorm(q, lp["q_norm"]), rmsnorm(k, lp["k_norm"])
        q = q.reshape(t, hq, dh)
        k = k.reshape(t, hkv, dh)
        v = (xb @ lp["wv"]).reshape(t, hkv, dh)
        q = rope_rotate(q, pos, cfg.rope_theta, cfg.rope_interleaved)
        k = rope_rotate(k, pos, cfg.rope_theta, cfg.rope_interleaved)

        # per-head causal attention with GQA grouping (llama2-tasks.cpp:54-94)
        att_out = np.zeros((t, hq, dh), np.float32)
        kv_mul = hq // hkv
        for h in range(hq):
            kh = h // kv_mul
            scores = (q[:, h] @ k[:, kh].T) / np.sqrt(dh)  # (T, T)
            mask = np.tril(np.ones((t, t), bool))
            scores = np.where(mask, scores, -np.inf)
            att_out[:, h] = softmax(scores) @ v[:, kh]
        proj = att_out.reshape(t, hq * dh) @ lp["wo"]
        if cfg.post_block_norms:
            proj = rmsnorm(proj, lp["rms_ffn"])
        x = x + proj

        if cfg.is_moe:
            pre = lp["rms_moe"] if cfg.post_block_norms else lp["rms_ffn"]
            xb = rmsnorm(x, pre)
            ff = moe(xb, lp["router"], lp["up"], lp["gate"], lp["down"],
                     cfg.n_active_experts, act, cfg.norm_topk_prob)
            if cfg.post_block_norms:
                ff = rmsnorm(ff, lp["rms_ffn2"])
        else:
            xb = rmsnorm(x, lp["rms_ffn"])
            ff = (act(xb @ lp["w1"]) * (xb @ lp["w3"])) @ lp["w2"]
        x = x + ff

    x = rmsnorm(x, np.asarray(params["rms_final"]))
    logits = (x @ params["wcls"]).astype(np.float32) * cfg.logit_scale
    return logits
