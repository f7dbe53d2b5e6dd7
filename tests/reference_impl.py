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


# ---- DeepSeek-V2 (ARCH_DEEPSEEK2) -----------------------------------------
# Written from the published equations (HF modeling_deepseek.py's
# DeepseekV2Attention / MoEGate / DeepseekV2MoE), in the expanded form only:
# per-head keys and values from the latent, full-sequence causal attention, no
# cache, experts one at a time.  Shares nothing with dllama_tpu.ops.mla.

def yarn_inv_freq(dim, theta, factor, orig_len, beta_fast, beta_slow):
    """``inv = inter * (1 - m) + extra * m``, ``m = 1 - ramp(low, high)``."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    if factor <= 1.0:
        return extra
    inter = extra / factor

    def c(r):
        return dim * np.log(orig_len / (2 * np.pi * r)) / (2 * np.log(theta))

    low = max(int(np.floor(c(beta_fast))), 0)
    high = min(int(np.ceil(c(beta_slow))), dim - 1)
    if low == high:
        high = high + 0.001
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * (1.0 - m) + extra * m


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1.0 else 0.1 * m * np.log(factor) + 1.0


def rope_pairs(x, pos, inv_freq, amp=1.0):
    """Adjacent-pair rotation of x (T, H, D) at per-pair frequencies."""
    ang = np.asarray(pos, np.float64)[:, None] * inv_freq
    cos, sin = np.cos(ang)[:, None] * amp, np.sin(ang)[:, None] * amp
    out = np.empty_like(x, dtype=np.float64)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    out[..., 0::2] = x0 * cos - x1 * sin
    out[..., 1::2] = x0 * sin + x1 * cos
    return out.astype(np.float32)


def rmsnorm_eps(x, w, eps):
    ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
    return (w * (x / np.sqrt(ms + eps))).astype(np.float32)


def grouped_choice(probs, n_groups, topk_groups, k):
    """One row's experts and their probabilities: the ``topk_groups`` groups
    with the largest best-expert probability, then the top-k within them."""
    per = probs.reshape(n_groups, -1)
    groups = np.argsort(-per.max(-1), kind="stable")[:topk_groups]
    masked = np.zeros_like(probs)
    for g in groups:
        lo = g * per.shape[1]
        masked[lo:lo + per.shape[1]] = probs[lo:lo + per.shape[1]]
    idx = np.argsort(-masked, kind="stable")[:k]
    return idx, masked[idx]


def deepseek2_moe(xb, lp, cfg, act, *, groups=True, scale=True, shared=True):
    """xb (T, D).  The keyword switches leave a piece of the mathematics out:
    the tests use them to show that leaving it out is seen."""
    probs = softmax(xb.astype(np.float64) @ lp["router"].astype(np.float64))
    out = np.zeros_like(xb)
    for i in range(xb.shape[0]):
        if groups:
            idx, w = grouped_choice(probs[i], cfg.n_groups, cfg.topk_groups,
                                    cfg.n_active_experts)
        else:
            idx = np.argsort(-probs[i], kind="stable")[:cfg.n_active_experts]
            w = probs[i, idx]
        if scale:
            w = w * cfg.routed_scale
        for wj, e in zip(w, idx):
            h = act(xb[i] @ lp["gate"][e]) * (xb[i] @ lp["up"][e])
            out[i] += wj * (h @ lp["down"][e])
    if shared and "shared_w2" in lp:
        out += (act(xb @ lp["shared_w1"]) * (xb @ lp["shared_w3"])) @ lp["shared_w2"]
    return out


def np_forward_deepseek2(params, cfg, tokens, *, mscale=True, yarn=True, **moe_kw):
    """Full-sequence forward of DeepSeek-V2.  ``params``: numpy dict in the
    runtime layout, unfused and dense (attention stacks over all layers, the
    dense FFN's over the leading ``n_dense_layers``, the experts' over the
    rest).  Returns (T, V) logits."""
    act = {0: gelu_tanh, 1: silu}[cfg.hidden_act]
    t = len(tokens)
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.norm_eps
    pos = np.arange(t)
    factor = cfg.rope_factor if yarn else 1.0
    inv = yarn_inv_freq(dr, cfg.rope_theta, factor, cfg.rope_orig_seq_len,
                        cfg.rope_beta_fast, cfg.rope_beta_slow)
    amp = yarn_mscale(factor, cfg.rope_mscale) / yarn_mscale(
        factor, cfg.rope_mscale_all_dim)
    scale = (dn + dr) ** -0.5
    if mscale:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    mask = np.tril(np.ones((t, t), bool))

    def layer_params(keys, i):
        return {k: np.asarray(params[k][i], np.float32) for k in keys
                if k in params}

    x = params["embedding"][tokens].astype(np.float32)
    for li in range(cfg.n_layers):
        lp = layer_params(("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                           "wkv_b", "wo", "rms_att", "rms_ffn"), li)
        xb = rmsnorm_eps(x, lp["rms_att"], eps)
        q = rmsnorm_eps(xb @ lp["wq_a"], lp["q_a_norm"], eps) @ lp["wq_b"]
        q = q.reshape(t, h, dn + dr)
        ckv = xb @ lp["wkv_a"]
        c_kv = rmsnorm_eps(ckv[:, :r], lp["kv_a_norm"], eps)
        k_pe = rope_pairs(ckv[:, None, r:], pos, inv, amp)        # (T, 1, dr)
        q_pe = rope_pairs(q[..., dn:], pos, inv, amp)
        kv = (c_kv @ lp["wkv_b"]).reshape(t, h, dn + dv)
        att = np.zeros((t, h, dv), np.float32)
        for hh in range(h):
            s = (q[:, hh, :dn] @ kv[:, hh, :dn].T + q_pe[:, hh] @ k_pe[:, 0].T)
            s = np.where(mask, s.astype(np.float64) * scale, -np.inf)
            att[:, hh] = softmax(s) @ kv[:, hh, dn:]
        x = x + att.reshape(t, h * dv) @ lp["wo"]
        xb = rmsnorm_eps(x, lp["rms_ffn"], eps)
        if li < cfg.n_dense_layers:
            fp = layer_params(("w1", "w2", "w3"), li)
            x = x + (act(xb @ fp["w1"]) * (xb @ fp["w3"])) @ fp["w2"]
        else:
            fp = layer_params(("router", "up", "gate", "down", "shared_w1",
                               "shared_w2", "shared_w3"), li - cfg.n_dense_layers)
            x = x + deepseek2_moe(xb, fp, cfg, act, **moe_kw)
    x = rmsnorm_eps(x, np.asarray(params["rms_final"]), eps)
    return (x @ np.asarray(params["wcls"], np.float32)).astype(np.float32)


# ---- SmallThinker (ARCH_SMALLTHINKER) --------------------------------------

def relu(x):
    return np.maximum(x, 0.0)


def np_forward_smallthinker(params, cfg, tokens, wrong=None):
    """SmallThinker's full-sequence forward, (T, V) logits: layer ``l`` is
    full and unrotated where ``l % window_period == 0`` and otherwise a
    sliding-window layer (key ``j`` visible to query ``p`` iff ``p - window <
    j <= p``) with rotate-half RoPE; the router reads ``x_l`` as it enters the
    layer, before the attention norm; the experts are ReGLU; the six largest
    logits are chosen and softmaxed among themselves.  No cache, loops over
    layers, heads and rows.

    ``wrong`` names one deliberate fault, for the tests that prove each is
    seen: ``rope_on_full``, ``window_plus_one``, ``router_after_norm``,
    ``silu``, ``softmax_all``."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    pos = np.arange(t)
    act = silu if wrong == "silu" else relu
    width = cfg.window + (1 if wrong == "window_plus_one" else 0)

    def norm(x, w):
        ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
        return (w * (x / np.sqrt(ms + cfg.norm_eps))).astype(np.float32)

    x = params["embedding"][tokens].astype(np.float32)
    for li in range(cfg.n_layers):
        lp = {k: np.asarray(v[li]) for k, v in params.items()
              if k not in ("embedding", "rms_final", "wcls")}
        windowed = li % cfg.window_period != 0
        xb = norm(x, lp["rms_att"])
        r_in = xb if wrong == "router_after_norm" else x
        scores_r = r_in.astype(np.float32) @ lp["router"]            # (T, E)
        q = (xb @ lp["wq"]).reshape(t, hq, dh)
        k = (xb @ lp["wk"]).reshape(t, hkv, dh)
        v = (xb @ lp["wv"]).reshape(t, hkv, dh)
        if windowed or wrong == "rope_on_full":
            q = rope_rotate(q, pos, cfg.rope_theta, False)
            k = rope_rotate(k, pos, cfg.rope_theta, False)
        mask = pos[None, :] <= pos[:, None]
        if windowed:
            mask &= pos[None, :] > pos[:, None] - width
        att = np.zeros((t, hq, dh), np.float32)
        for h in range(hq):
            kh = h // (hq // hkv)
            sc = np.where(mask, (q[:, h] @ k[:, kh].T) / np.sqrt(dh), -np.inf)
            att[:, h] = softmax(sc) @ v[:, kh]
        x = x + att.reshape(t, hq * dh) @ lp["wo"]
        m = norm(x, lp["rms_ffn"])
        out = np.zeros_like(x)
        for i in range(t):
            idx = np.argsort(-scores_r[i], kind="stable")[:cfg.n_active_experts]
            w = (softmax(scores_r[i])[idx] if wrong == "softmax_all"
                 else softmax(scores_r[i, idx]))
            for wj, e in zip(w, idx):
                out[i] += wj * ((act(m[i] @ lp["gate"][e]) * (m[i] @ lp["up"][e]))
                                @ lp["down"][e])
        x = x + out
    x = norm(x, np.asarray(params["rms_final"]))
    return (x @ params["wcls"]).astype(np.float32)


def exaone_moe_layer(m, lp, cfg, share=None, wrong=None, shared=True):
    """K-EXAONE's expert FFN over normed rows ``m (T, D)``, float32 loops: a
    sigmoid router over all ``n_experts``, the top ``k`` of score + bias, the
    chosen scores normalised over ALL k and scaled; then the routed sum over
    the experts of ``share = (first, held)`` (``None``: all of them; ``lp``'s
    expert stacks hold exactly those, file index ``e`` being the router's
    ``first + e``) and, with ``shared``, the shared expert.

    ``wrong``: ``bias_in_weights``, ``softmax_router``, ``no_scale``,
    ``norm_over_held`` (the weights normalised over the held chosen only)."""
    first, held = share or (0, cfg.n_experts)
    k = cfg.n_active_experts
    logits = m.astype(np.float32) @ lp["router"]
    s = softmax(logits) if wrong == "softmax_router" else 1.0 / (1.0 + np.exp(-logits))
    biased = s + lp["router_bias"]
    out = np.zeros_like(m)
    for i in range(len(m)):
        idx = np.argsort(-biased[i], kind="stable")[:k]
        w = (biased if wrong == "bias_in_weights" else s)[i, idx]
        here = (idx >= first) & (idx < first + held)
        w = w / max(w[here].sum() if wrong == "norm_over_held" else w.sum(), 1e-30)
        if wrong != "no_scale":
            w = w * cfg.routed_scale
        for wj, e in zip(w[here], idx[here] - first):
            out[i] += wj * ((silu(m[i] @ lp["gate"][e]) * (m[i] @ lp["up"][e]))
                            @ lp["down"][e])
    if shared and cfg.n_shared_experts:
        out += (silu(m @ lp["shared_w1"]) * (m @ lp["shared_w3"])) @ lp["shared_w2"]
    return out


def np_forward_exaone_moe(params, cfg, tokens, wrong=None):
    """K-EXAONE's full-sequence forward, (T, V) logits, for the share the
    params hold (``cfg.first_expert``, ``cfg.n_experts_held``): layer ``l`` is
    full and unrotated where ``l % window_period == window_full_at`` and
    otherwise a sliding-window layer with rotate-half RoPE; each head of q and
    k is RMS-normalised before RoPE; the first ``n_dense_layers`` layers have a
    dense SwiGLU, the others :func:`exaone_moe_layer`.  No cache, loops over
    layers, heads and rows.

    ``wrong`` names one deliberate fault: ``rope_on_full``, ``full_first``
    (the full layer at the period's start), ``no_head_norm``, ``window_plus_one``,
    or one of :func:`exaone_moe_layer`'s."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    pos = np.arange(t)
    width = cfg.window + (1 if wrong == "window_plus_one" else 0)
    full_at = 0 if wrong == "full_first" else cfg.window_full_at

    def norm(x, w):
        ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
        return (w * (x / np.sqrt(ms + cfg.norm_eps))).astype(np.float32)

    def stack(key, li):
        a = params[key]
        if key in ("w1", "w2", "w3"):
            return np.asarray(a[li]) if li < cfg.n_dense_layers else None
        if a.shape[0] == cfg.n_layers - cfg.n_dense_layers != cfg.n_layers:
            return np.asarray(a[li - cfg.n_dense_layers]) if li >= cfg.n_dense_layers else None
        return np.asarray(a[li])

    x = params["embedding"][tokens].astype(np.float32)
    for li in range(cfg.n_layers):
        lp = {k: stack(k, li) for k in params
              if k not in ("embedding", "rms_final", "wcls")}
        windowed = li % cfg.window_period != full_at
        xb = norm(x, lp["rms_att"])
        q = (xb @ lp["wq"]).reshape(t, hq, dh)
        k = (xb @ lp["wk"]).reshape(t, hkv, dh)
        v = (xb @ lp["wv"]).reshape(t, hkv, dh)
        if wrong != "no_head_norm":
            q, k = norm(q, lp["q_norm"]), norm(k, lp["k_norm"])
        if windowed or wrong == "rope_on_full":
            q = rope_rotate(q, pos, cfg.rope_theta, False)
            k = rope_rotate(k, pos, cfg.rope_theta, False)
        mask = pos[None, :] <= pos[:, None]
        if windowed:
            mask &= pos[None, :] > pos[:, None] - width
        att = np.zeros((t, hq, dh), np.float32)
        for h in range(hq):
            kh = h // (hq // hkv)
            sc = np.where(mask, (q[:, h] @ k[:, kh].T) / np.sqrt(dh), -np.inf)
            att[:, h] = softmax(sc) @ v[:, kh]
        x = x + att.reshape(t, hq * dh) @ lp["wo"]
        m = norm(x, lp["rms_ffn"])
        if li < cfg.n_dense_layers:
            x = x + (silu(m @ lp["w1"]) * (m @ lp["w3"])) @ lp["w2"]
        else:
            x = x + exaone_moe_layer(
                m, lp, cfg, (cfg.first_expert, cfg.n_experts_held), wrong)
    x = norm(x, np.asarray(params["rms_final"]))
    return (x @ params["wcls"]).astype(np.float32)


def lfm2_moe_layer(m, lp, cfg, wrong=None):
    """LFM2's expert FFN over normed rows ``m (T, D)``, float32 loops: a
    sigmoid router, the top ``k`` of score + bias, the chosen scores over
    their sum ``+ 1e-6``, times ``routed_scale``; no shared expert.

    ``wrong``: ``bias_in_weights``, ``softmax_router``, ``no_eps``."""
    k = cfg.n_active_experts
    logits = m.astype(np.float32) @ lp["router"]
    s = softmax(logits) if wrong == "softmax_router" else 1.0 / (1.0 + np.exp(-logits))
    biased = s + lp["router_bias"]
    out = np.zeros_like(m)
    for i in range(len(m)):
        idx = np.argsort(-biased[i], kind="stable")[:k]
        w = (biased if wrong == "bias_in_weights" else s)[i, idx]
        w = w / (w.sum() + (0.0 if wrong == "no_eps" else 1e-6)) * cfg.routed_scale
        for wj, e in zip(w, idx):
            out[i] += wj * ((silu(m[i] @ lp["gate"][e]) * (m[i] @ lp["up"][e]))
                            @ lp["down"][e])
    return out


def np_forward_lfm2_moe(params, cfg, tokens, wrong=None):
    """LFM2's full-sequence forward, (T, V) logits, no cache and no state:
    layer ``l`` is attention where ``l % window_period == window_full_at``
    (per-head q/k RMSNorm, rotate-half RoPE, causal softmax) and otherwise a
    gated short convolution, computed as a sum of ``conv_taps`` shifted copies
    of ``z`` over the whole sequence; the first ``n_dense_layers`` layers have
    a dense SwiGLU, the others :func:`lfm2_moe_layer`.  The stacks are by kind
    and by segment (``models/params.py``).

    ``wrong`` names one deliberate fault: ``split_order`` (``C, B, X``),
    ``gate_after`` (``C`` multiplied before the taps), ``taps_reversed``,
    ``no_rope``, ``no_head_norm``, ``attention_first`` (the attention layer at
    the period's start), or one of :func:`lfm2_moe_layer`'s."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    taps = cfg.conv_taps
    pos = np.arange(t)
    full_at = 0 if wrong == "attention_first" else cfg.window_full_at
    period = cfg.window_period

    def norm(x, w):
        ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
        return (w * (x / np.sqrt(ms + cfg.norm_eps))).astype(np.float32)

    def seg(key, i):
        return np.asarray(params[key][i], np.float32)

    x = np.asarray(params["embedding"], np.float32)[tokens]
    n_att = n_conv = 0
    for li in range(cfg.n_layers):
        u = norm(x, seg("rms_att", li))
        if li % period == full_at:
            a = n_att
            n_att += 1
            q = (u @ seg("wq", a)).reshape(t, hq, dh)
            k = (u @ seg("wk", a)).reshape(t, hkv, dh)
            v = (u @ seg("wv", a)).reshape(t, hkv, dh)
            if wrong != "no_head_norm":
                q, k = norm(q, seg("q_norm", a)), norm(k, seg("k_norm", a))
            if wrong != "no_rope":
                q = rope_rotate(q, pos, cfg.rope_theta, False)
                k = rope_rotate(k, pos, cfg.rope_theta, False)
            mask = pos[None, :] <= pos[:, None]
            att = np.zeros((t, hq, dh), np.float32)
            for h in range(hq):
                kh = h // (hq // hkv)
                sc = np.where(mask, (q[:, h] @ k[:, kh].T) / np.sqrt(dh), -np.inf)
                att[:, h] = softmax(sc) @ v[:, kh]
            x = x + att.reshape(t, hq * dh) @ seg("wo", a)
        else:
            c = n_conv
            n_conv += 1
            parts = np.split(u @ seg("conv_in", c), 3, axis=-1)
            gb, gc, xs = ((parts[1], parts[0], parts[2])
                          if wrong == "split_order" else parts)
            z = gb * xs
            if wrong == "gate_after":
                z = z * gc
            w = seg("conv_taps", c)                       # (D, taps)
            if wrong == "taps_reversed":
                w = w[:, ::-1]
            ext = np.concatenate([np.zeros((taps - 1, z.shape[1]), np.float32), z])
            y = sum(ext[j:j + t] * w[:, j] for j in range(taps))
            if wrong != "gate_after":
                y = gc * y
            x = x + y @ seg("conv_out", c)
        m = norm(x, seg("rms_ffn", li))
        if li < cfg.n_dense_layers:
            x = x + (silu(m @ seg("w1", li)) * (m @ seg("w3", li))) @ seg("w2", li)
        else:
            e = li - cfg.n_dense_layers
            lp = {k: seg(k, e) for k in ("router", "router_bias", "up", "gate", "down")}
            x = x + lfm2_moe_layer(m, lp, cfg, wrong)
    x = norm(x, np.asarray(params["rms_final"]))
    return (x @ np.asarray(params["wcls"], np.float32)).astype(np.float32)


def np_forward_brumby(params, cfg, tokens, wrong=None):
    """Brumby's full-sequence forward, (T, V) logits, in the ATTENTION form of
    power retention: no state, no ring, no ``phi``.  Every layer: per-head q/k
    RMSNorm, rotate-half RoPE, one gate a kv head ``log gamma = logsigmoid(W_g
    u)``, scores ``exp(G_t - G_j) (q_t . k_j / sqrt(dh))^2`` for ``j <= t``
    with ``G`` the gate's running sum (float64 here), the quotient by their sum
    plus 1e-6; then a dense SwiGLU.

    ``wrong`` names one deliberate fault: ``no_gate`` (gamma = 1), ``no_quotient``
    (the sum of scores not divided by), ``degree_1`` (scores not squared),
    ``no_rope``, ``no_head_norm``, ``gate_per_query_head`` (head ``h`` reads
    gate ``h % n_kv_heads``)."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    pos = np.arange(t)

    def norm(x, w):
        ms = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
        return (w * (x / np.sqrt(ms + cfg.norm_eps))).astype(np.float32)

    def seg(key, i):
        return np.asarray(params[key][i], np.float32)

    x = np.asarray(params["embedding"], np.float32)[tokens]
    mask = pos[None, :] <= pos[:, None]
    for li in range(cfg.n_layers):
        u = norm(x, seg("rms_att", li))
        q = (u @ seg("wq", li)).reshape(t, hq, dh)
        k = (u @ seg("wk", li)).reshape(t, hkv, dh)
        v = (u @ seg("wv", li)).reshape(t, hkv, dh)
        if wrong != "no_head_norm":
            q, k = norm(q, seg("q_norm", li)), norm(k, seg("k_norm", li))
        if wrong != "no_rope":
            q = rope_rotate(q, pos, cfg.rope_theta, False)
            k = rope_rotate(k, pos, cfg.rope_theta, False)
        gate = u.astype(np.float64) @ seg("wg", li).astype(np.float64)  # (T, Hkv)
        cum = np.cumsum(-np.logaddexp(0.0, -gate), axis=0)               # G_t
        if wrong == "no_gate":
            cum = np.zeros_like(cum)
        att = np.zeros((t, hq, dh), np.float32)
        for h in range(hq):
            g = h // (hq // hkv)
            gg = h % hkv if wrong == "gate_per_query_head" else g
            s = (q[:, h].astype(np.float64) @ k[:, g].astype(np.float64).T
                 ) / np.sqrt(dh)
            s = s if wrong == "degree_1" else s * s
            a = np.where(mask, s * np.exp(np.where(
                mask, cum[:, None, gg] - cum[None, :, gg], 0.0)), 0.0)
            num = a @ v[:, g].astype(np.float64)
            den = 1.0 if wrong == "no_quotient" else \
                a.sum(-1, keepdims=True) + 1e-6
            att[:, h] = num / den
        x = x + att.reshape(t, hq * dh) @ seg("wo", li)
        n = norm(x, seg("rms_ffn", li))
        x = x + (silu(n @ seg("w1", li)) * (n @ seg("w3", li))) @ seg("w2", li)
    x = norm(x, np.asarray(params["rms_final"], np.float32))
    return (x @ np.asarray(params["wcls"], np.float32)).astype(np.float32)


# ---- Ouro (ARCH_OURO) -------------------------------------------------------
# A looped model, written from the equations (Zhu et al., "Scaling Latent
# Reasoning via Looped Language Models", arXiv:2510.25741; the published
# modeling code's names in brackets): the whole sequence every pass, no cache,
# so "pass u of layer l attends over what pass u of layer l wrote" is simply
# causal attention over this pass's own keys and values.  Shares nothing with
# dllama_tpu.models.transformer.  The keyword switches compute something else:
# the tests use them to show that each wrong computation is seen.

def np_forward_ouro(params, cfg, tokens, *, passes=None, wrong=""):
    """Full-sequence forward of ``cfg.n_loops`` passes over ``cfg.n_layers``
    weight sets.  tokens (T,); returns (T, V) float32 logits of the last pass.

    ``wrong``: ``"read_pass0"`` (every pass attends over pass 0's keys and
    values of the layer), ``"write_next"`` (a pass's keys and values are the
    next pass's: what a pass reads is what the one before it wrote),
    ``"no_loop_norm"`` (the final norm left out between passes),
    ``"no_post_norm"`` (the norms that close a branch left out),
    ``"rope_by_pass"`` (the position advanced by the pass in RoPE)."""
    t = len(tokens)
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    eps, n_pass = cfg.norm_eps, passes or cfg.n_loops
    mask = np.tril(np.ones((t, t), bool))

    def norm(x, w):
        return rmsnorm_eps(x, w, eps)

    def post(x, w):
        return x if wrong == "no_post_norm" else norm(x, w)

    x = params["embedding"][tokens].astype(np.float32)
    kept = {}  # (pass, layer) -> the keys and values that pass wrote
    for u in range(n_pass):
        pos = np.arange(t) + (u if wrong == "rope_by_pass" else 0)
        for li in range(cfg.n_layers):
            lp = {k: np.asarray(v[li]) for k, v in params.items()
                  if k not in ("embedding", "rms_final", "wcls")}
            xb = norm(x, lp["rms_att"])                      # [input_layernorm]
            q = rope_rotate((xb @ lp["wq"]).reshape(t, h, dh), pos,
                            cfg.rope_theta, False)
            k = rope_rotate((xb @ lp["wk"]).reshape(t, hkv, dh), pos,
                            cfg.rope_theta, False)
            v = (xb @ lp["wv"]).reshape(t, hkv, dh)
            kept[u, li] = (k, v)
            if wrong == "read_pass0":
                k, v = kept[0, li]
            elif wrong == "write_next" and u:
                k, v = kept[u - 1, li]
            att = np.zeros((t, h, dh), np.float32)
            for i in range(h):
                g = i // (h // hkv)
                s = np.where(mask, q[:, i] @ k[:, g].T / np.sqrt(dh), -np.inf)
                att[:, i] = softmax(s) @ v[:, g]
            a = att.reshape(t, h * dh) @ lp["wo"]
            x = x + post(a, lp["rms_ffn"])                   # [input_layernorm_2]
            y = norm(x, lp["rms_moe"])                       # [post_attention_layernorm]
            f = (silu(y @ lp["w1"]) * (y @ lp["w3"])) @ lp["w2"]
            x = x + post(f, lp["rms_ffn2"])                  # [post_attention_layernorm_2]
        if u < n_pass - 1 and wrong != "no_loop_norm":
            x = norm(x, np.asarray(params["rms_final"]))     # closes EVERY pass
    x = norm(x, np.asarray(params["rms_final"]))
    return (x @ params["wcls"]).astype(np.float32)


# ---- Falcon-H1 (ARCH_FALCON_H1) ---------------------------------------------
# A hybrid-head model, written from the equations (Zuo et al., "Falcon-H1: A
# Family of Hybrid-Head Language Models", TII 2025; the published modeling
# code's names in brackets): the whole sequence, the state-space mixer in its
# ATTENTION form (a double sum), no state, no ring, no convolution cache, no
# pages.  Shares nothing with dllama_tpu.models.transformer or ops/ssm.py.

def np_forward_falcon_h1(params, cfg, tokens, wrong=""):
    """Full-sequence forward, (T, V) float32 logits.  Every block: ONE norm
    feeds grouped-query attention (rotate-half RoPE, ``key_multiplier`` on k)
    and the Mamba-2 mixer (``in_proj`` rows ``z | x | B | C | dt`` times
    ``ssm_multipliers``, a causal depthwise convolution with bias and silu over
    ``x | B | C``, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, ``y_t =
    sum_{j<=t} exp(sum_{i=j+1..t} dt_i A) (C_t . B_j) dt_j x_j + D x_t``, gated
    by ``silu(z)`` and THEN RMS-normed group by group), both added to the
    residual; then a SwiGLU with its two multipliers.

    ``wrong`` names one deliberate fault: ``no_<name>`` for a multiplier of
    ``cfg.mup_<name>`` set to 1, ``no_decay`` (A = 0), ``no_conv`` (the taps
    replaced by the identity), ``norm_before_gate``, ``one_group`` (every head
    reads group 0's B and C), ``no_skip`` (D = 0), ``no_ssm`` / ``no_attn`` (a
    branch dropped)."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    h, p, g, n, taps = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                        cfg.ssm_state, cfg.ssm_conv)
    inner, f64 = cfg.ssm_inner, np.float64
    pos = np.arange(t)
    causal = pos[None, :] <= pos[:, None]

    def mup(name):
        return 1.0 if wrong == "no_" + name else getattr(cfg, "mup_" + name)

    def norm(x, w):
        return rmsnorm_eps(x, w, cfg.norm_eps)

    def seg(key, i):
        return np.asarray(params[key][i], np.float32)

    x = np.asarray(params["embedding"], np.float32)[tokens] * mup("embedding")
    for li in range(cfg.n_layers):
        u = norm(x, seg("rms_att", li))                   # [input_layernorm]
        # -- attention [self_attn]
        ua = u * mup("attn_in")
        q = rope_rotate((ua @ seg("wq", li)).reshape(t, hq, dh), pos,
                        cfg.rope_theta, False)
        k = rope_rotate((ua @ seg("wk", li) * mup("key")).reshape(t, hkv, dh),
                        pos, cfg.rope_theta, False)
        v = (ua @ seg("wv", li)).reshape(t, hkv, dh)
        att = np.zeros((t, hq, dh), np.float32)
        for i in range(hq):
            j = i // (hq // hkv)
            s = np.where(causal, q[:, i] @ k[:, j].T / np.sqrt(dh), -np.inf)
            att[:, i] = softmax(s) @ v[:, j]
        a = att.reshape(t, hq * dh) @ seg("wo", li) * mup("attn_out")
        # -- the mixer [mamba]
        us = u * mup("ssm_in")
        z, xbc = np.split(us @ seg("ssm_in", li), [inner], axis=-1)
        z = z * mup("z")
        xbc = xbc * np.repeat([mup("x"), mup("b"), mup("c")],
                              [inner, g * n, g * n]).astype(np.float32)
        dt = (us.astype(f64) @ seg("ssm_dt", li).astype(f64)) * mup("dt")
        dt = np.logaddexp(0.0, dt + seg("ssm_dt_bias", li))           # (T, H)
        if wrong != "no_conv":
            w = seg("ssm_conv_w", li)                                  # (C, K)
            ext = np.concatenate([np.zeros((taps - 1, xbc.shape[1]),
                                           np.float32), xbc])
            xbc = sum(ext[j:j + t] * w[:, j] for j in range(taps)) \
                + seg("ssm_conv_b", li)
        xbc = silu(xbc)
        xs, bm, cm = np.split(xbc, [inner, inner + g * n], axis=-1)
        xs = xs.reshape(t, h, p).astype(f64)
        bm, cm = bm.reshape(t, g, n).astype(f64), cm.reshape(t, g, n).astype(f64)
        a_h = -np.exp(seg("ssm_a_log", li).astype(f64))
        if wrong == "no_decay":
            a_h = a_h * 0.0
        cum = np.cumsum(dt * a_h, axis=0)                              # (T, H)
        d_h = seg("ssm_d", li) * (0.0 if wrong == "no_skip" else 1.0)
        y = np.zeros((t, h, p), f64)
        for i in range(h):
            j = 0 if wrong == "one_group" else i // (h // g)
            w = np.where(causal, (cm[:, j] @ bm[:, j].T) * np.exp(np.where(
                causal, cum[:, None, i] - cum[None, :, i], 0.0)), 0.0)
            y[:, i] = (w * dt[None, :, i]) @ xs[:, i] + d_h[i] * xs[:, i]
        y = y.reshape(t, inner)

        def grouped(y):  # RMSNorm over each of the g groups [FalconH1RMSNormGated]
            y = y.reshape(t, g, -1)
            y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
            return y.reshape(t, inner)

        gate = silu(z.astype(f64))
        y = grouped(y) * seg("ssm_norm", li) * gate \
            if wrong == "norm_before_gate" \
            else grouped(y * gate) * seg("ssm_norm", li)
        s = y.astype(np.float32) @ seg("ssm_out", li) * mup("ssm_out")
        x = x + (0.0 if wrong == "no_attn" else a) + (0.0 if wrong == "no_ssm" else s)
        f = norm(x, seg("rms_ffn", li))                   # [pre_ff_layernorm]
        x = x + (silu(f @ seg("w1", li) * mup("gate")) * (f @ seg("w3", li))
                 ) @ seg("w2", li) * mup("down")
    x = norm(x, np.asarray(params["rms_final"], np.float32))
    return (x @ np.asarray(params["wcls"], np.float32) * mup("head")).astype(np.float32)


def np_forward_granite_hybrid(params, cfg, tokens, wrong=""):
    """Full-sequence forward, (T, V) float32 logits, of Granite-4.0-H: the
    embedding times ``mup_embedding``; layer ``l`` is grouped-query attention
    WITHOUT positions where ``l % window_period == window_full_at`` (scores
    ``q . k * mup_key / sqrt(head)``: the key's multiplier carries
    ``attention_multiplier``) and else a Mamba-2 mixer (``np_forward_falcon_h1``'s,
    with no multiplier inside, ONE group, the gate first and one RMSNorm over
    all of its channels), its weights at the layer's place among its kind;
    then top-k of a softmax router renormalised over the CHOSEN experts plus a
    shared gated MLP, in every layer; each branch's output times its
    multiplier (``mup_attn_out``, ``mup_ssm_out``, ``mup_down``: the published
    ``residual_multiplier`` three times); logits times ``mup_head``.

    ``wrong`` names one deliberate fault: ``no_<name>`` for ``cfg.mup_<name>``
    set to 1 (``no_residual``: all three of the branches'), ``rope`` (q and k rotated), ``softmax_all`` (the chosen weights
    as the softmax over ALL experts gave them), ``no_shared``, ``no_decay``,
    ``no_conv``, ``norm_before_gate``, ``no_skip``."""
    t = len(tokens)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    h, p, n, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
    inner, f64 = cfg.ssm_inner, np.float64
    pos = np.arange(t)
    causal = pos[None, :] <= pos[:, None]
    assert cfg.ssm_groups == 1

    def mup(name):
        if wrong == "no_residual" and name in ("attn_out", "ssm_out", "down"):
            return 1.0
        return 1.0 if wrong == "no_" + name else getattr(cfg, "mup_" + name)

    def norm(x, w):
        return rmsnorm_eps(x, w, cfg.norm_eps)

    def attention(u, i):
        def seg(key):
            return np.asarray(params[key][i], np.float32)

        q = (u @ seg("wq")).reshape(t, hq, dh)
        k = (u @ seg("wk") * mup("key")).reshape(t, hkv, dh)
        if wrong == "rope":
            q = rope_rotate(q, pos, cfg.rope_theta, False)
            k = rope_rotate(k, pos, cfg.rope_theta, False)
        v = (u @ seg("wv")).reshape(t, hkv, dh)
        att = np.zeros((t, hq, dh), np.float32)
        for a in range(hq):
            j = a // (hq // hkv)
            s = np.where(causal, q[:, a] @ k[:, j].T / np.sqrt(dh), -np.inf)
            att[:, a] = softmax(s) @ v[:, j]
        return att.reshape(t, hq * dh) @ seg("wo")

    def mixer(u, i):
        def seg(key):
            return np.asarray(params[key][i], np.float32)

        z, xbc = np.split(u @ seg("ssm_in"), [inner], axis=-1)
        dt = np.logaddexp(0.0, u.astype(f64) @ seg("ssm_dt").astype(f64)
                          + seg("ssm_dt_bias"))                        # (T, H)
        if wrong != "no_conv":
            w = seg("ssm_conv_w")                                      # (C, K)
            ext = np.concatenate([np.zeros((taps - 1, xbc.shape[1]),
                                           np.float32), xbc])
            xbc = sum(ext[j:j + t] * w[:, j] for j in range(taps)) \
                + seg("ssm_conv_b")
        xs, bm, cm = np.split(silu(xbc).astype(f64), [inner, inner + n], axis=-1)
        xs = xs.reshape(t, h, p)
        a_h = -np.exp(seg("ssm_a_log").astype(f64)) * (
            0.0 if wrong == "no_decay" else 1.0)
        cum = np.cumsum(dt * a_h, axis=0)                              # (T, H)
        d_h = seg("ssm_d") * (0.0 if wrong == "no_skip" else 1.0)
        cb = cm @ bm.T                                                 # one group
        y = np.zeros((t, h, p), f64)
        for a in range(h):
            w = np.where(causal, cb * np.exp(np.where(
                causal, cum[:, None, a] - cum[None, :, a], 0.0)), 0.0)
            y[:, a] = (w * dt[None, :, a]) @ xs[:, a] + d_h[a] * xs[:, a]
        y = y.reshape(t, inner)

        def normed(y):  # ONE RMSNorm over all of the mixer's channels
            return y / np.sqrt(np.mean(y * y, -1, keepdims=True) + cfg.norm_eps)

        gate = silu(z.astype(f64))
        y = normed(y) * seg("ssm_norm") * gate if wrong == "norm_before_gate" \
            else normed(y * gate) * seg("ssm_norm")
        return y.astype(np.float32) @ seg("ssm_out")

    def experts(f, li):
        def seg(key):
            return np.asarray(params[key][li], np.float32)

        logits = f @ seg("router")
        out = np.zeros_like(f)
        for r in range(t):
            top = np.argsort(-logits[r], kind="stable")[:cfg.n_active_experts]
            w = softmax(logits[r])[top] if wrong == "softmax_all" \
                else softmax(logits[r][top])
            for e, we in zip(top, w):
                out[r] += we * ((silu(f[r] @ seg("gate")[e]) * (f[r] @ seg("up")[e]))
                                @ seg("down")[e])
        if wrong != "no_shared":
            out = out + (silu(f @ seg("shared_w1")) * (f @ seg("shared_w3"))
                         ) @ seg("shared_w2")
        return out

    x = np.asarray(params["embedding"], np.float32)[tokens] * mup("embedding")
    n_att = n_mix = 0
    for li in range(cfg.n_layers):
        u = norm(x, np.asarray(params["rms_att"][li], np.float32))
        if li % cfg.window_period == cfg.window_full_at:
            a, n_att = mup("attn_out") * attention(u, n_att), n_att + 1
        else:
            a, n_mix = mup("ssm_out") * mixer(u, n_mix), n_mix + 1
        x = x + a
        f = norm(x, np.asarray(params["rms_ffn"][li], np.float32))
        x = x + mup("down") * experts(f, li)
    x = norm(x, np.asarray(params["rms_final"], np.float32))
    return (x @ np.asarray(params["wcls"], np.float32) * mup("head")).astype(np.float32)
