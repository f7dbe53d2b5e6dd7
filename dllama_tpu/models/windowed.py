"""SmallThinker's layers: periods of one full and ``window_period - 1``
sliding-window layers over a cache with two kinds of plane.

A layer ``l`` with ``l % window_period == 0`` is *full*: no rotation at all
(NoPE) and a causal mask over every position.  The others are *window* layers:
rotate-half RoPE and the last ``window`` keys (``ops/window.py``).  Every layer
is an expert layer whose router reads the layer's input ``x_l`` as it arrives,
before the attention norm; its logits are handed to ``moe_ffn``, which chooses
and weighs as for Mixtral (softmax over all, top-k, renormalised: equal to a
softmax over the chosen logits).

The contiguous cache (``init_cache``) keeps the two kinds apart: ``k`` / ``v``
are the full layers' ``(Lf, B, Hkv, S, Dh)`` and ``wk`` / ``wv`` the window
layers' rings ``(Lw, B, Hkv, R, Dh)``, ``R = cfg.window_ring(S)``; full layer
``l`` is plane ``l // period``, window layer ``l`` is ring ``l - l // period -
1``.  The paged pool is one pool and one table for all layers (``(L, P, ps,
Hkv, Dh)``, indexed by ``l``), the window a bound on what is read.

The layer loop is a ``lax.scan`` over periods whose body unrolls the period's
layers, so a layer's kind is static where it is traced; weights stay stacked by
layer and are indexed where used, as in ``transformer.run_blocks``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import q40, q8, window
from ..ops.attention import (gqa_attention_at, live_gqa_attention,
                             paged_gqa_attention_at, paged_update_kv_rows,
                             update_kv_cache_at)
from ..ops.kernels import apply_rope, rmsnorm
from ..ops.scopes import part, scope
from .config import ModelConfig


# keys a trip of a full layer's live walk reads for ONE decoded token.  A trip
# has a fixed cost of about 1.8 us beside 2.8 us a 1024 keys of 4 kv heads (a
# walk with a tail of 128-key blocks read 0.19 ms more a token for every 1024
# keys of tail, 13 layers: PERF.md section 6, PR 38), so a token's walk takes
# fewer and larger trips than a prompt's rows (``_kv_chunk``: 1024).
DECODE_BLOCK = 2048


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype, quant: bool):
    """The contiguous cache of a windowed model: full planes of ``seq_len``
    positions, rings of ``cfg.window_ring(seq_len)``."""
    from .transformer import KVCache
    if quant:
        raise ValueError("a cache with window layers has no int8 form yet "
                         "(--kv-quant int8 is refused for this architecture)")
    dt = dtype or cfg.dtype
    tail = (cfg.n_kv_heads, seq_len, cfg.head_size)
    ring = (cfg.n_kv_heads, cfg.window_ring(seq_len), cfg.head_size)
    full = (cfg.n_full_layers, batch) + tail
    win = (cfg.n_window_layers, batch) + ring
    return KVCache(jnp.zeros(full, dt), jnp.zeros(full, dt),
                   wk=jnp.zeros(win, dt), wv=jnp.zeros(win, dt))


def _attention(x, lp, cfg: ModelConfig, cache, cos, sin, pos, layer, plane,
               windowed: bool, offsets, pos_rows, paged):
    """One attention sub-block.  ``layer`` indexes the weights (and the paged
    pool), ``plane`` the contiguous cache's stack of this layer's kind."""
    from .transformer import _mm
    b, t, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    with scope("norm"):
        xb = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
    with scope("qkv"):
        if "wqkv" in lp:
            q, k, v = jnp.split(_mm(xb, lp["wqkv"], cfg),
                                [hq * dh, (hq + hkv) * dh], axis=-1)
        else:
            q, k, v = (_mm(xb, lp[w], cfg, kind="row") for w in ("wq", "wk", "wv"))
        q = q.reshape(b, t, hq, dh)
        k = k.reshape(b, t, hkv, dh)
        v = v.reshape(b, t, hkv, dh)
    with scope("rope"):
        if windowed:  # a full layer is not rotated at all
            q = apply_rope(q, cos, sin, interleaved=False)
            k = apply_rope(k, cos, sin, interleaved=False)
        q = q.transpose(0, 2, 1, 3)  # (B, Hq, T, Dh)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    kind = "window" if windowed else "full"
    if paged is not None:
        page_table, pidx, oidx = paged
        with scope("kv_write"):
            ck, cv = paged_update_kv_rows(cache.k, cache.v, k, v, layer, pidx,
                                          oidx)
            cache = cache._replace(k=ck, v=cv)
        with scope("attn"), part(kind):
            if windowed:
                att = window.paged_window_attention(
                    q, cache.k, cache.v, layer, page_table, pos_rows,
                    cfg.window)
            else:
                att = paged_gqa_attention_at(q, cache.k, cache.v, layer,
                                             page_table, pos_rows)
    elif windowed:
        ring = cache.wk.shape[3]
        if ring < cache.k.shape[3] and ring < cfg.window + t - 1:
            raise ValueError(
                f"a call of {t} rows does not fit a window layer's ring of "
                f"{ring} positions (window {cfg.window} + one prefill chunk "
                f"of {cfg.prefill_chunk()}): feed at most "
                f"{ring - cfg.window + 1} rows a call")
        rows = pos_rows if pos_rows is not None else jnp.broadcast_to(pos, (b,))
        with scope("kv_write"):
            wk, wv = window.ring_write(cache.wk, cache.wv, k, v, plane, rows)
            cache = cache._replace(wk=wk, wv=wv)
        with scope("attn"), part(kind):
            att = window.ring_attention(q, cache.wk, cache.wv, plane, rows,
                                        cfg.window, floor=offsets)
    elif pos_rows is not None:
        # a full layer of contiguous slots: the full planes are a ring that
        # never wraps, and a window of the whole sequence is the causal mask
        with scope("kv_write"):
            ck, cv = window.ring_write(cache.k, cache.v, k, v, plane, pos_rows)
            cache = cache._replace(k=ck, v=cv)
        with scope("attn"), part(kind):
            att = window.ring_attention(q, cache.k, cache.v, plane, pos_rows,
                                        cache.k.shape[3])
    else:
        with scope("kv_write"):
            ck, cv = update_kv_cache_at(cache.k, cache.v, k, v, plane, pos)
            cache = cache._replace(k=ck, v=cv)
        with scope("attn"), part(kind):
            if t == 1 and cache.k.shape[3] % DECODE_BLOCK == 0:
                att = live_gqa_attention(q, cache.k, cache.v, pos, layer=plane,
                                         start=offsets, block=DECODE_BLOCK)
            else:
                att = gqa_attention_at(q, cache.k, cache.v, plane, pos, t,
                                       start=offsets)
    with scope("attn"):
        att = att.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
    with scope("wo"):
        return _mm(att, lp["wo"], cfg, kind="col"), cache


def run_periods(params, cfg: ModelConfig, x, cache, cos, sin, pos, offsets,
                pos_rows, paged):
    """All layers of a windowed model over the residual stream ``x (B, T,
    D)``; returns it and the updated cache (``transformer.run_blocks`` has
    embedded the tokens and made the angles)."""
    from .transformer import moe_ffn
    b, t, d = x.shape
    period = cfg.window_period
    keys = [k for k in params if k not in ("embedding", "rms_final", "wcls")]

    def at(w, i):
        if isinstance(w, (q40.QTensor, q8.Q8Tensor)):
            return q40.QLayerView(w, i)
        return jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)

    def one_layer(x, kvc, layer, plane, windowed: bool):
        lp = {k: at(params[k], layer) for k in keys}
        with scope("moe"), part("router"):
            # x_l as it enters the layer, before any norm
            router_logits = (x.reshape(b * t, d).astype(jnp.float32)
                             @ lp["router"].astype(jnp.float32))
        att_out, kvc = _attention(x, lp, cfg, kvc, cos, sin, pos, layer, plane,
                                  windowed, offsets, pos_rows, paged)
        with scope("wo"):
            x = x + att_out
        with scope("norm"):
            xb = rmsnorm(x, lp["rms_ffn"], cfg.norm_eps)
        with scope("moe"):
            ff = moe_ffn(xb.reshape(b * t, d), lp, cfg, router_logits)
            return x + ff.reshape(b, t, d), kvc

    def one_period(carry, p):
        x, kvc = carry
        x, kvc = one_layer(x, kvc, p * period, p, False)
        for j in range(1, period):
            x, kvc = one_layer(x, kvc, p * period + j,
                               p * (period - 1) + (j - 1), True)
        return (x, kvc), None

    return jax.lax.scan(one_period, (x, cache),
                        jnp.arange(cfg.n_layers // period, dtype=jnp.int32))[0]
