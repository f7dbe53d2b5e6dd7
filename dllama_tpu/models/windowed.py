"""Layers of a model whose layers come in periods (SmallThinker, K-EXAONE,
LFM2, Granite-4.0-H): ``window_period`` layers of which one, at
``window_full_at``, is full attention, over a cache with a kind of plane per
layer kind.  The others are sliding-window attention (``cfg.window``), gated
short convolutions (``cfg.conv_taps``, ``ops/conv.py``: no keys or values, a
ring of the last positions' ``z`` in the plane ``cz``) or Mamba-2 state-space
mixers (``cfg.ssm_heads``, ``ops/ssm.py``: a state matrix a head behind rings
of recent positions, ``transformer._ssm_block``, the function Falcon-H1's
blocks call).  A convolution model's full layers carry RoPE
(``cfg.full_rotates``), a mixer model's rotate nothing; the weights of both are
stacked by layer kind (``cfg.kind_stacked``: ``params.ATT_KIND_KEYS`` /
``CONV_KEYS`` / ``MIXER_KEYS``, indexed by ``kind_index``).

A layer ``l`` with ``l % window_period == window_full_at`` is *full*: no
rotation at all (NoPE) and a causal mask over every position.  The others are
*window* layers: rotate-half RoPE and the last ``window`` keys
(``ops/window.py``).  What else a layer does follows from the config, not from
a copy of the loop: K-EXAONE normalises each head of q and k before RoPE
(``cfg.qk_head_norm``), has a dense FFN in its leading ``n_dense_layers`` and
routes from the FFN's normed input as every arch but SmallThinker, whose
router reads the layer's input ``x_l`` as it arrives, before the attention
norm, and hands the logits to ``moe_ffn``.

The contiguous cache (``init_cache``) keeps the two kinds apart: ``k`` / ``v``
are the full layers' ``(Lf, B, Hkv, S, Dh)`` and ``wk`` / ``wv`` the window
layers' rings ``(Lw, B, Hkv, R, Dh)``, ``R = cfg.window_ring(S)``.  The paged
engine does the same with pages (``init_pool``): ``k`` / ``v`` are the full
layers' pool ``(Lf, P, ps, Hkv, Dh)``, what ``--kv-pages`` counts and the
scheduler's page tables address, and ``wk`` / ``wv`` the window layers' planes
``(Lw, B * ring, ps, Hkv, Dh)`` in which a slot owns a ring of ``ring`` pages
(``ops/window.py``).  Layer ``l``'s place in its kind's stack is
``kind_index``.

The layer loop is a ``lax.scan`` over periods whose body unrolls the period's
layers, so a layer's kind is static where it is traced (a period's runs of
mixer layers, nine of Granite's ten, are a scan of their own inside it: one
mixer body a run, half of the program to trace and compile); the periods that
hold a dense layer are unrolled in front of the scan.  Weights stay stacked
by layer (by segment for the two FFN kinds) and are indexed where used, as in
``transformer.run_blocks``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import conv, q40, q8, ssm, window
from ..ops.attention import (gqa_attention_at, live_gqa_attention,
                             paged_gqa_attention_at, paged_update_kv_rows,
                             pool_rows,
                             update_kv_cache_at)
from ..ops.kernels import apply_rope, rmsnorm
from ..ops.scopes import part, scope
from . import grouping, packing
from .cache_kinds import SLOT_ROWS
from .config import ModelConfig
from .params import (ATT_KIND_KEYS, CONV_KEYS, DENSE_FFN_KEYS, MIXER_KEYS,
                     MOE_FFN_KEYS)


# keys a trip of a full layer's live walk reads for ONE decoded token.  A trip
# has a fixed cost of about 1.8 us beside 2.8 us a 1024 keys of 4 kv heads (a
# walk with a tail of 128-key blocks read 0.19 ms more a token for every 1024
# keys of tail, 13 layers: PERF.md section 6, PR 38), so a token's walk takes
# fewer and larger trips than a prompt's rows (``_kv_chunk``: 1024).
DECODE_BLOCK = 2048


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    """The contiguous cache of a windowed model: full planes of ``seq_len``
    positions, rings of ``cfg.window_ring(seq_len)``."""
    from .transformer import KVCache
    dt = dtype or cfg.dtype
    tail = (cfg.n_kv_heads, seq_len, cfg.head_size)
    full = (cfg.n_full_layers, batch) + tail
    if cfg.kind_stacked:
        return KVCache(jnp.zeros(full, dt), jnp.zeros(full, dt),
                       **_slot_state(cfg, batch, dt))
    ring = (cfg.n_kv_heads, cfg.window_ring(seq_len), cfg.head_size)
    win = (cfg.n_window_layers, batch) + ring
    return KVCache(jnp.zeros(full, dt), jnp.zeros(full, dt),
                   wk=jnp.zeros(win, dt), wv=jnp.zeros(win, dt))


def _slot_state(cfg: ModelConfig, rows: int, dt) -> dict:
    """What a period's other layers keep of ``rows`` rows (a sequence of the
    contiguous cache, a slot of a slot engine), by field of ``KVCache``.  The
    convolution layers' state ``cz (Lc, rows, 1, R, D)``: a ring of
    ``conv.RING`` positions of ``z`` a row, laid out as a ring of one head of
    ``D`` so that the window writes of ``ops/window.py`` and the cache's one
    placement apply; or the mixer layers' planes (``ops/ssm.py``)."""
    if cfg.has_ssm:
        return ssm.init_planes(cfg, rows, dt, cfg.n_ssm_layers)
    return {"cz": jnp.zeros((cfg.n_conv_layers, rows, 1, conv.RING, cfg.dim), dt)}


def init_pool(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
              slots: int, max_pages: int):
    """The paged engine's cache of a windowed model: the full layers' pool of
    ``n_pages`` and, for ``slots`` slots, the window layers' rings of
    ``window_pages(window, SLOT_ROWS, page_size, max_pages)`` pages each."""
    from .transformer import KVCache
    if slots < 1:
        raise ValueError(
            "a pool beside a state-space mixer needs the number of slots: each "
            "owns a state and its rings" if cfg.has_ssm else
            "a windowed model's pool needs the number of slots: each owns a "
            "ring of pages in the window layers' planes")
    dt = dtype or cfg.dtype
    page = (page_size, cfg.n_kv_heads, cfg.head_size)
    # the pool's heads under 128 lanes are stored lane-dense (ops/attention.py
    # pool_rows); the window layers' rings are read as slices, never by page
    full = (cfg.n_full_layers, n_pages, page_size) + pool_rows(
        cfg.n_kv_heads, cfg.head_size)
    if cfg.kind_stacked:  # the state is the slots' own, whatever the pages hold
        return KVCache(jnp.zeros(full, dt), jnp.zeros(full, dt),
                       **_slot_state(cfg, slots, dt))
    ring = window.window_pages(cfg.window, SLOT_ROWS, page_size, max_pages)
    win = (cfg.n_window_layers, slots * ring) + page
    return KVCache(jnp.zeros(full, dt), jnp.zeros(full, dt),
                   wk=jnp.zeros(win, dt), wv=jnp.zeros(win, dt))


def kind_index(cfg: ModelConfig, p, j: int):
    """The place of period ``p``'s ``j``-th layer among the layers of its kind
    (``p`` may be traced, ``j`` is static)."""
    if j == cfg.window_full_at:
        return p
    return p * (cfg.window_period - 1) + j - (j > cfg.window_full_at)


def _short_conv(x, lp, cfg: ModelConfig, cache, pos, plane, offsets, pos_rows,
                n_real, packed):
    """One gated short-convolution sub-block (``ops/conv.py`` has the operator
    and why its state is a ring of positions); ``plane`` indexes ``cache.cz``.
    The two projections and both gates are row-local and pack; the state's
    read and write and the taps are a per-row sequence operation and keep
    ``(B, T)``, as ``rope`` and the KV write do.  Device time under part
    ``conv`` of the scopes an attention sub-block's stages have."""
    from .transformer import _mm
    b, t, d = x.shape
    taps, ring = cfg.conv_taps, cache.cz.shape[3]
    if pos_rows is not None and t > ring - (taps - 1):
        raise ValueError(
            f"a slot step of {t} rows does not fit a convolution layer's "
            f"state ring of {ring} positions: feed at most "
            f"{ring - (taps - 1)} rows a step")
    conv.record(t, ring, taps)

    def project(x):  # row-local: any leading axes
        with scope("norm"):
            u = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
        with scope("qkv"), part("conv"):
            gate_b, gate_c, xs = jnp.split(_mm(u, lp["conv_in"], cfg), 3, axis=-1)
            return gate_b * xs, gate_c

    def project_out(y, lp, cfg):
        with scope("wo"), part("conv"):
            return _mm(y, lp["conv_out"], cfg)

    z, gate_c = packing.over(packed, "qkv", project, x)
    rows = pos_rows if pos_rows is not None else jnp.broadcast_to(pos, (b,))
    with scope("attn"), part("conv"):
        carried = conv.state_read(cache.cz, plane, rows, taps, floor=offsets)
        y = conv.taps_and_gate(z, carried, gate_c, lp["conv_taps"], rows,
                               floor=offsets)
    with scope("kv_write"), part("conv"):
        cache = cache._replace(cz=conv.state_write(cache.cz, z, plane, rows,
                                                   taps, n_real))
    return packing.over(packed, "wo", project_out, y, lp=lp, cfg=cfg), cache


def _attention(x, lp, cfg: ModelConfig, cache, cos, sin, pos, plane,
               windowed: bool, offsets, pos_rows, paged, packed):
    """One attention sub-block; ``plane`` indexes the cache's stack of this
    layer's kind (the contiguous planes or rings, the pool or the slots'
    rings of pages).  ``packed``: as ``transformer._attention_block``."""
    from .transformer import _mm, _mup, _project_out
    b, t, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size

    def project(x):  # row-local: any leading axes
        with scope("norm"):
            xb = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
        with scope("qkv"):
            if "wqkv" in lp:
                q, k, v = jnp.split(_mm(xb, lp["wqkv"], cfg),
                                    [hq * dh, (hq + hkv) * dh], axis=-1)
            else:
                q, k, v = (_mm(xb, lp[w], cfg, kind="row") for w in ("wq", "wk", "wv"))
            if cfg.mup_key != 1.0:  # before the write (Granite: the scores' scale)
                k = k * _mup(cfg, "key")
            lead = x.shape[:-1]
            q = q.reshape(*lead, hq, dh)
            k = k.reshape(*lead, hkv, dh)
            v = v.reshape(*lead, hkv, dh)
            if cfg.qk_head_norm:
                with part("qk_norm"):
                    q = rmsnorm(q, lp["q_norm"], cfg.norm_eps)
                    k = rmsnorm(k, lp["k_norm"], cfg.norm_eps)
            return q, k, v

    q, k, v = packing.over(packed, "qkv", project, x)
    with scope("rope"):
        if windowed or cfg.full_rotates:  # a windowed model's full layer is not rotated at all
            q = apply_rope(q, cos, sin, interleaved=False)
            k = apply_rope(k, cos, sin, interleaved=False)
        q = q.transpose(0, 2, 1, 3)  # (B, Hq, T, Dh)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    kind = "window" if windowed else "full"
    if paged is not None and windowed:
        # the slot's ring of pages: its table is arithmetic (ops/window.py)
        with scope("kv_write"):
            wk, wv = paged_update_kv_rows(cache.wk, cache.wv, k, v, plane,
                                          *paged[3])
            cache = cache._replace(wk=wk, wv=wv)
        with scope("attn"), part(kind):
            att = window.paged_window_attention(q, cache.wk, cache.wv, plane,
                                                pos_rows, cfg.window,
                                                paged[0].shape[1])
    elif paged is not None:
        page_table, pidx, oidx = paged[:3]
        with scope("kv_write"):
            ck, cv = paged_update_kv_rows(cache.k, cache.v, k, v, plane, pidx,
                                          oidx)
            cache = cache._replace(k=ck, v=cv)
        with scope("attn"), part(kind):
            att = paged_gqa_attention_at(q, cache.k, cache.v, plane,
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
    return packing.over(packed, "wo", _project_out, att, lp=lp,
                        cfg=cfg), cache


def run_periods(params, cfg: ModelConfig, x, cache, cos, sin, pos, offsets,
                pos_rows, paged, packed=None, n_real=None, marks=None):
    """All layers of a periodic model over the residual stream ``x (B, T,
    D)``; returns it and the updated cache (``transformer.run_blocks`` has
    embedded the tokens and made the angles, and planned ``packed``).
    ``n_real``: how many of the ``T`` rows hold a token (a scalar, or ``(B,)``
    on a slot step; ``None``: all), which a convolution layer's state write
    needs of a call wider than its ring.  ``marks``: the call's watermarks
    where some layer's state lags the clock (``transformer._ssm_block``)."""
    from .transformer import _dense_ffn, _mup, _ssm_block, moe_ffn
    b, t, d = x.shape
    period, n_dense = cfg.window_period, cfg.n_dense_layers
    router_first = cfg.router_reads_input
    keys = [k for k in params if k not in ("embedding", "rms_final", "wcls")]
    # a stack covers all layers, the dense prefix or the expert layers
    dense_keys = [k for k in keys if n_dense and k in DENSE_FFN_KEYS]
    moe_keys = [k for k in keys if n_dense and k in MOE_FFN_KEYS]
    att_keys = [k for k in keys if k not in dense_keys and k not in moe_keys]
    if cfg.kind_stacked:  # its operators' weights are stacked by layer kind
        other_keys = CONV_KEYS if cfg.conv_taps else MIXER_KEYS
        kind_keys = {False: [k for k in att_keys if k in other_keys],
                     True: [k for k in att_keys if k in ATT_KIND_KEYS]}
        att_keys = [k for k in att_keys if k not in other_keys + ATT_KIND_KEYS]
    if paged is not None and cache.wk is not None:
        with scope("page_idx"):  # the window planes' write places, once
            paged = paged + (window.paged_ring_indices(
                pos_rows, t, cache.wk.shape[2], cache.wk.shape[1] // b),)

    def at(w, i):
        if isinstance(w, (q40.QTensor, q8.Q8Tensor)):
            return q40.QLayerView(w, i)
        return jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)

    def one_layer(x, kvc, layer, j: int, plane, dense: bool):
        """Layer ``layer`` (traced or static), the ``j``-th of its period."""
        full = j == cfg.window_full_at
        windowed = not full and not cfg.kind_stacked
        lp = {k: at(params[k], layer) for k in att_keys}
        if cfg.kind_stacked:
            lp.update({k: at(params[k], plane) for k in kind_keys[full]})
        lp.update({k: at(params[k], layer - (0 if dense else n_dense))
                   for k in (dense_keys if dense else moe_keys)})
        router_logits = None
        if router_first:
            with scope("moe"), part("router"):
                # x_l as it enters the layer, before any norm
                router_logits = (x.reshape(b * t, d).astype(jnp.float32)
                                 @ lp["router"].astype(jnp.float32))
        if full or windowed:
            att_out, kvc = _attention(x, lp, cfg, kvc, cos, sin, pos, plane,
                                      windowed, offsets, pos_rows, paged, packed)
        elif cfg.conv_taps:
            att_out, kvc = _short_conv(x, lp, cfg, kvc, pos, plane, offsets,
                                       pos_rows, n_real, packed)
        else:
            att_out, kvc = _ssm_block(x, lp, cfg, kvc, pos, plane, marks,
                                      offsets=offsets, pos_rows=pos_rows,
                                      packed=packed, n_real=n_real)
        with scope("wo"):
            x = x + att_out

        def normed(x):
            with scope("norm"):
                return rmsnorm(x, lp["rms_ffn"], cfg.norm_eps)

        if dense:
            ff = packing.over(packed, "w2",
                           lambda x: _dense_ffn(normed(x), lp, cfg), x)
            with scope("w2"):
                return x + ff, kvc

        def experts(x, *logits):  # row-local: any leading axes
            xb = normed(x)
            with scope("moe"):
                return moe_ffn(xb.reshape(-1, d), lp, cfg,
                               *(lg.reshape(-1, lg.shape[-1]) for lg in logits)
                               ).reshape(x.shape)

        if router_logits is not None and packed is not None:
            router_logits = router_logits.reshape(b, t, -1)  # the rows' own
        ff = packing.over(packed, "moe", experts, x,
                       *(() if router_logits is None else (router_logits,)))
        with scope("moe"):
            if cfg.mup_down != 1.0:
                # the experts' and the shared MLP's sum times one scalar
                # (Granite's residual multiplier: what stands on a dense FFN's
                # down projection, where ``_dense_ffn`` applies it itself)
                ff = ff * _mup(cfg, "down")
            return x + ff, kvc

    # a period's layers in runs of one kind: a run of mixer layers is ONE body
    # under a scan of its own (stacked by kind, its weights and planes are a
    # run of their stacks); attention layers, window layers and convolutions
    # stay unrolled, as their cells were measured
    runs = [(j, 1) for j in range(period)]
    if cfg.has_ssm and not n_dense:
        mid = cfg.window_full_at
        runs = [r for r in ((0, mid), (mid, 1), (mid + 1, period - mid - 1)) if r[1]]

    def one_period(carry, p, first_dense: int = 0):
        """Period ``p`` (traced in the scan, static in front of it); its first
        ``first_dense`` layers have the dense FFN."""
        x, kvc = carry
        for j, n in runs:
            first, plane = p * period + j, kind_index(cfg, p, j)
            if n == 1:
                x, kvc = one_layer(x, kvc, first, j, plane, dense=j < first_dense)
                continue
            (x, kvc), _ = grouping.scan(
                lambda c, i, j=j, first=first, plane=plane: (one_layer(
                    *c, first + i, j, plane + i, dense=False), None),
                (x, kvc), jnp.arange(n, dtype=jnp.int32))
        return (x, kvc), None

    carry = (x, cache)
    lead = -(-n_dense // period)  # periods with a dense layer: unrolled
    for p in range(lead):
        carry, _ = one_period(carry, jnp.int32(p),
                              min(n_dense - p * period, period))
    n_periods = cfg.n_layers // period
    if n_periods > lead:
        carry, _ = grouping.scan(one_period, carry,
                                 jnp.arange(lead, n_periods, dtype=jnp.int32))
    return carry
