"""Unified transformer forward pass: Llama / Mixtral / Grok-1 / OLMoE /
DeepSeek-V2.

One function serves prefill (T > 1) and decode (T == 1): tokens enter as
``(B, T)``, the KV cache as ``(L, B, Hkv, S, Dh)`` pairs, and ``pos`` is a
traced scalar, so a single compiled program handles every step of
autoregression — the TPU answer to the reference's per-token task-list
execution (`Inference::infer`, tasks.cpp:199-210).

The layer loop is a ``lax.scan`` over layer-stacked weights. Structural
differences between the three reference task graphs
(llama2-tasks.cpp:241-298, grok1-tasks.cpp:275-354, mixtral-tasks.cpp:5-78)
are *static* config properties, so each arch compiles to its own fused
program:

* Llama   — pre-norm residual attention + SwiGLU FFN
* Mixtral — same attention, MoE FFN, rotate-half RoPE
* Grok-1  — embedding ×78.38…, post-sub-block rmsnorms before each residual
            add, MoE with GELU, logits ×0.577…
* Ouro    — Llama's dense block with the same post-sub-block rmsnorms, and
            the whole layer scan entered ``cfg.n_loops`` times over its own
            output (a looped model): one cache plane a (pass, layer)
* Falcon-H1 — attention and a Mamba-2 state-space mixer (``ops/ssm.py``) side
            by side in every block, both on one normed input, both added to
            the residual; muP multipliers (``cfg.mup_*``) on every branch

Tensor-parallel execution needs no code here: weights arrive sharded
(parallel/sharding.py) and XLA inserts the all-reduces the reference
hand-rolls as gather+merge (llama2-tasks.cpp:115-131).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import dispatch as obs_dispatch
from ..ops import conv, mla, q40, q8, retention, ssm
from ..ops.attention import (gqa_attention_at, paged_gqa_attention_at,
                             paged_update_kv_rows, paged_write_indices,
                             quantize_kv, slot_gqa_attention_at,
                             update_kv_cache_at, update_kv_cache_rows)
from ..ops.kernels import ACTIVATIONS, apply_rope, rmsnorm, rope_angles, softmax_f32
from ..ops.scopes import part, scope
from ..ops.sp_attention import ring_attention, sp_gqa_attention, sp_update_kv_cache_at
from ..parallel.mesh import get_active_mesh
from . import cache_kinds, grouping, packing, windowed
from .config import ModelConfig
from .params import DENSE_FFN_KEYS, MLA_ATT_KEYS, MOE_FFN_KEYS, Params


# Quantized-MoE prefill unrolls the per-expert loop statically up to this
# many experts (schedulable by XLA); larger counts switch to a lax.scan so
# compile time / program size stay O(1) in the expert count (see moe_ffn).
MOE_PREFILL_UNROLL_MAX = 8


class KVCache(NamedTuple):
    # (L, B, Hkv, S, Dh) — cfg dtype, or int8 when quantized; a paged pool
    # (init_kv_pool) is (L, P, ps, Hkv, Dh), its scale planes (L, P, ps, Hkv, 1).
    # L is ``cfg.n_cache_planes``: a plane a layer, and in a looped model a
    # plane a (pass, layer)
    k: jax.Array
    v: jax.Array
    # per-(layer, row, head, position) dequant scales, (L, B, Hkv, S, 1)
    # f32 — present only for the quantized cache.  Kept 5-D (trailing 1)
    # so one NamedSharding broadcast over the cache pytree shards values
    # and scales identically.
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None
    # a windowed model (models/windowed.py) only: its window layers' rings
    # (Lw, B, Hkv, R, Dh), or beside a paged pool its slots' rings of pages
    # (Lw, B * ring, ps, Hkv, Dh); k and v are then its full layers' planes
    wk: jax.Array | None = None
    wv: jax.Array | None = None
    # a model with short-convolution layers only: their state, a ring of the
    # last positions' ``z`` a row, (Lc, B, 1, R, D) on both engines (a slot
    # owns its row; ops/conv.py); k and v are then its attention layers' planes
    cz: jax.Array | None = None
    # a model of retention layers only (ops/retention.py): a row's state matrix
    # and sum a kv head, rs (L, B, Hkv, D, Dh) and rz (L, B, Hkv, 1, D) float32,
    # which no position and no page addresses; beside them its ring of recent
    # keys, values and log-gates, rk / rv (L, B, Hkv, R, Dh) and rg (L, B, 1,
    # R, Hkv), and its watermark rw (1, B, 1, 1, 1) int32: the state holds the
    # tokens before it, the ring the rest.  k and v then have no layer
    rs: jax.Array | None = None
    rz: jax.Array | None = None
    rk: jax.Array | None = None
    rv: jax.Array | None = None
    rg: jax.Array | None = None
    rw: jax.Array | None = None
    # a model with a state-space mixer beside attention in every block has
    # BOTH: k and v and, in retention's fields at the mixer's sizes (ops/ssm.py),
    # rs (L, B, H, N, P), rk (B), rv (x), rg (dt), rw, and cz (L, B, 1, R, C)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        return self.k.ndim == 4

    def planes(self) -> dict[str, jax.Array]:
        """The arrays this cache has, by field name: what a snapshot carries,
        whatever the cache's kind."""
        return {n: a for n, a in self._asdict().items() if a is not None}

    def pool_planes(self) -> dict[str, jax.Array]:
        """The planes of a paged pool that a page id addresses (what a spill
        or a hand-off record carries page by page): all of them but what
        belongs to a slot (``models/cache_kinds.py``: ``full``'s fields)."""
        return {n: a for n, a in self.planes().items()
                if n in cache_kinds.FULL.planes}


def _init_full(shape, cfg: ModelConfig, dtype, quant: bool, rows: int) -> KVCache:
    """Keys and values of ``shape`` a head (int8 with their scale planes where
    ``quant``), and a state-space mixer's planes for ``rows`` rows beside them
    where the model has one."""
    if quant:
        sshape = shape[:-1] + (1,)
        return KVCache(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                       jnp.zeros(sshape, jnp.float32),
                       jnp.zeros(sshape, jnp.float32))
    dt = dtype or cfg.dtype
    cache = KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
    if not cfg.has_ssm:
        return cache
    if rows < 1:
        raise ValueError("a pool beside a state-space mixer needs the number "
                         "of slots: each owns a state and its rings")
    return cache._replace(**ssm.init_planes(cfg, rows, dt, cfg.n_ssm_layers))


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None,
                  dtype=None, quant: bool = False) -> KVCache:
    """Preallocated full-length cache (reference: transformer.cpp:280-282).

    The reference holds F32 caches; dtype is configurable here because a
    bf16 cache halves HBM traffic in the decode attention — the main
    bandwidth consumer at long context.  ``quant=True`` goes further
    (beyond reference): int8 values + per-(head, position) f32 scales —
    ~1.97× less cache HBM traffic and residency than bf16 (the ~3%
    overhead is the scales), so max context per chip nearly doubles.
    Quantization happens at cache-write time (update_cache_at); attention
    dequantizes on read (block-wise on the long-context decode path, so
    the HBM read stays int8-sized).
    """
    s = seq_len or cfg.seq_len
    if quant:
        cache_kinds.refuse_int8(cfg)
    if cfg.attention_free:
        return _init_retention(cfg, batch, dtype)
    if cfg.periodic:
        return windowed.init_cache(cfg, batch, s, dtype)
    if cfg.is_mla:
        return _init_latent((cfg.n_layers, batch, s), cfg, dtype)
    shape = (cfg.n_cache_planes, batch, cfg.n_kv_heads, s, cfg.head_size)
    return _init_full(shape, cfg, dtype, quant, batch)


# axis order inside one pool page, by name: part of the snapshot and
# DLREQ01 fingerprints (runtime/engine.py), so a file written with another
# order is refused even where the sizes coincide
PAGE_AXES = "ps,Hkv,Dh"
LATENT_PAGE_AXES = "ps,r|ps,rope"  # a latent (MLA) pool's page, plane by plane


def _init_retention(cfg: ModelConfig, rows: int, dtype) -> KVCache:
    """The cache of a model of retention layers, the contiguous engine's and a
    slot engine's alike (``rows``: sequences, or slots): no layer has keys and
    values, so ``k`` / ``v`` have no layer and no position, and what a row
    leaves behind is its state and its ring of recent positions
    (``ops/retention.py``), a fixed size whatever the context's depth."""
    dt = dtype or cfg.dtype
    none = jnp.zeros((0, rows, cfg.n_kv_heads, 0, cfg.head_size), dt)
    return KVCache(none, none, **retention.init_planes(
        cfg.n_layers, rows, cfg.n_kv_heads, cfg.head_size, dt))


def _init_latent(lead, cfg: ModelConfig, dtype) -> KVCache:
    """MLA's cache in either form, ``lead`` = (L, B, S) or (L, P, ps):
    ``kv_lora_rank + qk_rope_head_dim`` values a token a layer in two planes
    (ops/mla.py has why two), nothing per head."""
    dt = dtype or cfg.dtype
    return KVCache(jnp.zeros(lead + (cfg.kv_lora_rank,), dt),
                   jnp.zeros(lead + (cfg.qk_rope_head_dim,), dt))


def init_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                 dtype=None, quant: bool = False, slots: int = 0,
                 max_pages: int | None = None) -> KVCache:
    """Paged KV pool ``(L, n_pages, page_size, Hkv, Dh)``: physical pages
    in place of the batch axis, and each page token-major, so a token's
    (Hkv, Dh) slab is contiguous and the per-token KV write's layout is
    the pool's own (the contiguous cache keeps (L, B, Hkv, S, Dh): its
    writes are windows along S).  The pool has a sharding spec of its own
    (``parallel/sharding.py kv_pool_sharding``: kv heads on axis 3).  Page 0
    is the reserved scratch page (see ops.attention paged section); slots
    address the pool through per-slot page tables, so pool memory is
    bounded by live *tokens*, not slots × max-seq.

    ``quant=True`` (``--kv-quant int8``) stores int8 values plus a
    per-(page, position, head) f32 scale plane ``(L, P, ps, Hkv, 1)`` —
    the page-granular mirror of the contiguous quantized cache's codec
    (same quantize_kv absmax math, same ~2× HBM saving), so a pool page
    is self-describing: values and scales always travel together through
    spills, snapshots and DLREQ01 hand-offs.

    ``k`` / ``v`` are the pool of the layers that keep keys and values alone
    (none in a model of retention layers: a pool of no pages); every other kind
    of plane (``KVCache``) is there once for each of ``slots`` slots, a window
    layer's as a ring of pages bounded by the window (``models/windowed.py
    init_pool``; ``max_pages``: a slot's table width, which bounds the ring)."""
    if quant:
        cache_kinds.refuse_int8(cfg)
    if cfg.attention_free:
        return _init_retention(cfg, slots, dtype)
    if cfg.periodic:
        return windowed.init_pool(cfg, n_pages, page_size, dtype, slots,
                                  max_pages or n_pages)
    if cfg.is_mla:
        # (L, P, ps, ·): the same token-major page, one row a token a plane
        return _init_latent((cfg.n_layers, n_pages, page_size), cfg, dtype)
    # heads stay one to a row here, whatever their size (``attention.pool_rows``
    # folds narrow heads for a periodic model's pool): this pool is the one a
    # mesh shards by kv head on axis 3 (``kv_pool_sharding``) and that
    # ``--kv-quant int8`` gives a scale a head, both of which a periodic model
    # refuses by name.  An arch here with heads under 128 lanes (none of the
    # supported ones on one chip) pays the layout copies ``pool_rows`` names.
    shape = (cfg.n_cache_planes, n_pages, page_size, cfg.n_kv_heads, cfg.head_size)
    return _init_full(shape, cfg, dtype, quant, slots)


def _mm(x, w, cfg: ModelConfig, kind: str | None = None):
    """Matmul that accepts dense arrays or packed Q40 weights.  Weight
    dtype/format is a per-tensor property (the reference likewise
    dispatches per weight dtype, funcs.cpp:414-455).  ``kind`` declares the
    weight's TP slicing ("row"/"col", commands.cpp:8-70) so the fused
    kernel can run per shard on a multi-device mesh (ops/q40.py)."""
    return q40.mm(x, w, impl=cfg.quant_impl, kind=kind).astype(cfg.dtype)


def update_cache_at(cache: KVCache, k_new, v_new, layer, pos) -> KVCache:
    """Write one layer's step KV window into the stacked cache at
    ``(layer, pos)`` — quantizing to int8 + per-position scales first when
    the cache is quantized (see init_kv_cache)."""
    with scope("kv_write"):
        if not cache.quantized:
            ck, cv = update_kv_cache_at(cache.k, cache.v, k_new, v_new,
                                        layer, pos)
            return cache._replace(k=ck, v=cv)
        qk, sk = quantize_kv(k_new)
        qv, sv = quantize_kv(v_new)
        zero = jnp.zeros((), layer.dtype)
        idx = (layer, zero, zero, pos.astype(layer.dtype), zero)
        return KVCache(
            jax.lax.dynamic_update_slice(cache.k, qk[None], idx),
            jax.lax.dynamic_update_slice(cache.v, qv[None], idx),
            jax.lax.dynamic_update_slice(cache.k_scale, sk[None], idx),
            jax.lax.dynamic_update_slice(cache.v_scale, sv[None], idx))


def _mup(cfg: ModelConfig, name: str):
    """A muP multiplier of the published config (Falcon-H1) in the activation
    dtype; every use is guarded by ``!= 1.0``, so no other arch's program has
    the multiply."""
    return jnp.asarray(getattr(cfg, "mup_" + name), cfg.dtype)


def _project_out(att, lp, cfg: ModelConfig):
    """An attention sub-block's output projection (row-local; col-sharded on
    a tp mesh: partial sums all-reduced here)."""
    with scope("wo"):
        out = _mm(att, lp["wo"], cfg, kind="col")
        return out if cfg.mup_attn_out == 1.0 else out * _mup(cfg, "attn_out")


def _attention_block(x, lp, cfg: ModelConfig, cache: KVCache, cos, sin, pos,
                     layer, offsets=None, pos_rows=None, paged=None,
                     packed=None):
    """One attention sub-block.  ``cache`` holds the *stacked*
    (L, B, Hkv, S, Dh) buffers carried through the layer scan; this layer
    writes its (B, Hkv, T, Dh) step window in place at ``(layer, pos)`` and
    reads back only its own layer slice for attention (see
    ops.attention.update_kv_cache_at for the cost model).  Two indices: the
    weight set is ``lp`` (the caller's view of layer ``l``) and ``layer`` is
    the CACHE PLANE, ``l`` itself unless the stack runs several times
    (``cfg.n_cache_planes``).  With ``packed``
    (a slot step at ``t > 1``, models/packing.py) the two projections run over
    the rows that hold a token; everything between them keeps (B, T)."""
    b, t, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_size

    def project(x):  # row-local: any leading axes
        with scope("norm"):
            xb = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
        with scope("qkv"):
            if cfg.mup_attn_in != 1.0:
                xb = xb * _mup(cfg, "attn_in")
            if "wqkv" in lp:  # fused projection (quantized load): one kernel launch
                qkv = _mm(xb, lp["wqkv"], cfg)
                q, k, v = jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=-1)
            else:
                q = _mm(xb, lp["wq"], cfg, kind="row")
                k = _mm(xb, lp["wk"], cfg, kind="row")
                v = _mm(xb, lp["wv"], cfg, kind="row")
            if cfg.mup_key != 1.0:  # before the rotation and the write
                k = k * _mup(cfg, "key")
            if cfg.qk_norm:
                # over the whole projection, before the head split and RoPE; on
                # a tp mesh q and k are sharded on this axis and the mean is
                # GSPMD's all-reduce (tests/test_olmoe.py, 4-device CPU mesh)
                q = rmsnorm(q, lp["q_norm"])
                k = rmsnorm(k, lp["k_norm"])
            lead = x.shape[:-1]
            return (q.reshape(*lead, hq, dh), k.reshape(*lead, hkv, dh),
                    v.reshape(*lead, hkv, dh))

    q, k, v = packing.over(packed, "qkv", project, x)

    with scope("rope"):
        q = apply_rope(q, cos, sin, interleaved=cfg.rope_interleaved)
        k = apply_rope(k, cos, sin, interleaved=cfg.rope_interleaved)

        q = q.transpose(0, 2, 1, 3)  # (B, Hq, T, Dh)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    mesh = get_active_mesh()
    sp_on = mesh is not None and mesh.shape.get("sp", 1) > 1
    ring = sp_on and cfg.ring_prefill and t > 1
    if pos_rows is not None:
        # continuous-batching slots: per-row write positions and per-row
        # causal ceilings (sp meshes and quantized caches are gated off
        # the slot path at the engine boundary)
        if paged is not None:
            # paged pool: same slot semantics, reads/writes indirected
            # through the page table (write indices precomputed once in
            # forward_slots — identical for every layer)
            page_table, pidx, oidx = paged
            if cache.quantized:
                # int8 pages: quantize the step window once, scatter
                # values and per-position scales through the same write
                # indices, and let attention dequantize on read
                with scope("kv_write"):
                    qk, sk = quantize_kv(k)
                    qv, sv = quantize_kv(v)
                    ck, cv = paged_update_kv_rows(cache.k, cache.v, qk, qv,
                                                  layer, pidx, oidx)
                    csk, csv = paged_update_kv_rows(
                        cache.k_scale, cache.v_scale, sk, sv, layer, pidx,
                        oidx)
                    cache = KVCache(ck, cv, csk, csv)
                with scope("attn"):
                    att = paged_gqa_attention_at(
                        q, cache.k, cache.v, layer, page_table, pos_rows,
                        scales=(cache.k_scale, cache.v_scale))
            else:
                with scope("kv_write"):
                    ck, cv = paged_update_kv_rows(cache.k, cache.v, k, v,
                                                  layer, pidx, oidx)
                    cache = cache._replace(k=ck, v=cv)
                with scope("attn"):
                    att = paged_gqa_attention_at(q, cache.k, cache.v, layer,
                                                 page_table, pos_rows)
        else:
            with scope("kv_write"):
                ck, cv = update_kv_cache_rows(cache.k, cache.v, k, v, layer,
                                              pos_rows)
                cache = cache._replace(k=ck, v=cv)
            with scope("attn"):
                att = slot_gqa_attention_at(q, cache.k, cache.v, layer,
                                            pos_rows)
        with scope("attn"):
            att = att.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
        return packing.over(packed, "wo", _project_out, att, lp=lp,
                            cfg=cfg), cache
    if t == 1 and sp_on:
        # seq-sharded cache: explicit shard-local write (no GSPMD-chosen
        # gather/scatter per decode step); quantized caches are gated off
        # sp meshes at the engine boundary
        with scope("kv_write"):
            ck, cv = sp_update_kv_cache_at(cache.k, cache.v, k, v, layer, pos,
                                           mesh)
            cache = cache._replace(k=ck, v=cv)
    else:
        cache = update_cache_at(cache, k, v, layer, pos)
    with scope("attn"):
        att = _attend(q, k, v, cache, cfg, pos, t, layer, offsets, mesh,
                      sp_on, ring)
        att = att.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
    return _project_out(att, lp, cfg), cache


def _retention_block(x, lp, cfg: ModelConfig, cache: KVCache, cos, sin, pos,
                     layer, marks, offsets=None, pos_rows=None, packed=None):
    """One power-retention sub-block (``ops/retention.py`` has the operator and
    why its state lags the clock).  The projections, the head norms and the
    gate are row-local and pack; the fold, the ring's write and the read are a
    per-row sequence operation and keep ``(B, T)``, as ``rope`` and a KV write
    do.  ``marks``: the call's watermarks ``(w, w_new)``, the same for every
    layer (``retention.clock``, once a step)."""
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
            lead = x.shape[:-1]
            q = q.reshape(*lead, hq, dh)
            k = k.reshape(*lead, hkv, dh)
            with part("qk_norm"):
                q = rmsnorm(q, lp["q_norm"], cfg.norm_eps)
                k = rmsnorm(k, lp["k_norm"], cfg.norm_eps)
            with part("retention"):  # one gate a kv head, float32
                lg = jax.nn.log_sigmoid(jnp.matmul(
                    xb.astype(jnp.float32), lp["wg"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
            return q, k, v.reshape(*lead, hkv, dh), lg

    q, k, v, lg = packing.over(packed, "qkv", project, x)
    with scope("rope"):
        q = apply_rope(q, cos, sin, interleaved=False).transpose(0, 2, 1, 3)
        k = apply_rope(k, cos, sin, interleaved=False).transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)                              # (B, Hkv, T, Dh)
        lg = lg.transpose(0, 2, 1)                               # (B, Hkv, T)
    rows = pos_rows if pos_rows is not None else jnp.broadcast_to(pos, (b,))
    w, w_new = marks
    c = cache
    with scope("kv_write"):
        with part("fold"):
            rs, rz = retention.fold(c.rs, c.rz, c.rk, c.rv, c.rg, layer, w,
                                    w_new, floor=offsets)
        with part("recent"):
            rk, rv, rg = retention.write(c.rk, c.rv, c.rg, k, v, lg, layer, rows)
        cache = c._replace(rs=rs, rz=rz, rk=rk, rv=rv, rg=rg)
    with scope("attn"):
        att = retention.read(q, rs, rz, rk, rv, rg, layer, rows, w_new,
                             floor=offsets)
        att = att.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
    return packing.over(packed, "wo", _project_out, att, lp=lp, cfg=cfg), cache


def _ssm_block(x, lp, cfg: ModelConfig, cache: KVCache, pos, layer, marks,
               offsets=None, pos_rows=None, packed=None, n_real=None):
    """A state-space mixer (``ops/ssm.py`` has the operator and why its state
    lags the clock).  In Falcon-H1 the second branch of a block whose first is
    :func:`_attention_block` over the same cache: it norms ``x`` with the same
    vector.  In Granite the whole of a mixer layer's first sub-block
    (``models/windowed.py``).  ``layer`` is the layer's place among the layers
    that have a mixer, which indexes the planes (``cfg.n_ssm_layers`` deep) and
    is the index ``lp``'s mixer weights were taken at.  The projections,
    ``dt``, the gate and the grouped norm are row-local and pack; the
    convolution, the fold, the rings' write and the read are a per-row
    sequence operation and keep ``(B, T)``.  ``marks``: the
    call's watermarks, as :func:`_retention_block`'s."""
    b, t, _ = x.shape
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, f32 = cfg.ssm_inner, jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def project(x):  # row-local: any leading axes
        with scope("norm"):
            u = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
        with scope("qkv"), part("ssm"):
            if cfg.mup_ssm_in != 1.0:
                u = u * _mup(cfg, "ssm_in")
            z, xbc = jnp.split(_mm(u, lp["ssm_in"], cfg), [inner], axis=-1)
            # ``ssm_multipliers`` (Falcon-H1), in the order of ``W_in``'s split
            scaled = (cfg.mup_z, cfg.mup_x, cfg.mup_b, cfg.mup_c, cfg.mup_dt) != (1.0,) * 5
            if scaled:
                mup = jnp.repeat(
                    jnp.asarray([cfg.mup_x, cfg.mup_b, cfg.mup_c], cfg.dtype),
                    jnp.asarray([inner, g * n, g * n]),
                    total_repeat_length=cfg.ssm_channels)
            dt = jnp.matmul(u.astype(f32), lp["ssm_dt"].astype(f32), precision=hi)
            if scaled:
                dt = dt * cfg.mup_dt
            dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
            return (z * _mup(cfg, "z"), xbc * mup, dt) if scaled else (z, xbc, dt)

    def project_out(y, z, lp, cfg):
        with scope("wo"), part("ssm"):
            # gate first, then RMSNorm over each group (mamba_norm_before_gate
            # false), in float32
            y = (y * jax.nn.silu(z.astype(f32))).reshape(*y.shape[:-1], g, -1)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
            y = y.reshape(*z.shape) * lp["ssm_norm"]
            out = _mm(y.astype(cfg.dtype), lp["ssm_out"], cfg)
            return out if cfg.mup_ssm_out == 1.0 else out * _mup(cfg, "ssm_out")

    z, xbc, dt = packing.over(packed, "qkv", project, x)
    rows = pos_rows if pos_rows is not None else jnp.broadcast_to(pos, (b,))
    w, w_new = marks
    a = -jnp.exp(lp["ssm_a_log"])
    c, taps = cache, cfg.ssm_conv
    # the convolution's ring is written FIRST and read after: a call's rows do
    # not reach the taps - 1 positions before it (ring >= T + taps - 1), and a
    # read that precedes the writes makes XLA copy the whole plane a layer
    # (twice 377 MB at the published widths; tests/test_tpu_compile.py)
    with scope("kv_write"), part("conv"):
        cz = conv.state_write(c.cz, xbc, layer, rows, taps, n_real)
    with scope("attn"), part("conv"):
        conv.record(t, cz.shape[3], taps)
        carried = conv.state_read(cz, layer, rows, taps, floor=offsets)
        xbc_c = jax.nn.silu(conv.taps(xbc, carried, lp["ssm_conv_w"], rows,
                                      floor=offsets) + lp["ssm_conv_b"]
                            ).astype(cfg.dtype)
        xs, bm, cm = (v.reshape(b, t, k, -1).transpose(0, 2, 1, 3) for v, k in zip(
            jnp.split(xbc_c, [inner, inner + g * n], axis=-1), (h, g, g)))
    with scope("kv_write"):
        with part("fold"):
            # the fold reads the rings as the last call left them; nothing else
            # orders it before this call's writes, and without the barrier XLA
            # kept the old x ring for it: the whole plane copied twice a layer
            # in the mixed step (tests/test_tpu_compile.py)
            rs, rk, rv, rg = jax.lax.optimization_barrier((ssm.fold(
                c.rs, c.rk, c.rv, c.rg, a, layer, w, w_new), c.rk, c.rv, c.rg))
        with part("recent"):
            rk, rv, rg = ssm.write(rk, rv, rg, bm, xs, ssm.live_dt(
                dt, rows, offsets, n_real), layer, rows)
        cache = c._replace(rs=rs, rk=rk, rv=rv, rg=rg, cz=cz)
    with scope("attn"):
        y = ssm.read(cm, rs, rk, rv, rg, a, layer, rows, w_new)   # (B, H, T, P)
        with part("recent"):
            y = y + lp["ssm_d"][None, :, None, None] * xs.astype(f32)
            y = y.transpose(0, 2, 1, 3).reshape(b, t, inner)
    return packing.over(packed, "wo", project_out, y, z, lp=lp, cfg=cfg), cache


def _attend(q, k, v, cache: KVCache, cfg: ModelConfig, pos, t, layer, offsets,
            mesh, sp_on: bool, ring: bool):
    """The contiguous-cache attention forms of :func:`_attention_block`."""
    if sp_on:
        # ragged batches are gated off sp meshes at the engine boundary
        # (Engine.generate_batch raises), so offsets is always None here
        if ring:
            # from-scratch prefill: the fresh block IS the whole history
            # (engine gates this on pos==0), so attend blockwise over the
            # sequence-sharded q/k/v ring — no cache read, O(T/sp) memory
            return ring_attention(q, k, v, mesh, pos0=pos)
        # sequence-parallel decode / continuation: seq-sharded cache,
        # one-round distributed softmax combine; the layer is sliced
        # inside the shard body (see sp_gqa_attention)
        return sp_gqa_attention(q, cache.k, cache.v, pos, t, mesh,
                                layer=layer)
    return gqa_attention_at(
        q, cache.k, cache.v, layer, pos, t, start=offsets,
        scales=((cache.k_scale, cache.v_scale) if cache.quantized else None))


def _mla_attention_block(x, lp, cfg: ModelConfig, cache: KVCache, cos, sin,
                         pos, layer, offsets=None, pos_rows=None, paged=None,
                         packed=None):
    """DeepSeek-V2's attention sub-block (ops/mla.py has the two forms).  The
    layer writes its tokens' latent rows into the stacked cache in place and
    reads the live part back; per-head keys and values exist only inside the
    attention call.  ``packed``: as :func:`_attention_block`."""
    h, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    eps = cfg.norm_eps

    def project(x):  # row-local: any leading axes
        with scope("norm"):
            xb = rmsnorm(x, lp["rms_att"], eps)
        with scope("qkv"):
            with part("kv_lora"):
                if "wqkv_a" in lp:  # both down-projections from x: one launch
                    c_q, ckv = jnp.split(_mm(xb, lp["wqkv_a"], cfg),
                                         [cfg.q_lora_rank], axis=-1)
                else:
                    ckv = _mm(xb, lp["wkv_a"], cfg)
                c_kv = rmsnorm(ckv[..., :r], lp["kv_a_norm"], eps)
            with part("q_lora"):
                if "wqkv_a" not in lp:
                    c_q = _mm(xb, lp["wq_a"], cfg)
                q = _mm(rmsnorm(c_q, lp["q_a_norm"], eps), lp["wq_b"], cfg)
                return q.reshape(*x.shape[:-1], h, cfg.qk_head_dim), ckv, c_kv

    q, ckv, c_kv = packing.over(packed, "qkv", project, x)
    with scope("rope"):
        # adjacent pairs, as the published rows have them; one key for all heads
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], cos, sin, interleaved=True)],
            -1)
        k_pe = apply_rope(ckv[..., None, r:], cos, sin, interleaved=True)[..., 0, :]
    page_table = paged[0] if paged is not None else None

    def write(plane, rows):
        if paged is not None:
            return mla.write_paged(plane, rows, layer, *paged[1:])
        if pos_rows is not None:
            return mla.write_rows(plane, rows, layer, pos_rows)
        return mla.write_at(plane, rows, layer, pos)

    with scope("kv_write"):
        cache = KVCache(write(cache.k, c_kv), write(cache.v, k_pe))
    with scope("attn"):
        w_kvb = lp["wkv_b"].reshape(r, h, dn + cfg.v_head_dim)
        att = mla.attention(q, cache.k, cache.v, w_kvb, cfg, layer, pos=pos,
                            pos_rows=pos_rows, page_table=page_table,
                            floor=offsets)
    return packing.over(packed, "wo", _project_out, att, lp=lp,
                        cfg=cfg), cache


def _swiglu(xb, lp, cfg: ModelConfig, pre: str):
    """``W2 (act(W1 x) * W3 x)`` of the weights ``pre + "1"`` ... (or the
    fused ``pre + "13"``), under the caller's scope."""
    act = ACTIVATIONS[cfg.hidden_act]
    if pre + "13" in lp:
        h1, h3 = jnp.split(_mm(xb, lp[pre + "13"], cfg), 2, axis=-1)
    else:
        h1 = _mm(xb, lp[pre + "1"], cfg, kind="row")
        h3 = _mm(xb, lp[pre + "3"], cfg, kind="row")
    return _mm(act(h1) * h3, lp[pre + "2"], cfg, kind="col")


def _dense_ffn(xb, lp, cfg: ModelConfig):
    act = ACTIVATIONS[cfg.hidden_act]
    if "w13" in lp:  # fused gate+up (quantized load)
        with scope("w13"):
            h13 = _mm(xb, lp["w13"], cfg)
            h1, h3 = jnp.split(h13, 2, axis=-1)
            if cfg.mup_gate != 1.0:
                h1 = h1 * _mup(cfg, "gate")
            h = act(h1) * h3
    else:
        with scope("w1"):
            h1 = _mm(xb, lp["w1"], cfg, kind="row")
            if cfg.mup_gate != 1.0:
                h1 = h1 * _mup(cfg, "gate")
            h1 = act(h1)
        with scope("w3"):
            h = h1 * _mm(xb, lp["w3"], cfg, kind="row")
    with scope("w2"):
        out = _mm(h, lp["w2"], cfg, kind="col")
        return out if cfg.mup_down == 1.0 else out * _mup(cfg, "down")


def moe_ffn(xb2d: jax.Array, lp, cfg: ModelConfig,
            router_logits: jax.Array | None = None) -> jax.Array:
    """The routed experts (:func:`_routed_experts`, every strategy) plus,
    where the layer has one (DeepSeek-V2), the shared expert every row takes:
    sub-scope ``shared`` inside ``moe``."""
    out = _routed_experts(xb2d, lp, cfg, router_logits)
    if "shared_w2" in lp:
        with part("shared"):
            out = out + _swiglu(xb2d, lp, cfg, "shared_w")
    return out


@functools.partial(jax.jit, static_argnames=("held", "tr", "act", "impl", "dtype"))
def _grouped_experts(xb2d, sel_idx, sel_w, here, stacks, layers, *, held: int,
                     tr: int, act, impl: str, dtype):
    """:func:`_routed_experts`'s strategy ``grouped`` from the router's choice
    on: the pairs sorted by expert into blocks of ``tr`` rows, a block one
    expert's (``models/grouping.py``), gate, up and down one launch each of
    ``q40_mm_grouped`` over the blocks that hold rows, and a row's k results
    gathered and summed.  Returns the rows' outputs and the blocks used.

    ``stacks`` / ``layers``: the three expert stacks (``QTensor``) and the
    traced index of this layer's planes in each; ``here``: the pairs whose
    expert this chip holds (None: all).  One jitted function, so that a
    program's expert layers, all of one shape, are traced and lowered once
    and not once a layer: a prompt's program started 0.4 to 0.6 s later a
    program without it (PERF.md section 6, PR 53).  Its cache outlives a
    program, so the dispatch ledger is the caller's to write: nothing in here
    records a site."""
    n, d = xb2d.shape
    m = grouping.blocks(n, sel_idx.shape[1], held, tr)
    gate, up, down = (q40.QLayerView(qt, i) for qt, i in zip(stacks, layers))
    with part("router"):
        gp = grouping.plan(sel_idx, held, tr, here)
    with part("experts"):
        xg = xb2d.at[gp.gather].get(mode="promise_in_bounds").reshape(m, tr, d)
        launch = functools.partial(q40.matmul_experts, experts=held, impl=impl,
                                   chosen=gp.planes, used=gp.used, record=False)
        g, u = launch(xg, gate), launch(xg, up)
        o = launch(ACTIVATIONS[act](g) * u, down, out_dtype=jnp.float32)  # (M, tr, D)
    with part("combine"):
        # a row's k pairs, where the plan put them; a pair of an expert held
        # elsewhere has no slot and what stands at the one it reads may be
        # anything
        own = o.reshape(m * tr, d).at[gp.slot].get(mode="promise_in_bounds")
        if here is not None:
            own = jnp.where(here[..., None], own, 0.0)
        return (sel_w[..., None] * own).sum(1).astype(dtype), gp.used


def route(xb2d: jax.Array, lp, cfg: ModelConfig,
          router_logits: jax.Array | None = None
          ) -> tuple[jax.Array, jax.Array]:
    """The router's choice for every row: ``(top_idx, weights)``, both
    ``(N, k)``, the experts among all ``cfg.n_experts`` and what each weighs
    (:func:`_routed_experts` says how, per architecture)."""
    n, e, k = xb2d.shape[0], cfg.n_experts, cfg.n_active_experts
    with part("router"):
        if router_logits is None:
            router = lp["router"]
            router_logits = xb2d.astype(jnp.float32) @ router.astype(jnp.float32)  # (N, E)
        if cfg.router_sigmoid:
            probs = jax.nn.sigmoid(router_logits)
            # the bias moves the choice, never the weights
            _, top_idx = jax.lax.top_k(probs + lp["router_bias"].astype(jnp.float32), k)
            top_vals = jnp.take_along_axis(probs, top_idx, axis=-1)
        else:
            probs = softmax_f32(router_logits)
            if cfg.n_groups > 1:
                # a group's score is its best expert's; experts outside the
                # topk_groups best groups are out of the top-k (their p set to 0)
                best = probs.reshape(n, cfg.n_groups, -1).max(-1)
                _, gidx = jax.lax.top_k(best, cfg.topk_groups)
                kept = jnp.put_along_axis(jnp.zeros(best.shape, bool), gidx, True,
                                          axis=-1, inplace=False)
                probs = jnp.where(jnp.repeat(kept, e // cfg.n_groups, axis=-1),
                                  probs, 0.0)
            top_vals, top_idx = jax.lax.top_k(probs, k)  # (N, k)
        weights = top_vals
        if cfg.norm_topk_prob:
            total = jnp.sum(top_vals, axis=-1, keepdims=True)
            if cfg.router_norm_eps:  # LFM2's ``+ 1e-6``; K-EXAONE has none
                total = total + jnp.float32(cfg.router_norm_eps)
            weights = top_vals / total
        if cfg.routed_scale != 1.0:
            weights = weights * jnp.float32(cfg.routed_scale)
    return top_idx, weights


def _routed_experts(xb2d: jax.Array, lp, cfg: ModelConfig,
                    router_logits: jax.Array | None = None) -> jax.Array:
    """Mixture-of-experts FFN (grok1-tasks.cpp:56-228 semantics).

    Routing: softmax over *all* expert logits, top-k, renormalize the
    selected probabilities (grokMoeRouterSoftmax/Topk/NormWeights,
    grok1-tasks.cpp:60-114); OLMoE (``not cfg.norm_topk_prob``) uses the
    selected probabilities as they are.  DeepSeek-V2 (``cfg.n_groups > 1``)
    chooses in two stages, ``topk_groups`` groups by their best expert and
    then the top-k of the experts in them, and scales the chosen
    probabilities by ``cfg.routed_scale``; every strategy below takes its
    experts and weights from this one choice.  K-EXAONE
    (``cfg.router_sigmoid``) scores with a sigmoid, adds ``lp["router_bias"]``
    for the choice only, and normalises and scales the chosen scores; LFM2
    does the same over the sum ``+ cfg.router_norm_eps``.  The
    logits are this layer's
    FFN input times ``lp["router"]`` unless the caller hands ``router_logits``
    ``(N, E)`` made elsewhere (SmallThinker's router reads the layer's input
    before attention, ``models/windowed.py``).

    A layer may hold planes for a run of its experts only
    (``cfg.n_experts_held`` of them from ``cfg.first_expert``: one chip's share
    of an expert-parallel deployment).  The router then still chooses among all
    ``E`` and weighs over all ``k`` chosen, and the result is the held experts'
    part of the sum: a chosen expert that lives elsewhere contributes nothing
    here (every strategy: weight 0; ``all-experts`` and the loops never visit
    it, ``grouped`` gives the pair no slot, ``select-chosen`` / ``select``
    point its grid step at a held plane, which is read in its stead).  The
    ledger records ``held`` beside ``experts``.

    Sub-scopes inside ``moe``: ``router`` (logits, softmax, top-k),
    ``experts`` (the expert matmuls and, on the scan, its bookkeeping),
    ``combine`` (the weighted sum and the cast to the activation dtype).  Each
    compiled call site records its strategy in the dispatch ledger as
    ``{codec="moe", path="select"|"select-chosen"|"all-experts"|"grouped"|"scan"|
    "unrolled"|"dense"}``.

    Execution strategies, chosen statically (token count, packed or not,
    mesh, kernel path; no flag):
    * up to 4 tokens: compute only the k selected experts, so HBM reads are
      bounded by the k active experts' *packed* bytes (the reference likewise
      keeps MoE Q40 end-to-end, transformer.cpp:299-317).  Packed Q40 experts
      on one device with the fused kernel chosen (``q40.all_experts_impl``;
      every one-stream decode step on a TPU) take ``select-chosen``: a row's
      k planes are a grid axis of ``q40_mm_chosen``, gate, up and down one
      launch each, then one weighted sum over k.  With ``quant_impl="xla"``,
      on any mesh, and with Q80 experts (``select``) each (token, slot) pair
      runs the fused dequant-matmul on a ``QLayerView`` whose flat index
      selects the expert; dense experts use a gather + einsum.
    * 5 to 16 tokens: run every expert and mask.  Packed Q40 experts on one
      device with the fused kernel chosen (``q40.all_experts_impl``; every
      served pure-decode step on a TPU) take ``all-experts``: gate, up and down
      are one launch each of ``q40_mm_experts`` over all E experts, then one
      weighted sum over E.
    * more than 16 tokens, on the same condition (a prompt's bucket, a prefill
      chunk, a packed mixed step): ``grouped``, a row goes to its own k
      experts.  The ``rows x k`` pairs are sorted by expert into blocks of
      ``tr`` rows, a block one expert's (``models/grouping.py``: ``tr`` from
      the mean rows an expert gets, one choice a call site), and gate, up and
      down are one launch each of ``q40_mm_grouped`` over the blocks that hold
      rows, each block's plane in a prefetched vector; then a row's k results
      are gathered and summed.  No ``(E, rows, .)`` array is built.  The
      result is ``all-experts``'s but for the order of a float32 sum over k
      terms, where that one summed E of which E - k were zeros.
    * more tokens otherwise: run every expert and mask.
      With ``quant_impl="xla"``, on any mesh, and with Q80 experts, the loop
      over experts stays: a static unroll up to MOE_PREFILL_UNROLL_MAX
      (``unrolled``), a ``lax.scan`` past it (``scan``), one expert's weights
      dequantized at a time.  Dense experts: one einsum (``dense``).

    Experts are TP-sliced like the reference (all experts on all shards,
    hidden dim sharded — transformer.cpp:299-317).  Under an ``ep`` mesh
    axis the expert stacks additionally shard over experts — dense via the
    PartitionSpecs (GSPMD inserts the gather), packed Q40 via the fused
    kernel's per-shard flat-index decode + psum (q40._sharded_matmul_ep) —
    so MoE weight residency scales 1/ep in both layouts.
    """
    n, d = xb2d.shape
    e, k = cfg.n_experts, cfg.n_active_experts
    # the stacks' planes a layer: all e, or this chip's share of them
    held, first = cfg.n_experts_held, cfg.first_expert
    share = held != e
    site = dict(experts=e, held=held) if share else dict(experts=e)
    act = ACTIVATIONS[cfg.hidden_act]

    top_idx, weights = route(xb2d, lp, cfg, router_logits)
    with part("router"):
        if share:
            # the few-row strategies index planes: a chosen expert held
            # elsewhere gets weight 0 and a held plane to stand on
            here = (top_idx >= first) & (top_idx < first + held)
            sel_w = jnp.where(here, weights, 0.0)
            sel_idx = jnp.where(here, top_idx - first, 0)
        else:
            sel_w, sel_idx = weights, top_idx

    quant = isinstance(lp["up"], (q40.QTensor, q40.QLayerView))

    if n <= 4 and quant:
        # decode, packed experts: each row runs its k chosen experts alone
        views = (lp["gate"], lp["up"], lp["down"])
        kernel = q40.all_experts_impl(views, 1, cfg.quant_impl)
        obs_dispatch.record_dispatch(
            "moe", "select-chosen" if kernel else "select", rows=n, **site)

        def chosen_row(i):
            # one device, the fused kernel: the row's chosen planes are a grid
            # axis, three launches whatever k
            xi, idx = xb2d[i:i + 1], sel_idx[i]
            with part("experts"):
                g, u = (q40.matmul_experts(xi, w, held, kernel, chosen=idx)
                        for w in views[:2])
                o = q40.matmul_experts(act(g) * u, views[2], held, kernel,
                                       out_dtype=jnp.float32, chosen=idx)  # (k, 1, D)
            with part("combine"):
                return (sel_w[i][:, None, None] * o).sum(0)

        def looped_row(i):
            # a mesh, Q80 experts, the XLA path: per-(token, slot) matmuls on
            # the selected expert's packed planes
            xi = xb2d[i:i + 1]
            acc = jnp.zeros((1, d), jnp.float32)
            for j in range(k):
                with part("experts"):
                    sel = sel_idx[i, j]
                    up = lp["up"].select(sel, held)
                    gate = lp["gate"].select(sel, held)
                    down = lp["down"].select(sel, held)
                    h = act(_mm(xi, gate, cfg, kind="row")) * _mm(xi, up, cfg, kind="row")
                    o = q40.mm(h, down, impl=cfg.quant_impl, kind="col",
                               out_dtype=jnp.float32)
                with part("combine"):
                    acc = acc + sel_w[i, j] * o
            return acc

        outs = [(chosen_row if kernel else looped_row)(i) for i in range(n)]
        with part("combine"):
            return jnp.concatenate(outs, 0).astype(cfg.dtype)

    if n <= 4 and not quant:  # decode path: gather selected experts' weights
        obs_dispatch.record_dispatch("moe", "select", rows=n, **site)
        with part("experts"):
            up_w = jnp.take(lp["up"], sel_idx, axis=0)      # (N, k, D, F)
            gate_w = jnp.take(lp["gate"], sel_idx, axis=0)  # (N, k, D, F)
            down_w = jnp.take(lp["down"], sel_idx, axis=0)  # (N, k, F, D)
            h = act(jnp.einsum("nd,nkdf->nkf", xb2d, gate_w)) * jnp.einsum("nd,nkdf->nkf", xb2d, up_w)
            out = jnp.einsum("nkf,nkfd->nkd", h, down_w)
        with part("combine"):
            return jnp.einsum("nk,nkd->nd", sel_w.astype(out.dtype), out)

    kernel = quant and q40.all_experts_impl(
        (lp["gate"], lp["up"], lp["down"]), n, cfg.quant_impl)
    tr = grouping.block_rows(n, k, e) if kernel else None
    if tr:
        # packed experts on the fused kernel, one device, more than 16 rows:
        # a row goes to its own k experts
        m = grouping.blocks(n, k, held, tr)
        obs_dispatch.record_dispatch("moe", "grouped", rows=n, tr=tr, **site,
                                     blocks=m)
        views = [lp[w] for w in ("gate", "up", "down")]
        for v in views:  # the three launches of the jitted block
            q40.record_experts_site(tr, v, m)
        out, used = _grouped_experts(
            xb2d, sel_idx, sel_w, here if share else None,
            [v.qt for v in views], [v.layer for v in views], held=held, tr=tr,
            act=cfg.hidden_act, impl=kernel, dtype=cfg.dtype)
        # the pairs that took a slot: under ``share`` those held here
        grouping.note(here if share else n * k, tr, used)
        return out

    with part("router"):
        dense_w = jnp.zeros((n, e), weights.dtype)
        dense_w = jnp.put_along_axis(dense_w, top_idx, weights, axis=-1, inplace=False)
        if share:  # the held experts' columns; from here on ``e`` planes = held
            dense_w = dense_w[:, first:first + held]

    if kernel:
        # packed experts on the fused kernel, one device: the expert index is
        # a grid axis, three launches a layer whatever E; every expert is
        # read, as the masked loops below read them
        obs_dispatch.record_dispatch("moe", "all-experts", rows=n, **site)
        with part("experts"):
            g = q40.matmul_experts(xb2d, lp["gate"], held, kernel)
            u = q40.matmul_experts(xb2d, lp["up"], held, kernel)
            o = q40.matmul_experts(act(g) * u, lp["down"], held, kernel,
                                   out_dtype=jnp.float32)          # (E, N, D)
        with part("combine"):
            w_e = dense_w.T.astype(jnp.float32)[:, :, None]
            return (w_e * o).sum(0).astype(cfg.dtype)

    if quant:
        # prefill, packed experts: one expert dequantized at a time with a
        # masked accumulate.  Up to MOE_PREFILL_UNROLL_MAX experts the loop
        # is a static unroll (XLA can interleave/schedule the per-expert
        # kernels freely — the right trade for 8-expert Mixtral/Grok-1);
        # past it, a lax.scan with a *traced* expert index bounds compile
        # time and program size at O(1) in E (VERDICT r04 Weak #3: the
        # unconditional unroll scaled both linearly, which would not
        # survive a 64-expert model).  Both paths run the same per-expert
        # math; the scan's QLayerView.select simply gets a traced index —
        # exactly how the decode path already selects experts.
        def one_expert(ei):
            with part("experts"):
                up = lp["up"].select(ei, held)
                gate = lp["gate"].select(ei, held)
                down = lp["down"].select(ei, held)
                h = act(_mm(xb2d, gate, cfg, kind="row")) * _mm(xb2d, up, cfg, kind="row")
                return q40.mm(h, down, impl=cfg.quant_impl, kind="col",
                              out_dtype=jnp.float32)

        unrolled = held <= MOE_PREFILL_UNROLL_MAX
        obs_dispatch.record_dispatch("moe", "unrolled" if unrolled else "scan",
                                     rows=n, **site)
        if unrolled:
            out = jnp.zeros((n, d), jnp.float32)
            for ei in range(held):
                oe = one_expert(jnp.int32(ei))
                with part("combine"):
                    out = out + dense_w[:, ei:ei + 1].astype(jnp.float32) * oe
        else:
            def body(acc, ei):
                with part("combine"):
                    w_e = jax.lax.dynamic_slice_in_dim(dense_w, ei, 1, axis=1)
                oe = one_expert(ei)
                with part("combine"):
                    return acc + w_e.astype(jnp.float32) * oe, None

            # the loop itself (its counter, its carries) is the experts' too
            with part("experts"):
                out, _ = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32),
                                      jnp.arange(held, dtype=jnp.int32))
        with part("combine"):
            return out.astype(cfg.dtype)

    # prefill path: dense dispatch over all experts
    obs_dispatch.record_dispatch("moe", "dense", rows=n, **site)
    with part("experts"):
        h = act(jnp.einsum("nd,edf->nef", xb2d, lp["gate"])) * jnp.einsum("nd,edf->nef", xb2d, lp["up"])
        outs = jnp.einsum("nef,efd->ned", h, lp["down"])
    with part("combine"):
        return jnp.einsum("ne,ned->nd", dense_w.astype(outs.dtype), outs)


def run_blocks(params: Params, cfg: ModelConfig, tokens: jax.Array,
               cache: KVCache, pos: jax.Array,
               offsets: jax.Array | None = None,
               pos_rows: jax.Array | None = None,
               paged=None, packed=None, n_real=None
               ) -> tuple[jax.Array, KVCache]:
    """Embed + all transformer blocks; returns the residual stream (B, T, D)
    and the updated cache.  ``packed`` (models/packing.py, a slot step at
    ``t > 1``): the row-local regions of every layer run over the rows that
    hold a token.  ``n_real``: how many of the ``T`` rows hold a token, where
    the caller knows (a bucketed prefill's last index + 1, a slot step's
    ``n_valid``); only a state that is not addressed row by row asks
    (``models/windowed.py``).

    ``offsets`` (B,) enables ragged batches of *distinct* streams via left
    padding (beyond reference — the reference fixes batch=1,
    tasks.cpp:199-210): row ``r``'s prompt is right-aligned so every row
    ends at the same cache slot, its real tokens live at cache positions
    ``offsets[r]..``, and its RoPE positions are the cache position minus
    the offset — each stream sees exactly the angles and keys it would see
    decoding alone, so batched greedy output matches the single-stream
    run token for token."""
    b, t = tokens.shape
    with scope("embed"):
        x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.dtype)
        if cfg.embedding_scale != 1.0:
            x = x * jnp.asarray(cfg.embedding_scale, cfg.dtype)

    with scope("rope"):
        positions = pos + jnp.arange(t)
        if pos_rows is not None:
            # continuous-batching slots: every row has its own clock, and
            # slot requests always start at cache position 0, so cache
            # position == logical RoPE position (no offset subtraction)
            positions = pos_rows[:, None] + jnp.arange(t)[None, :]
        elif offsets is not None:
            # per-row logical positions; pad slots clamp to 0 (their k/q
            # values are garbage either way and masked out of every live
            # row's view)
            positions = jnp.maximum(positions[None, :] - offsets[:, None], 0)
        if cfg.is_mla:
            cos, sin = mla.rope_angles(positions, cfg)
        else:
            cos, sin = rope_angles(positions, cfg.head_size, cfg.rope_theta)  # (T, Dh/2)

    if cfg.is_mla:
        return _run_segments(params, cfg, x, cache, cos, sin, pos, offsets,
                             pos_rows, paged, packed)
    marks = None
    if cfg.folds_state:  # the watermarks of this call, once for all layers
        with scope("page_idx"):
            marks = retention.clock(
                cache.rw, pos_rows if pos_rows is not None
                else jnp.broadcast_to(pos, (b,)), t, n_real)
    if cfg.periodic:
        x, cache = windowed.run_periods(params, cfg, x, cache, cos, sin, pos,
                                        offsets, pos_rows, paged, packed,
                                        n_real, marks)
        return x, _marked(cache, marks)

    layer_keys = [k for k in params if k not in ("embedding", "rms_final", "wcls")]
    # Packed-Q40 weights stay out of the scan's xs: the scan would slice a
    # per-layer copy of the stacked HBM buffer every step; instead the body
    # gets a QLayerView and the fused kernel indexes the stacked buffer
    # directly (scalar-prefetch index_map, ops/q40.py).
    qt_keys = [k for k in layer_keys
               if isinstance(params[k], (q40.QTensor, q8.Q8Tensor))]
    stacked = {k: params[k] for k in layer_keys if k not in qt_keys}

    L, loops = cfg.n_layers, cfg.n_loops
    eps = cfg.norm_eps

    def closed(branch, g):
        """A branch as the residual takes it: normed again first where the arch
        has post-block norms (part ``post`` of scope ``norm``)."""
        if not cfg.post_block_norms:
            return branch
        with scope("norm"), part("post"):
            return rmsnorm(branch, g, eps)

    def block(carry, layer, first_plane=0):
        x, kvc = carry
        idx, lp = layer
        lp = dict(lp)
        for k in qt_keys:
            lp[k] = q40.QLayerView(params[k], idx)
        # the weight set is ``idx``; the cache plane is the pass's own
        plane = idx + first_plane if loops > 1 else idx
        if cfg.retention_degree:
            att_out, kvc = _retention_block(x, lp, cfg, kvc, cos, sin, pos,
                                            idx, marks, offsets=offsets,
                                            pos_rows=pos_rows, packed=packed)
        else:
            att_out, kvc = _attention_block(x, lp, cfg, kvc, cos, sin, pos,
                                            plane, offsets=offsets,
                                            pos_rows=pos_rows, paged=paged,
                                            packed=packed)
        if cfg.has_ssm:  # the second mixer reads the same normed input
            ssm_out, kvc = _ssm_block(x, lp, cfg, kvc, pos, idx, marks,
                                      offsets=offsets, pos_rows=pos_rows,
                                      packed=packed, n_real=n_real)
            att_out = att_out + ssm_out
        att_out = closed(att_out, lp.get("rms_ffn"))  # grokRmfFfnNorm
        with scope("wo"):
            x = x + att_out

        # the norm before the FFN: ``rms_moe`` where ``rms_ffn`` closed attention
        pre = "rms_moe" if cfg.post_block_norms else "rms_ffn"
        if cfg.is_moe:
            def experts(x):  # row-local: any leading axes
                with scope("norm"):
                    xb = rmsnorm(x, lp[pre])
                with scope("moe"):
                    return moe_ffn(xb.reshape(-1, cfg.dim), lp,
                                   cfg).reshape(x.shape)

            ff = packing.over(packed, "moe", experts, x)
            ff = closed(ff, lp.get("rms_ffn2"))  # grokMoeRmsNormFinal
            with scope("moe"):
                x = x + ff
        else:
            def dense(x):
                with scope("norm"):
                    xb = rmsnorm(x, lp[pre], eps)
                return closed(_dense_ffn(xb, lp, cfg), lp.get("rms_ffn2"))

            ff = packing.over(packed, "w2", dense, x)
            with scope("w2"):
                x = x + ff
        return (x, kvc), None

    # The stacked caches are scan *carries*, not xs/ys: each layer touches
    # only its own (layer, pos) window in place.  Routing them through
    # xs/ys makes XLA slice out and restack a full layer slab per step and
    # defensively copy the whole cache in the enclosing decode loop —
    # measured ~8 ms/token at 7B/1k, comparable to all the matmuls.
    layers = (jnp.arange(L), stacked)
    if loops == 1:
        (x, cache), _ = grouping.scan(block, (x, cache), layers)
    else:
        # a looped model: the same scan over the same L weight sets, entered
        # once a pass by an outer scan that carries (x, cache), so the program
        # holds ONE layer body whatever the pass count; pass u writes and reads
        # planes u * L .. u * L + L - 1, and the final norm closes every pass
        # (``_head`` norms the last one's)
        obs_dispatch.record_dispatch("loop", "scan", passes=loops, layers=L,
                                     planes=cfg.n_cache_planes)

        def one_pass(carry, u):
            x, kvc = carry
            with scope("norm"):
                x = jax.lax.cond(
                    u > 0, lambda x: rmsnorm(x, params["rms_final"], eps),
                    lambda x: x, x)
            return jax.lax.scan(
                functools.partial(block, first_plane=u * L), (x, kvc),
                layers)[0], None

        (x, cache), _ = jax.lax.scan(one_pass, (x, cache),
                                     jnp.arange(loops, dtype=jnp.int32))
    return x, _marked(cache, marks)


def _marked(cache: KVCache, marks) -> KVCache:
    """``cache`` with the watermarks the call moved to (``marks``: None where no
    layer's state lags the clock)."""
    if marks is None:
        return cache
    return cache._replace(rw=marks[1].reshape(cache.rw.shape))


def _run_segments(params: Params, cfg: ModelConfig, x, cache: KVCache, cos,
                  sin, pos, offsets, pos_rows, paged, packed=None):
    """DeepSeek-V2's layers: a dense prefix and an expert segment, each one
    ``lax.scan`` over its own FFN stack, sharing the attention stacks (indexed
    by the running layer), the cache and the residual stream.  No stack rides
    a scan's xs: a layer's slice is indexed where it is used (packed weights
    through a ``QLayerView``, as in :func:`run_blocks`)."""
    def at(w, i):
        if isinstance(w, (q40.QTensor, q8.Q8Tensor)):
            return q40.QLayerView(w, i)
        return jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)

    def segment(carry, first: int, count: int, ffn_keys, ffn):
        def block(carry, i):
            x, kvc = carry
            layer = i + first
            lp = {k: at(params[k], layer) for k in MLA_ATT_KEYS if k in params}
            lp.update({k: at(params[k], i) for k in ffn_keys if k in params})
            att_out, kvc = _mla_attention_block(
                x, lp, cfg, kvc, cos, sin, pos, layer, offsets=offsets,
                pos_rows=pos_rows, paged=paged, packed=packed)
            with scope("wo"):
                x = x + att_out
            return (ffn(x, lp), kvc), None

        return grouping.scan(block, carry, jnp.arange(count, dtype=jnp.int32))[0]

    def normed(x, lp):
        with scope("norm"):
            return rmsnorm(x, lp["rms_ffn"], cfg.norm_eps)

    def dense(x, lp):
        ff = packing.over(packed, "w2",
                       lambda x: _dense_ffn(normed(x, lp), lp, cfg), x)
        with scope("w2"):
            return x + ff

    def experts(x, lp):
        def routed(x):  # row-local: any leading axes
            xb = normed(x, lp)
            with scope("moe"):
                return moe_ffn(xb.reshape(-1, cfg.dim), lp, cfg).reshape(x.shape)

        ff = packing.over(packed, "moe", routed, x)
        with scope("moe"):
            return x + ff

    carry = (x, cache)
    if cfg.n_dense_layers:
        carry = segment(carry, 0, cfg.n_dense_layers, DENSE_FFN_KEYS, dense)
    if cfg.n_moe_layers:
        carry = segment(carry, cfg.n_dense_layers, cfg.n_moe_layers,
                        MOE_FFN_KEYS, experts)
    return carry


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with scope("norm"):
        x = rmsnorm(x, params["rms_final"], cfg.norm_eps)
    with scope("head"):
        # out_dtype=f32 keeps the matmul's f32 accumulation for the sampler
        # instead of a round trip through the bf16 activation dtype
        logits = q40.mm(x, params["wcls"], impl=cfg.quant_impl,
                        out_dtype=jnp.float32, kind="row")
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
    return logits


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            cache: KVCache, pos: jax.Array,
            offsets: jax.Array | None = None) -> tuple[jax.Array, KVCache]:
    """Run the model over ``tokens`` (B, T) starting at position ``pos``.

    Returns logits (B, T, V) in f32 and the updated cache.
    """
    x, cache = run_blocks(params, cfg, tokens, cache, pos, offsets=offsets)
    return _head(params, cfg, x), cache


def forward_last(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 cache: KVCache, pos: jax.Array, last_index: jax.Array,
                 offsets: jax.Array | None = None
                 ) -> tuple[jax.Array, KVCache]:
    """Like :func:`forward` but applies the LM head only at ``last_index``,
    returning (B, V) — avoids materializing (T, V) logits during prefill
    when only the next-token distribution is needed.  With left-padded
    ragged batches (``offsets``) every row's genuine last token sits at
    the same final index, so the shared ``last_index`` needs no per-row
    variant."""
    x, cache = run_blocks(params, cfg, tokens, cache, pos, offsets=offsets,
                          n_real=last_index + 1 if cfg.keeps_state else None)
    with scope("head"):
        x_last = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)[:, 0]  # (B, D)
    return _head(params, cfg, x_last), cache


def forward_slots(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  cache: KVCache, pos_rows: jax.Array, n_valid: jax.Array,
                  page_table: jax.Array | None = None
                  ) -> tuple[jax.Array, KVCache]:
    """Continuous-batching slot step: run ``tokens`` (B, T) where row ``r``
    occupies cache positions ``pos_rows[r]..pos_rows[r]+T-1`` and only its
    first ``n_valid[r]`` tokens are real.  Returns the logits at each
    row's last *valid* token (B, V) and the updated cache.

    This is what lets a joining request prefill while its neighbors keep
    decoding: a prefilling slot feeds a prompt chunk (``n_valid`` = chunk
    length), a decoding slot feeds its previous sample plus padding
    (``n_valid`` = 1), and a free slot rides along at position 0.  Rows
    never see each other (attention masks per row, everything else is
    row-local), so each slot's stream is bit-identical to decoding alone.
    Garbage written above a row's ``n_valid`` window lands at positions
    the row has not reached yet — masked by its causal ceiling until the
    real tokens overwrite them (see ops.attention.slot_gqa_attention_at).

    With ``page_table`` (B, max_pages) the cache is a paged pool
    (:func:`init_kv_pool`) and every read/write is indirected through the
    table; logical semantics — positions, ceilings, RoPE clocks — are
    unchanged, which is what makes paged greedy output byte-identical to
    the contiguous layout.  Invalid-token writes are redirected to the
    scratch page instead of landing above the ceiling.
    """
    t = tokens.shape[1]
    x, cache = _run_slot_blocks(params, cfg, tokens, cache, pos_rows, n_valid,
                                page_table)
    with scope("head"):
        idx = jnp.clip(n_valid - 1, 0, t - 1)
        x_last = jax.vmap(
            lambda row, i: jax.lax.dynamic_index_in_dim(row, i, 0,
                                                        keepdims=False)
        )(x, idx)  # (B, D): per-row last-valid gather
    return _head(params, cfg, x_last), cache


def _run_slot_blocks(params: Params, cfg: ModelConfig, tokens, cache: KVCache,
                     pos_rows, n_valid, page_table):
    """:func:`run_blocks` for slot rows; on a paged pool the write indices
    are computed once here (identical for every layer), and at ``t > 1`` on
    one device the packing of the rows that hold a token (models/packing.py)."""
    paged = None
    if page_table is not None:
        with scope("page_idx"):
            pidx, oidx = paged_write_indices(page_table, pos_rows, n_valid,
                                             tokens.shape[1],
                                             cache.k.shape[2])
        paged = (page_table, pidx, oidx)
    with scope("page_idx"):  # which rows hold a token: once, as the indices
        packed = packing.plan(n_valid, *tokens.shape)
    return run_blocks(params, cfg, tokens, cache, jnp.int32(0),
                      pos_rows=pos_rows, paged=paged, packed=packed,
                      n_real=n_valid)


def forward_slots_all(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      cache: KVCache, pos_rows: jax.Array, n_valid: jax.Array,
                      page_table: jax.Array | None = None
                      ) -> tuple[jax.Array, KVCache]:
    """:func:`forward_slots` keeping EVERY position's logits (B, T, V)
    instead of the per-row last-valid gather — the slot-verify forward.
    Position ``j`` of row ``r`` is the model's next-token distribution
    after consuming ``tokens[r, :j+1]``, which is exactly what acceptance
    of a K-token proposal window needs (decode_loop.slot_verify_chunk).
    T is small (spec_k + 1), so the (B, T, V) buffer stays modest; the
    KV write/mask semantics — including stale writes above a row's
    ``n_valid`` landing beyond its causal ceiling (or in the scratch
    page when paged) — are identical to :func:`forward_slots`."""
    x, cache = _run_slot_blocks(params, cfg, tokens, cache, pos_rows, n_valid,
                                page_table)
    return _head(params, cfg, x), cache
