"""Multi-head latent attention (MLA, DeepSeek-V2) over a latent cache.

A token's cached state in a layer is ``kv_lora_rank + qk_rope_head_dim``
values (512 + 64): the RMS-normed latent ``c_kv`` and the one rotated key
``k_pe`` that all heads share.  Nothing per head is kept across steps.  The
two live in two planes, ``KVCache.k`` the latent and ``KVCache.v`` the rotated
key: contiguous ``(L, B, S, 512)`` / ``(L, B, S, 64)``, paged ``(L, P, ps, 512)``
/ ``(L, P, ps, 64)`` (a page is token-major, as the GQA pool's is).  One
576-wide plane would be simpler, but a row that is not a whole number of
128-lane tiles makes XLA re-lay the pool inside the step program, a copy of
the whole pool in and out every step (1.39 ms of a 38 ms step on the chip,
PERF.md §6 PR 33); the 512-wide plane compiles without, the 64-wide one is a
ninth of the bytes.

Two forms of the same mathematics read it:

* **absorbed** (``mla-absorbed``): ``W_kvb``'s key half is folded into the
  query, ``q_abs = q_nope W_uk^T``, every head scores against the latent
  rows themselves (``q_abs . c_kv + q_pe . k_pe``), the values are the latent
  rows, and ``W_uv`` is applied once to the result (``C = 576``, ``r = 512``):
  per head and query ``2 S (C + kv_lora_rank)`` operations and no expansion
  of the cache.  Cheaper than the expanded form while
  ``T (C + r - qk - v) < r (nope + v)``, T < 170 at DeepSeek-V2's sizes:
  the pure-decode step and every chunk of the served mixed step.
* **expanded** (``mla-expanded``): each block of latent rows is expanded
  through ``W_kvb`` to per-head ``k_nope ‖ k_pe`` and ``v`` inside the walk
  (a block's expansion is a temporary, dead at the block's end) and the
  heads attend as plain MHA: whole-prompt prefill of the contiguous engine,
  ``T >= EXPAND_MIN_T``.

Both walk only the blocks that hold live positions (an online softmax over a
``while_loop`` whose trip count follows the longest live row, PERF.md §6
PR 29 / PR 31 on what a capacity walk costs); the paged walk gathers
``WALK_TOKENS`` tokens of pages a trip through the page table.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import dispatch as obs_dispatch
from .attention import _NEG
from .scopes import part

# rows of a call from which the expanded form is the cheaper one (module
# docstring); below it the absorbed form
EXPAND_MIN_T = 128
# tokens a trip of the paged walk gathers (whole pages of them)
WALK_TOKENS = 256


def yarn_inv_freq(rope_dim: int, theta: float, factor: float, orig_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``rope_dim / 2`` rotation frequencies: plain RoPE's where
    ``factor <= 1``, else YaRN's blend of them (``extra``) with the
    interpolated ones (``inter = extra / factor``): dimension ``i`` keeps
    ``extra`` below ``low``, takes ``inter`` above ``high`` and ramps between,
    ``low``/``high`` the dimensions that turn ``beta_fast``/``beta_slow``
    times over the original context."""
    half = rope_dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rope_dim)
    if factor <= 1.0:
        return extra.astype(np.float32)

    def corr(rot):
        return rope_dim * math.log(orig_len / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), rope_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_angles(positions: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """cos/sin ``positions.shape + (qk_rope_head_dim / 2,)`` at the
    configuration's (YaRN) frequencies, times ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)`` (1 for DeepSeek-V2: both are 0.707)."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_orig_seq_len, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    ang = positions.astype(jnp.float32)[..., None] * inv
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (cos, sin) if m == 1.0 else (cos * np.float32(m), sin * np.float32(m))


# ---- cache writes (one plane a call: the latent, or the rotated key) ------

def write_at(plane: jax.Array, rows: jax.Array, layer, pos) -> jax.Array:
    """``rows`` (B, T, w) into a contiguous plane (L, B, S, w) at
    ``(layer, :, pos)``: one window, in place in the layer loop's carry."""
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_update_slice(
        plane, rows[None].astype(plane.dtype),
        (layer.astype(jnp.int32), zero, pos.astype(jnp.int32), zero))


def write_rows(plane: jax.Array, rows: jax.Array, layer, pos_rows) -> jax.Array:
    """:func:`write_at` with a position a row (contiguous slot rows)."""
    def row(c, new, p):  # c (L, S, w), new (T, w)
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(
            c, new[None].astype(c.dtype),
            (layer.astype(jnp.int32), p.astype(jnp.int32), zero))

    return jax.vmap(row, in_axes=(1, 0, 0), out_axes=1)(plane, rows, pos_rows)


def write_paged(pool: jax.Array, rows: jax.Array, layer, pidx, oidx) -> jax.Array:
    """``rows`` (B, T, w) into a pool plane (L, P, ps, w) at the per-token
    ``(page, offset)`` of ``attention.paged_write_indices``: one scatter, a
    token's row one contiguous window."""
    return pool.at[layer.astype(jnp.int32), pidx, oidx].set(
        rows.astype(pool.dtype))


# ---- the walk ---------------------------------------------------------------

def _block(s: int) -> int:
    for c in (512, 256, 128):
        if s % c == 0 and c < s:
            return c
    return s


def _walk(scores_of, values_of, fetch, n_live, block: int, ceil, floor,
          shape: tuple, dv: int):
    """Online softmax over latent blocks ``fetch(i) -> (c_kv (B, block, r),
    k_pe (B, block, rope))``, ``i < n_live``.  ``scores_of(c_kv, k_pe)`` gives
    the scaled scores (B, H, T, block) in float32 and ``values_of(p, c_kv)``
    the block's weighted values (B, H, T, dv); query ``(b, t)`` sees positions
    ``floor[b] <= s <= ceil[b, t]``.  ``shape`` is (B, H, T).  Returns
    (B, H, T, dv) float32.  The same fold as ``attention._online_fold`` (f32
    running max, denominator and numerator), with a block's scores in two
    terms."""
    def body(carry):
        i, m, l, acc = carry
        c_kv, k_pe = fetch(i)
        scores = scores_of(c_kv, k_pe)
        s_idx = i * block + jnp.arange(block)
        mask = s_idx[None, None, :] <= ceil[:, :, None]          # (B, T, blk)
        if floor is not None:
            mask = mask & (s_idx[None, None, :] >= floor[:, None, None])
        scores = jnp.where(mask[:, None], scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        return (i + 1, m_new, alpha * l + p.sum(axis=-1),
                alpha[..., None] * acc + values_of(p, c_kv))

    init = (jnp.int32(0), jnp.full(shape, _NEG), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (dv,), jnp.float32))
    _, _, l, acc = jax.lax.while_loop(lambda c: c[0] < n_live, body, init)
    return acc / jnp.maximum(l, 1e-38)[..., None]


def attention(q: jax.Array, c_cache: jax.Array, pe_cache: jax.Array,
              w_kvb: jax.Array, cfg, layer, *, pos=None, pos_rows=None,
              page_table=None, floor=None) -> jax.Array:
    """Causal MLA of ``q`` (B, T, H, nope + rope; the rotated part already
    rotated) over the latent cache's two planes at ``layer``; ``w_kvb`` is the
    layer's ``(r, H, nope + v)``.  Query ``t`` of row ``b`` stands at
    ``pos + t`` (one clock) or ``pos_rows[b] + t`` (slot rows); with
    ``page_table`` the planes are the pool's.  Returns (B, T, H * v) in
    ``q``'s dtype."""
    b, t, h, _ = q.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    li = layer.astype(jnp.int32)
    w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
    absorbed = t < EXPAND_MIN_T
    paged = page_table is not None
    obs_dispatch.record_dispatch(
        "attn", "mla-absorbed" if absorbed else "mla-expanded", t=t,
        s=(page_table.shape[1] if paged else 1) * c_cache.shape[2], paged=paged)

    first = (jnp.broadcast_to(pos, (b,)) if pos_rows is None
             else pos_rows).astype(jnp.int32)
    ceil = first[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]   # (B, T)

    if paged:
        ps = c_cache.shape[2]
        w = max(1, min(WALK_TOKENS // ps, page_table.shape[1]))
        block = w * ps
        pad = -page_table.shape[1] % w
        table = jnp.pad(page_table, ((0, 0), (0, pad))) if pad else page_table
        capacity = table.shape[1] * ps

        def fetch(i):
            pages = jax.lax.dynamic_slice_in_dim(table, i * w, w, axis=1)
            return tuple(plane[li, pages].reshape(b, block, plane.shape[-1])
                         for plane in (c_cache, pe_cache))
    else:
        capacity = c_cache.shape[2]
        block = _block(capacity)

        def fetch(i):
            zero = jnp.zeros((), jnp.int32)
            return tuple(jax.lax.dynamic_slice(
                plane, (li, zero, i * block, zero),
                (1, b, block, plane.shape[-1]))[0] for plane in (c_cache, pe_cache))

    last = jnp.clip(jnp.max(first) + (t - 1), 0, capacity - 1)
    n_live = last // block + 1
    dt = c_cache.dtype
    scale = cfg.attn_scale
    # operands in the cache's dtype, float32 accumulation (attention._online_fold
    # has why); the rotated part of q head-major like the rest
    q_pe = q[..., dn:].transpose(0, 2, 1, 3).astype(dt)          # (B, H, T, rope)

    if absorbed:
        with part("absorb"):
            q_abs = jnp.einsum("bthn,rhn->bhtr", q[..., :dn].astype(dt),
                               w_uk.astype(dt),
                               preferred_element_type=jnp.float32).astype(dt)

        def scores_of(c_kv, k_pe):
            return (jnp.einsum("bhtr,bsr->bhts", q_abs, c_kv,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("bhtd,bsd->bhts", q_pe, k_pe,
                                 preferred_element_type=jnp.float32)) * scale

        def values_of(p, c_kv):
            return jnp.einsum("bhts,bsr->bhtr", p.astype(dt), c_kv,
                              preferred_element_type=jnp.float32)

        with part("latent"):
            o_lat = _walk(scores_of, values_of, fetch, n_live, block, ceil,
                          floor, (b, h, t), cfg.kv_lora_rank)    # (B, H, T, r)
        with part("absorb"):
            out = jnp.einsum("bhtr,rhv->bthv", o_lat.astype(dt),
                             w_uv.astype(dt),
                             preferred_element_type=jnp.float32)
        return out.reshape(b, t, h * dv).astype(q.dtype)

    q_nope = q[..., :dn].transpose(0, 2, 1, 3).astype(dt)        # (B, H, T, nope)

    def scores_of(c_kv, k_pe):  # a block's rows through W_uk: a temporary
        k_nope = jnp.einsum("bsr,rhn->bhsn", c_kv, w_uk.astype(dt),
                            preferred_element_type=jnp.float32).astype(dt)
        return (jnp.einsum("bhtn,bhsn->bhts", q_nope, k_nope,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhtd,bsd->bhts", q_pe, k_pe,
                             preferred_element_type=jnp.float32)) * scale

    def values_of(p, c_kv):
        v = jnp.einsum("bsr,rhv->bhsv", c_kv, w_uv.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        return jnp.einsum("bhts,bhsv->bhtv", p.astype(dt), v,
                          preferred_element_type=jnp.float32)

    with part("expand"):
        out = _walk(scores_of, values_of, fetch, n_live, block, ceil, floor,
                    (b, h, t), dv)                               # (B, H, T, dv)
    return out.transpose(0, 2, 1, 3).reshape(b, t, h * dv).astype(q.dtype)
