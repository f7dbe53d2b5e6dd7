"""Sliding-window attention: a ring of positions on the contiguous cache, a
bounded gather on the paged pool.

A window layer's query at position ``p`` sees key ``j`` iff ``p - window < j
<= p`` (``window`` keys, the query's own included).  So its contiguous cache
need not hold a row's whole sequence: ``(Lw, B, Hkv, R, Dh)`` with ``R =
ModelConfig.window_ring(seq_len)`` positions a row, position ``j`` in slot ``j
% R``.  A call writes its ``T`` rows first and reads after, so the ring must
hold the window of the call's first query and the call's own rows: ``R >=
window + T - 1``, which ``window + prefill_chunk`` gives every call.  Rows a
call pads or overshoots with (a prompt's bucket, a decode burst past an EOS)
land ahead of the live position and overwrite only positions at least ``R``
behind them, which no later query's window reaches.

The read walks the ring's blocks in storage order, not in position order:
softmax does not care, and the position a slot holds follows from the call's
last position alone (``last - ((last - slot) mod R)``; negative: never
written).  Every row has a clock of its own (``pos`` is ``(B,)``): the
one-stream engine passes its scalar broadcast, the slot scheduler its rows'.

On the paged pool the pages are the model's one pool and one table for every
layer; a window layer gathers only the pages its window and the step's rows
span, ``ceil((window + T - 1) / ps) + 1`` of them from the window's first
page (runtime/pagepool.py does not release the pages behind it yet).

Ledger families, one a compiled call site: ``{codec="attn",
path="window-walk"}`` (the ring) and ``{codec="kv_dense",
path="window-gather"}`` (the pool).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import dispatch as obs_dispatch
from .attention import _NEG, _fold_init, _kv_chunk, _online_fold
from .kernels import softmax_f32


def _write_row(ring: jax.Array, new: jax.Array, layer, row: int, pos, r: int
               ) -> jax.Array:
    """One row's ``(Hkv, T, Dh)`` into ``ring[layer, row]`` at positions ``pos
    .. pos + T - 1`` modulo ``r``, as windows of the ring and never a scatter:
    a scatter over the slot axis wants the head axis inside it, and XLA then
    re-lays the whole ring in and out of every call (seen in the compile for
    the chip, tests/test_tpu_compile.py).  One token is one window.  ``T``
    rows that may wrap are two windows of ``T`` slots, the last ``T`` the rows
    reach before the ring's end and its first ``T``: each is read, the rows
    that fall into it are laid over what it held, and it is written back."""
    hkv, t, dh = new.shape
    zero = jnp.zeros((), jnp.int32)
    s0 = pos.astype(jnp.int32) % r
    new = new.astype(ring.dtype)

    def put(ring, block, start):
        return jax.lax.dynamic_update_slice(
            ring, block[None, None], (layer, jnp.int32(row), zero, start, zero))

    if t == 1:
        return put(ring, new, s0)

    def held(ring, start):
        return jax.lax.dynamic_slice(
            ring, (layer, jnp.int32(row), zero, start, zero),
            (1, 1, hkv, t, dh))[0, 0]

    j = jnp.arange(t)[None, :, None]
    pad = jnp.zeros_like(new)
    # window A, slots a0 .. a0 + T - 1 (a0 <= s0): slot a0 + j holds row j - (s0 - a0)
    a0 = jnp.minimum(s0, r - t)
    rows_a = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([pad, new], 1), t - (s0 - a0), t, axis=1)
    ring = put(ring, jnp.where(j >= s0 - a0, rows_a, held(ring, a0)), a0)
    # window B, slots 0 .. T - 1: slot j holds row j + (r - s0) where the rows wrap
    over = s0 + t - r
    rows_b = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([new, pad], 1), jnp.minimum(r - s0, t), t, axis=1)
    return put(ring, jnp.where(j < over, rows_b, held(ring, zero)), zero)


def ring_write(ring_k: jax.Array, ring_v: jax.Array, k_new: jax.Array,
               v_new: jax.Array, layer: jax.Array, pos: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Write a call's keys and values ``(B, Hkv, T, Dh)`` into the stacked
    rings ``(Lw, B, Hkv, R, Dh)`` of window layer ``layer`` at each row's
    positions ``pos[b] .. pos[b] + T - 1`` modulo ``R`` (``T <= R``)."""
    r = ring_k.shape[3]
    li = layer.astype(jnp.int32)
    for b in range(k_new.shape[0]):
        ring_k = _write_row(ring_k, k_new[b], li, b, pos[b], r)
        ring_v = _write_row(ring_v, v_new[b], li, b, pos[b], r)
    return ring_k, ring_v


def _window_mask(key_pos, q_pos, window: int, floor=None):
    """``(B, T, S)``: key position ``key_pos (B, S)`` visible to the query at
    ``q_pos (B, T)``; ``floor (B,)`` is a ragged batch's first real position."""
    kp, qp = key_pos[:, None, :], q_pos[:, :, None]
    mask = (kp >= 0) & (kp <= qp) & (kp > qp - window)
    if floor is not None:
        mask = mask & (kp >= floor[:, None, None])
    return mask


def ring_attention(q: jax.Array, ring_k: jax.Array, ring_v: jax.Array,
                   layer: jax.Array, pos: jax.Array, window: int,
                   floor: jax.Array | None = None) -> jax.Array:
    """Sliding-window GQA of ``q (B, Hq, T, Dh)``, row ``b``'s queries at
    positions ``pos[b] .. pos[b] + T - 1``, over the stacked rings ``(Lw, B,
    Hkv, R, Dh)`` at ``layer``, whose rows already hold the call's own keys.
    An online softmax over the ring's blocks (``_kv_chunk(R)`` slots each) up
    to the last slot any row has written: a ring not yet full is read as far
    as it is filled, a full one whole.  Numerics are the live walk's
    (``ops/attention.py _online_fold``)."""
    b, hq, t, dh = q.shape
    hkv, r = ring_k.shape[2], ring_k.shape[3]
    g = hq // hkv
    block = _kv_chunk(r)
    obs_dispatch.record_dispatch("attn", "window-walk", t=t, s=r, window=window)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, t, dh)
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    last = pos + (t - 1)                                             # (B,)
    q_pos = pos[:, None] + jnp.arange(t)[None, :]                    # (B, T)
    n_live = jnp.minimum(jnp.max(last), r - 1) // block + 1
    li = layer.astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def cut(ring, start):
        return jax.lax.dynamic_slice(
            ring, (li, zero, zero, start, zero), (1, b, hkv, block, dh))[0]

    def body(carry):
        i, m, l, acc = carry
        start = i * block
        slot = start + jnp.arange(block)
        # the newest position <= last that lives in this slot
        key_pos = last[:, None] - (last[:, None] - slot[None, :]) % r
        mask = _window_mask(key_pos, q_pos, window, floor)
        m, l, acc = _online_fold(qf, cut(ring_k, start), cut(ring_v, start),
                                 mask, m, l, acc, scale)
        return i + 1, m, l, acc

    _, _, l, acc = jax.lax.while_loop(
        lambda c: c[0] < n_live, body,
        (jnp.int32(0),) + _fold_init(b, hkv, g, t, dh))
    out = acc / jnp.maximum(l, 1e-38)[..., None]
    return out.reshape(b, hq, t, dh).astype(q.dtype)


def window_pages(window: int, t: int, page_size: int, max_pages: int) -> int:
    """Pages a window layer's step of ``t`` rows reads of a slot's table."""
    return min(max_pages, -(-(window + t - 1) // page_size) + 1)


def paged_window_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           pos_rows: jax.Array, window: int) -> jax.Array:
    """Sliding-window GQA through the page table: row ``b``'s ``T`` queries at
    ``pos_rows[b] ..`` over the ``window_pages`` pages of its table from the
    window's first page on, gathered from the dense pool ``(L, P, ps, Hkv,
    Dh)`` and scored in one shot.  Pages behind the window are not read."""
    b, hq, t, dh = q.shape
    ps, hkv = pool_k.shape[2], pool_k.shape[3]
    maxp = page_table.shape[1]
    g = hq // hkv
    n = window_pages(window, t, ps, maxp)
    obs_dispatch.record_dispatch("kv_dense", "window-gather", t=t, s=n * ps,
                                 page_size=ps, window=window)
    first = jnp.clip((pos_rows - window + 1) // ps, 0, maxp - n)      # (B,)
    pids = jnp.take_along_axis(page_table,
                               first[:, None] + jnp.arange(n)[None, :], axis=1)

    def view(pool):  # (B, n, ps, Hkv, Dh) -> (B, Hkv, n * ps, Dh)
        pages = pool[layer.astype(jnp.int32), pids]
        return pages.transpose(0, 3, 1, 2, 4).reshape(b, hkv, n * ps, dh)

    k_l, v_l = view(pool_k), view(pool_v)
    key_pos = first[:, None] * ps + jnp.arange(n * ps)[None, :]
    q_pos = pos_rows[:, None] + jnp.arange(t)[None, :]
    mask = _window_mask(key_pos, q_pos, window)
    qc = q.reshape(b, hkv, g, t, dh).astype(k_l.dtype)
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qc, k_l,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, None], scores / jnp.sqrt(jnp.float32(dh)),
                       _NEG)
    probs = softmax_f32(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs.astype(v_l.dtype), v_l,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, t, dh).astype(q.dtype)
