"""Sliding-window attention: a ring of positions on the contiguous cache, a
slot's ring of pages beside the paged pool.

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

On the paged engine the window layers have planes of their own beside the
full layers' pool (``models/transformer.py init_kv_pool``): ``(Lw, B * ring,
ps, Hkv, Dh)``, in which slot ``b`` owns pages ``b * ring .. b * ring + ring -
1`` for its whole life: the window kind's page table is this arithmetic, and
the scheduler's ``PagePool`` and radix tree manage the full layers' pages
alone.  ``ring = window_pages(window, rows, ps, table width)`` pages hold the
window of a step's first query and the step's own rows, so position ``p`` is
written to ring page ``(p // ps) % ring`` whatever the context's depth: the
page behind the window is the next one written, which is the release
(``paged_ring_indices``).  The read takes the slot's whole ring, a contiguous
slice, with the contiguous ring's rule for the position a slot holds
(``paged_window_attention``).

A ring plane is written (``ring_write``, ``ring_write_plane``; the mixers'
rings of ``ops/ssm.py``, ``ops/retention.py`` and ``ops/conv.py`` come through
the second) as in-place windows, one a row (``_write_row``), except by a call
of ONE token a row over several rows on one TPU device, every pure-decode step
of a slot engine: there a plane whose rows fill whole lanes takes ONE launch
(``ring_put``: every row's aligned window of 16 bfloat16 or 8 float32 positions
in flight together, the token laid over its slot by a select) and a narrow,
light one ONE fused update of the layer's slab (``_put_slab``); ``_put_form``
is the rule.

Ledger families, one a compiled call site: ``{codec="attn",
path="window-walk"}`` (the contiguous ring), ``{codec="kv_dense",
path="window-ring"}`` (a slot's ring of pages) and ``{codec="ring",
path="windows"|"put-kernel"|"put-slab"}`` (a ring plane's write).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import dispatch as obs_dispatch
from ..parallel.mesh import get_active_mesh
from .attention import _NEG, _fold_init, _kv_chunk, _online_fold
from .kernels import softmax_f32

# A call of one token a row (every pure-decode step) on one TPU device puts the
# rows of a plane in ONE launch from this many rows on (:func:`_put_form`;
# tools/sweep_attn.py --ring-write times both forms: PERF.md section 6, PR 66),
# and holds at most this many bytes of windows in VMEM at once (the default
# scope is 16 MiB of the v5e's 128).  A plane the launch cannot take goes in
# as one fused update of the layer's slab where a row's ring is at most
# PUT_SLAB_ROW bytes: the slab costs 2.2 us and 3.1 us a MB on the chip, a
# window 0.4 to 1.3 us in the served steps.
PUT_MIN_ROWS = 2
PUT_VMEM = 8 * 1024 * 1024
PUT_SLAB_ROW = 128 * 1024
_LANES = 128
# The launch asks for nearly all of the v5e's 128 MiB of VMEM as its scope, of
# which it uses PUT_VMEM: that is what keeps the plane it updates in place in
# HBM.  Left to itself XLA's memory-space assignment moves a plane that fits
# VMEM into it, whole, and back around the launch (75 MB of Falcon-H1's ``rk`` a
# layer, in the compile for the described chip; naming HBM for the operand
# changes nothing and pinning the result there aborts the compiler), and it
# cannot give the launch both a plane there and this scope.  Nor can it keep a
# stacked array parked there across the launch: it had parked the scale planes
# of two stacked Q40 matrices, all 18 layers of them, once a LAYER.
PUT_SCOPE = 120 * 1024 * 1024


def _write_row(ring: jax.Array, new: jax.Array, layer, row: int, pos, r: int
               ) -> jax.Array:
    """One row's ``(Hkv, T, Dh)`` into ``ring[layer, row]`` at positions ``pos
    .. pos + T - 1`` modulo ``r``, as windows of the ring and never a scatter:
    a scatter over the slot axis wants the head axis inside it, and XLA then
    re-lays the whole ring in and out of every call (seen in the compile for
    the chip, tests/test_tpu_compile.py).  A window is one op of about a
    microsecond on the chip whatever it carries, each waiting for the last
    through the plane it updates, so a call of ONE token a row over several
    rows does not come here since PR 66 (:func:`_put_form`: one launch or one
    fused update a plane, no scatter either).  One token is one window.  ``T``
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


def _put_window(dtype) -> int:
    """Positions of the aligned window a launch copies: one sublane tile of the
    ring's dtype, 8 words of 32 bits, so 16 positions of a bfloat16 ring (two a
    word: ONE of them is no copy the chip can make) and 8 of a float32 one."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _put_rows(ring_shape, dtype, rows: int) -> int:
    """Rows of a call whose windows one grid step of the launch holds in VMEM
    together: all of them where they fit ``PUT_VMEM``, else the largest
    divisor of ``rows`` that does; 0 where one row's window does not."""
    _, _, h, _, dh = ring_shape
    one = h * _put_window(dtype) * dh * jnp.dtype(dtype).itemsize
    return next((c for c in range(rows, 0, -1)
                 if rows % c == 0 and c * one <= PUT_VMEM), 0)


def _put_form(ring_shape, dtype, rows: int, t: int) -> str:
    """How a call's rows go into a plane, from static facts only, the same
    inside and outside a trace (as ``ops/attention.py _fused_choice``); the
    ledger's path.  ``windows``: :func:`_write_row` a row, which everything
    keeps but a call of ONE token a row (a chunk's rows span two aligned
    windows) of at least ``PUT_MIN_ROWS`` rows (one row is one window, one op)
    on one TPU device (a ``pallas_call`` is not partitioned by GSPMD).  There
    ``put-kernel`` (:func:`ring_put`) takes a ring of whole aligned windows
    whose last axis fills whole lanes (Mosaic copies no part of a 128-lane
    row), and ``put-slab`` (:func:`_put_slab`) a narrower one where a row's
    ring is at most ``PUT_SLAB_ROW`` bytes (a heavier slab costs more than the
    windows it saves): the ``dt`` ring of a mixer of 32 heads."""
    mesh = get_active_mesh()
    if t != 1 or rows < PUT_MIN_ROWS or jax.default_backend() != "tpu" or (
            mesh is not None and mesh.size > 1):
        return "windows"
    _, _, h, r, dh = ring_shape
    size = jnp.dtype(dtype).itemsize
    if dh % _LANES == 0 and size in (2, 4) and r % _put_window(dtype) == 0 \
            and _put_rows(ring_shape, dtype, rows):
        return "put-kernel"
    return "put-slab" if h * r * dh * size <= PUT_SLAB_ROW else "windows"


def _put_slab(ring: jax.Array, new: jax.Array, layer: jax.Array,
              pos: jax.Array) -> jax.Array:
    """One token a row into a plane the launch does not take (its rows are
    narrower than the lanes: the ``dt`` ring of a mixer of 32 heads): the
    layer's whole slab is read, the tokens laid over their slots by a select
    on the position's iota, and written back, ONE fused update in place where
    the windows were one a row."""
    r = ring.shape[3]
    li = layer.astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    held = jax.lax.dynamic_index_in_dim(ring, li, 0)
    at = (jnp.arange(r)[None, :] == (pos.astype(jnp.int32) % r)[:, None])
    return jax.lax.dynamic_update_slice(
        ring, jnp.where(at[None, :, None, :, None], new.astype(ring.dtype)[None],
                        held), (li, zero, zero, zero, zero))


def _put_kernel(layer_ref, slot_ref, new_ref, _, ring_ref, buf, sem_in, sem_out,
                *, rows: int, w: int):
    """One grid step: ``rows`` rows' aligned windows of the plane (in HBM,
    updated in place) are all started towards VMEM, and each, as it lands, has
    the row's token laid over slot ``slot % w`` of it and is started back."""
    li = layer_ref[0]
    base = pl.program_id(0) * rows
    at = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 1)

    def held(i):     # row ``base + i``'s aligned window of the plane
        start = pl.multiple_of(jax.lax.div(slot_ref[base + i], w) * w, w)
        return ring_ref.at[li, base + i, :, pl.ds(start, w), :]

    def landing(i):
        return pltpu.make_async_copy(held(i), buf.at[i], sem_in.at[i])

    def leaving(i):
        return pltpu.make_async_copy(buf.at[i], held(i), sem_out.at[i])

    def fetch(i, carry):
        landing(i).start()
        return carry

    def lay(i, carry):
        landing(i).wait()
        token = new_ref[:, pl.ds(base + i, 1), :].astype(buf.dtype)   # (H, 1, Dh)
        buf[i] = jnp.where(at == jax.lax.rem(slot_ref[base + i], w),
                           jnp.broadcast_to(token, buf.shape[1:]), buf[i])
        leaving(i).start()
        return carry

    def done(i, carry):
        leaving(i).wait()
        return carry

    for step in (fetch, lay, done):
        jax.lax.fori_loop(0, rows, step, 0)


def ring_put(ring: jax.Array, new: jax.Array, layer: jax.Array, pos: jax.Array,
             interpret: bool = False) -> jax.Array:
    """``new (B, H, 1, Dh)``, one token a row, into the stacked rings ``(L, B,
    H, R, Dh)`` at ``layer``, row ``b`` at slot ``pos[b] % R``, in ONE launch
    that updates the plane in place: what ``B`` calls of :func:`_write_row`
    write, bit for bit (``new.astype(ring.dtype)``; the token crosses into the
    kernel as float32, which holds a bfloat16 exactly, with the rows on the
    sublanes, where a row is a load at a dynamic offset).  Nothing of the plane
    but the rows' aligned windows is read or written."""
    _, b, h, r, dh = ring.shape
    w = _put_window(ring.dtype)
    rows = _put_rows(ring.shape, ring.dtype, b)
    token = new.astype(ring.dtype).astype(jnp.float32)[:, :, 0].transpose(1, 0, 2)
    return pl.pallas_call(
        functools.partial(_put_kernel, rows=rows, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((rows, h, w, dh), ring.dtype),
                            pltpu.SemaphoreType.DMA((rows,)),
                            pltpu.SemaphoreType.DMA((rows,))]),
        out_shape=jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=PUT_SCOPE),
        interpret=interpret,
        name="ring_put",
    )(jnp.atleast_1d(layer).astype(jnp.int32), pos.astype(jnp.int32) % r,
      token, ring)


def _write_planes(rings, news, layer: jax.Array, pos: jax.Array) -> tuple:
    """Each ``new (B, H, T, Dh)`` into its stacked plane (planes of one ring
    length and dtype: a layer's keys and values, or one plane alone) by the form
    :func:`_put_form` names, which the ledger records a call site."""
    b, _, t, _ = news[0].shape
    r = rings[0].shape[3]
    form = _put_form(rings[0].shape, rings[0].dtype, b, t)
    obs_dispatch.record_dispatch("ring", form, rows=b, t=t, ring=r)
    if form != "windows":
        put = ring_put if form == "put-kernel" else _put_slab
        return tuple(put(ring, new, layer, pos)
                     for ring, new in zip(rings, news))
    rings = list(rings)
    li = layer.astype(jnp.int32)
    for row in range(b):
        for i, new in enumerate(news):
            rings[i] = _write_row(rings[i], new[row], li, row, pos[row], r)
    return tuple(rings)


def ring_write(ring_k: jax.Array, ring_v: jax.Array, k_new: jax.Array,
               v_new: jax.Array, layer: jax.Array, pos: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Write a call's keys and values ``(B, Hkv, T, Dh)`` into the stacked
    rings ``(Lw, B, Hkv, R, Dh)`` of window layer ``layer`` at each row's
    positions ``pos[b] .. pos[b] + T - 1`` modulo ``R`` (``T <= R``)."""
    return _write_planes((ring_k, ring_v), (k_new, v_new), layer, pos)


def ring_write_plane(ring: jax.Array, new: jax.Array, layer: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """:func:`ring_write` for one plane: ``new (B, H, T, Dh)`` into the stacked
    rings ``(L, B, H, R, Dh)`` at ``layer``, row ``b`` at positions ``pos[b] ..
    pos[b] + T - 1`` modulo ``R`` (``T <= R``).  What a state that is no key and
    no value is written with (``ops/conv.py``)."""
    return _write_planes((ring,), (new,), layer, pos)[0]


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
    """Pages of a slot's ring for steps of up to ``t`` rows: the window of the
    step's first query and the step's rows may start and end inside a page,
    hence one more; never more than the slot's table is wide (a context that
    fits them never wraps)."""
    return min(max_pages, -(-(window + t - 1) // page_size) + 1)


def paged_ring_indices(pos_rows: jax.Array, t: int, page_size: int, ring: int
                       ) -> tuple[jax.Array, jax.Array]:
    """``(page, offset)`` index arrays, both ``(B, T)``, of a slot step's writes
    into the window planes: slot ``b``'s token at position ``p`` goes to page
    ``b * ring + (p // ps) % ring``, offset ``p % ps``.  Computed once a
    forward (every window layer writes the same places).  Rows past a slot's
    ``n_valid`` are written too, as the contiguous ring's: they land ahead of
    the live position, at most ``t - 1`` rows, and overwrite positions a whole
    ring behind them, which no later query's window reaches."""
    tpos = pos_rows[:, None] + jnp.arange(t)[None, :]
    base = jnp.arange(pos_rows.shape[0], dtype=jnp.int32)[:, None] * ring
    return ((base + (tpos // page_size) % ring).astype(jnp.int32),
            (tpos % page_size).astype(jnp.int32))


def paged_window_attention(q: jax.Array, ring_k: jax.Array, ring_v: jax.Array,
                           layer: jax.Array, pos_rows: jax.Array, window: int,
                           max_pages: int) -> jax.Array:
    """Sliding-window GQA over the slots' rings of pages: row ``b``'s ``T``
    queries at ``pos_rows[b] ..`` against its own ``ring`` pages of the window
    planes ``(Lw, B * ring, ps, Hkv, Dh)`` at window layer ``layer``, which
    already hold the step's keys, scored in one shot.  The position a ring
    slot holds follows from the row's last position alone (as
    :func:`ring_attention`); a slot not yet written, or written by the slot's
    previous request, comes out negative or above the query and is masked.
    ``max_pages`` is the slots' table width: a ring that wide never wraps."""
    b, hq, t, dh = q.shape
    ps, hkv = ring_k.shape[2], ring_k.shape[3]
    ring = ring_k.shape[1] // b
    r = ring * ps
    g = hq // hkv
    if r < window + t - 1 and ring < max_pages:
        raise ValueError(
            f"a step of {t} rows does not fit a window layer's ring of {ring} "
            f"pages of {ps} (window {window}): the engine sized it for fewer "
            "rows a step")
    obs_dispatch.record_dispatch("kv_dense", "window-ring", t=t, s=r,
                                 page_size=ps, window=window)

    def view(planes):  # (B * ring, ps, Hkv, Dh) of the layer -> (B, Hkv, r, Dh)
        own = jax.lax.dynamic_index_in_dim(planes, layer.astype(jnp.int32), 0,
                                           keepdims=False)
        return own.reshape(b, r, hkv, dh).transpose(0, 2, 1, 3)

    k_l, v_l = view(ring_k), view(ring_v)
    last = pos_rows + (t - 1)                                          # (B,)
    key_pos = last[:, None] - (last[:, None] - jnp.arange(r)[None, :]) % r
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
