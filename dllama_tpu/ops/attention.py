"""Grouped-query attention with a persistent KV cache.

TPU-native replacement for the reference's per-head scalar attention loop
(/root/reference/src/llama2-tasks.cpp:54-94): instead of iterating heads ×
positions on a thread pool, the whole (batch, heads, q_len, kv_len) score
tensor is one batched einsum on the MXU, with causal/position masking done
with an iota comparison (static shapes; ``pos`` is a traced scalar so one
compiled program serves every decode step).

The KV cache layout is ``(batch, n_kv_heads, seq_len, head_size)`` — the
kv-head axis is the reference's ``KvCacheSlice`` dim (commands.cpp:94-99)
and is the axis sharded across the tensor-parallel mesh.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import get_active_mesh
from .kernels import softmax_f32


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(…, position) int8 quantization of a KV step window
    (B, Hkv, T, Dh) → int8 values + f32 absmax/127 scales (B, Hkv, T, 1).

    The int8 KV cache (beyond reference — transformer.cpp:280-282 holds
    f32) halves cache HBM traffic and residency vs bf16; a per-position
    scale over Dh values keeps the quantization row-local so decode's
    block reads stay self-contained."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.round(xf * inv).astype(jnp.int8)
    return q, scale


def dequant_kv(vals: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 KV block × f32 per-position scale → bf16 (the dot operand
    dtype _online_fold wants: the cast+mul fuses into the score dot, so
    only int8 bytes cross HBM)."""
    return (vals.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def update_kv_cache_at(k_cache: jax.Array, v_cache: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       layer: jax.Array, pos: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Write one layer's step KV (B, Hkv, T, Dh) into the *stacked*
    (L, B, Hkv, S, Dh) caches at ``(layer, pos)``.

    The reference appends at ``pos`` into its per-slice cache
    (llama2-tasks.cpp:33-45 writes k/v straight into the cache row); here
    it is a dynamic_update_slice into the layer's window.  The stacked
    caches ride the layer scan as a **carry** and each layer writes only
    its (1, B, Hkv, T, Dh) window — a few KB — in place.  (Passing the
    caches through the scan as xs/ys instead makes XLA slice out and
    re-stack an entire layer slab per step, plus whole-cache defensive
    copies in the enclosing decode loop: measured ~8 ms/token of pure
    cache movement at 7B/1k, nearly the matmul cost itself.)"""
    zero = jnp.zeros((), layer.dtype)
    idx = (layer, zero, zero, pos.astype(layer.dtype), zero)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new[None].astype(k_cache.dtype), idx)
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new[None].astype(v_cache.dtype), idx)
    return k_cache, v_cache


def update_kv_cache_rows(k_cache: jax.Array, v_cache: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         layer: jax.Array, pos_rows: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Write one layer's step KV (B, Hkv, T, Dh) into the *stacked*
    (L, B, Hkv, S, Dh) caches at **per-row** positions (B,).

    The continuous-batching twin of :func:`update_kv_cache_at`: slot rows
    belong to different requests, so each row advances its own clock —
    a joining slot prefills at position 0 while its neighbors decode at
    position 900.  A vmap over the batch axis gives every row its own
    ``dynamic_update_slice`` start, which XLA lowers to B independent
    windowed writes into the carried cache (same in-place cost model as
    the shared-clock write).

    Callers must keep ``pos_rows[r] + T <= S`` for every row:
    dynamic_update_slice clamps out-of-range starts *backward*, which
    would silently overwrite the newest valid history (the scheduler
    retires rows at the context edge before dispatching)."""

    def row(ck, cv, kn, vn, p):
        # ck/cv: (L, Hkv, S, Dh) one row's stacked planes; kn/vn: (Hkv, T, Dh)
        zero = jnp.zeros((), jnp.int32)
        idx = (layer.astype(jnp.int32), zero, p.astype(jnp.int32), zero)
        ck = jax.lax.dynamic_update_slice(ck, kn[None].astype(ck.dtype), idx)
        cv = jax.lax.dynamic_update_slice(cv, vn[None].astype(cv.dtype), idx)
        return ck, cv

    return jax.vmap(row, in_axes=(1, 1, 0, 0, 0), out_axes=(1, 1))(
        k_cache, v_cache, k_new, v_new, pos_rows)


def _rows_ceiling_attention(q: jax.Array, k_l: jax.Array, v_l: jax.Array,
                            pos_rows: jax.Array) -> jax.Array:
    """One-shot causal GQA over one layer's K/V (B, Hkv, S, Dh) with a
    **per-row** causal ceiling: row ``r``'s query tokens occupy positions
    ``pos_rows[r]..pos_rows[r]+T-1`` and may see key positions
    ``<= pos_rows[r] + t_local`` only.  Shared by the contiguous slot
    read (:func:`slot_gqa_attention_at`) and the paged gather-view read
    (:func:`paged_gqa_attention_at`) so the two layouts cannot drift on
    masking or accumulation dtype."""
    b, hq, t, dh = q.shape
    hkv = k_l.shape[1]
    s = k_l.shape[2]
    g = hq // hkv

    # operands in cache dtype, f32 accumulation — see _online_fold for why
    qc = q.reshape(b, hkv, g, t, dh).astype(k_l.dtype)
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qc, k_l,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(dh))

    s_idx = jnp.arange(s)[None, None, :]
    t_idx = pos_rows[:, None, None] + jnp.arange(t)[None, :, None]
    mask = s_idx <= t_idx  # (B, T, S) — per-row causal ceiling
    scores = jnp.where(mask[:, None, None], scores, _NEG)

    probs = softmax_f32(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs.astype(v_l.dtype), v_l,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, t, dh).astype(q.dtype)


def slot_gqa_attention_at(q: jax.Array, ck: jax.Array, cv: jax.Array,
                          layer: jax.Array, pos_rows: jax.Array) -> jax.Array:
    """One-shot causal GQA over the *stacked* caches at ``layer`` with a
    **per-row** causal ceiling (see :func:`_rows_ceiling_attention`).

    This is the attention read of the continuous-batching slot step.
    Unlike the ragged-batch path there is no key *floor*: every slot's
    request starts at cache position 0, and a freed slot is reused by
    simply resetting its position — the previous occupant's stale keys
    sit *above* the new request's ceiling, masked until each position is
    overwritten by the new occupant (write-before-visible).  Zeroing the
    row instead would be wrong twice over: it costs an O(S) write, and a
    zero key is a *real* key (it would contribute exp(0-ish) mass to the
    softmax denominator).

    Per-step traffic is O(S) like the one-shot decode path; slot serving
    targets the throughput regime (batch > 1, moderate context) where the
    weight read — amortized over B rows — dominates.
    """
    k_l = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    return _rows_ceiling_attention(q, k_l, v_l, pos_rows)


# ---------------------------------------------------------------------------
# Paged KV: a global page pool + per-slot block tables (PagedAttention).
#
# The pool is ``(L, n_pages, page_size, Hkv, Dh)``: a page is stored
# token-major, so one token's ``(Hkv, Dh)`` slab is contiguous and the KV
# write's scatter has the pool's resident layout.  The page offset must not
# sit between the two axes a token's slab spans: the scatter then wants
# another physical order than the program boundary, the layer loop's carry
# and the fused kernel hold, and XLA bridges them with copies of the whole
# pool (PERF.md §6, PR 27).  Readers bring a page to the head-major
# ``(Hkv, ps, Dh)`` the score math takes.  A slot's logical cache is
# described by one (max_pages,) int32 row of the page table, shared across
# layers: logical position ``p`` of slot ``r`` lives at
# ``pool[:, table[r, p // ps], p % ps]``.
# Physical page 0 is reserved as a scratch page: table entries
# past a slot's reserved pages point at it, and every *invalid* token write
# (decode padding, tokens past ``n_valid``, burst overshoot past a retired
# row's budget) is redirected there — so shared prefix pages are immutable
# by construction and garbage lands where no mask can ever expose it.


# lanes of a vector register.  A pool whose rows are narrower is not only
# stored padded: the TPU's compact layout for ``(L, P, ps, Hkv, 64)`` puts the
# PAGES minor-most (``{1,4,3,2,0}``), so every step that scatters or gathers
# by page copies the whole pool to a page-major layout and back
# (tests/test_tpu_compile.py, LFM2's heads of 64).  ``pool_rows`` keeps a row
# lane-dense instead, and the pool functions below take a pool of either form:
# the scatter and the gather forms by reshaping a token's slab, the fused page
# walk by reading a row of several heads as it lies.
_LANES = 128


def pool_rows(hkv: int, dh: int) -> tuple[int, int]:
    """The two minor axes of a dense paged pool ``(L, P, ps, *pool_rows)``:
    ``(Hkv, Dh)``, but heads narrower than 128 lanes are stored ``128 // Dh``
    to a row, ``(Hkv * Dh // 128, 128)``, where the heads divide so (the same
    bytes in the same order: a token's ``(Hkv, Dh)`` slab reshaped).  A pool
    so folded is one the fused page walk takes (``_fused_choice``: its rows
    fill whole lanes, so a page can be copied as it lies); one that keeps a
    narrow head a row reads through the gather form."""
    f = _LANES // dh if dh < _LANES and _LANES % dh == 0 else 1
    return (hkv // f, f * dh) if f > 1 and hkv % f == 0 else (hkv, dh)


def _heads(pages: jax.Array, dh: int | None) -> jax.Array:
    """``(..., G, W)`` rows of a pool as ``(..., Hkv, Dh)`` heads (the
    identity for an unfolded pool and for an int8 pool's scale planes)."""
    if dh is None or pages.shape[-1] in (dh, 1):
        return pages
    return pages.reshape(*pages.shape[:-2], -1, dh)


def paged_write_indices(page_table: jax.Array, pos_rows: jax.Array,
                        n_valid: jax.Array, t: int, page_size: int
                        ) -> tuple[jax.Array, jax.Array]:
    """Physical (page, offset) index arrays, both (B, T) int32, for one
    slot step's KV writes through the page table.

    Computed ONCE per forward (outside the layer scan — every layer writes
    the same logical positions).  Invalid tokens (``t_local >= n_valid``)
    are redirected to scratch page 0; logical pages past the table width
    clamp into it, where unreserved entries already hold 0."""
    maxp = page_table.shape[1]
    tpos = pos_rows[:, None] + jnp.arange(t)[None, :]          # (B, T)
    valid = jnp.arange(t)[None, :] < n_valid[:, None]          # (B, T)
    pslot = jnp.clip(tpos // page_size, 0, maxp - 1)
    pidx = jnp.take_along_axis(page_table, pslot, axis=1)
    pidx = jnp.where(valid, pidx, 0)
    oidx = tpos % page_size
    return pidx.astype(jnp.int32), oidx.astype(jnp.int32)


def paged_update_kv_rows(pool_k: jax.Array, pool_v: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         layer: jax.Array, pidx: jax.Array, oidx: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Write one layer's step KV (B, Hkv, T, Dh) into the paged pools
    (L, P, ps, Hkv, Dh) at per-token physical ``(page, offset)`` indices
    (B, T) from :func:`paged_write_indices`.

    One advanced-indexing scatter per pool: layer, page and offset are
    adjacent advanced indices, each token's (Hkv, Dh) slab is one
    contiguous window of the pool, and the update operand is
    (B, T, Hkv, Dh) — the step KV with its token axis moved ahead of the
    head axis.  Invalid tokens all target scratch page 0; colliding
    scratch writes are unordered, which is fine — nothing reads that page
    unmasked."""
    kbt = k_new.transpose(0, 2, 1, 3).astype(pool_k.dtype)  # (B, T, Hkv, Dh)
    vbt = v_new.transpose(0, 2, 1, 3).astype(pool_v.dtype)
    # a pool of lane-dense rows (pool_rows) takes the same slab reshaped
    kbt = kbt.reshape(kbt.shape[:2] + pool_k.shape[3:])
    vbt = vbt.reshape(vbt.shape[:2] + pool_v.shape[3:])
    li = layer.astype(jnp.int32)
    pool_k = pool_k.at[li, pidx, oidx].set(kbt)
    pool_v = pool_v.at[li, pidx, oidx].set(vbt)
    return pool_k, pool_v


def paged_gather_layer(pool: jax.Array, layer: jax.Array,
                       page_table: jax.Array,
                       scale_pool: jax.Array | None = None,
                       dh: int | None = None) -> jax.Array:
    """Materialize one layer's logical KV view (B, Hkv, maxp·ps, Dh) by
    gathering each slot's pages from the pool (L, P, ps, Hkv, Dh) and
    moving the head axis ahead of the tokens.  The gather is the paged
    twin of the contiguous layer slice: XLA fuses it into the score dot
    for the short-cache one-shot path, and the long-cache decode path
    avoids it entirely (page-walk fold).

    ``scale_pool``: the int8 pool's per-position scale planes
    (L, P, ps, Hkv, 1) — the gather stays int8-sized and the dequant
    multiply fuses into the downstream dot like the plain cast.  ``dh``: the
    head size, for a pool of lane-dense rows (:func:`pool_rows`)."""

    def view(p):
        pl = jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
        pages = _heads(pl[page_table], dh)  # (B, maxp, ps, Hkv, Dh | 1)
        b, maxp, ps, hkv, last = pages.shape
        return pages.transpose(0, 3, 1, 2, 4).reshape(b, hkv, maxp * ps, last)

    if scale_pool is None:
        return view(pool)
    return dequant_kv(view(pool), view(scale_pool))


def paged_decode_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           pos_rows: jax.Array,
                           scales: tuple[jax.Array, jax.Array] | None = None
                           ) -> jax.Array:
    """Single-token decode over the paged pool that walks only live pages:
    :func:`blocked_live_fold` with the page as the block (the pool already
    stores fixed-size KV chunks — pages ARE the fold's block granularity)
    and one pool gather per step in place of the contiguous block slice.
    Per-row ceilings ride the fold's ``row_pos`` mask; rows whose table
    runs out before the longest neighbor read scratch page 0, fully
    masked.

    ``scales``: the int8 pool's (k, v) scale planes (L, P, ps, Hkv, 1) —
    each fold step gathers the value page AND its scale page and
    dequantizes after the int8-sized HBM read (the point of the
    quantized pool)."""
    b, hq, t, dh = q.shape
    ps, hkv = pool_k.shape[2], pool_k.shape[3] * pool_k.shape[4] // dh
    maxp = page_table.shape[1]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, t, dh)

    def slice_page(pool, start, length):
        pid = jax.lax.dynamic_index_in_dim(page_table, start // ps, 1,
                                           keepdims=False)  # (B,)
        # advanced (scalar layer, (B,) page) indexing: one (B, ps, Hkv, Dh)
        # page gather per fold step — never the whole layer slab — brought
        # to the fold's head-major (B, Hkv, ps, Dh) block
        return _heads(pool[layer.astype(jnp.int32), pid], dh).transpose(0, 2, 1, 3)

    if scales is None:
        kc_arg, vc_arg = pool_k, pool_v
        sl = slice_page
    else:
        ks, vs = scales

        def sl(pair, start, length):
            vals, sc = pair
            return dequant_kv(slice_page(vals, start, length),
                              slice_page(sc, start, length))

        kc_arg, vc_arg = (pool_k, ks), (pool_v, vs)

    _, l, acc = blocked_live_fold(qf, sl, kc_arg, vc_arg,
                                  jnp.max(pos_rows), jnp.int32(0), maxp * ps,
                                  row_pos=pos_rows, block=ps)
    out = acc / jnp.maximum(l, 1e-38)[..., None]
    return out.reshape(b, hq, t, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused paged-attention megakernel (one-dispatch decode, ROADMAP item 2).
#
# One Pallas program per paged step (pure-decode, mixed or verify: one or
# more query tokens a slot) walks the page table directly: grid
# (B,), one slot a step, and inside it a loop over the slot's LIVE pages
# in chunks of ``_WALK_PAGES``.  The pool stays in HBM; a chunk's pages are
# copied straight out of ``pool[layer, table[b, p]]`` into one of two VMEM
# buffers, all of a chunk's copies in flight together and the next chunk's
# behind the fold of this one, the next SLOT's first chunk behind a slot's
# last fold (PR 64: only the launch's first chunk is copied with nothing
# beside it) — no materialized (B, Hkv, maxp·ps, Dh)
# gather, and the online-softmax state is the loop's carry.  Dead pages
# cost nothing: the loop's trip count is the slot's own.  Dense pools
# whose rows fill whole lanes only: heads of 128, or narrower heads stored
# ``f = 128 // Dh`` to a row (``pool_rows``), which the fold reads without
# unpacking (the query is widened to the row, zero in the other heads'
# lanes; a query row keeps the key rows its kv head lies in and takes its
# own lanes of the result).  An int8 pool's scale plane (L, P, ps, Hkv, 1)
# has one lane of 128 a row, and a pool of one narrow head a row half of
# them: no page of either can be sliced for a copy, and such a pool reads
# through the XLA forms below.  The copies are the kernel's own and not a
# BlockSpec's: a BlockSpec keeps two buffers, so one page's copy in
# flight, and takes a grid step for every page of the table, live or not
# (0.46 us a live page and 0.16 us a dead one on a v5e, PERF.md §6 PR 31).
# Gating mirrors the q40 matmul ladder: ``DLLAMA_FUSED_ATTN``
# auto/on/off/interp, auto is a static choice (platform, mesh, shape), and
# every forced-path fallback goes through the warn-once degrade ledger
# (obs/dispatch.py).


_FUSED_ENV = "DLLAMA_FUSED_ATTN"
# pages of one chunk of the walk: 8 pages of 16 tokens at 16 kv heads are
# 16 copies of 64 KB in flight and 2 MB of VMEM for the two buffers.  The
# same 8 over a pool of several heads a row (LFM2's 8 heads of 64: 16 copies
# of 16 KB): a chunk of 16 such pages moved the token gap by 0.1% and cost
# 5 s of every start, the kernel's copies being unrolled where it is traced
# (PERF.md §6, PR 48)
_WALK_PAGES = 8
# the most score elements one fold of the walk may hold: ``Hq * T`` query
# rows by a chunk's ``tokens * Hkv`` keys, in f32 beside its mask and its
# exponentials.  1 Mi of them compile into a v5e's 16 MiB of scoped VMEM
# (Mistral's 32/8 heads up to T = 32, Llama-2-7B's 32/32 up to T = 8, at
# page 16; LFM2's 32/8 of 64, whose token has 4 key rows in the pool, up to
# T = 64), 2 Mi do not (tests/test_tpu_compile.py); a wider block of rows
# keeps the gather form
_SCORE_TILE_MAX = 1 << 20


def fused_mode() -> str:
    """The fused paged-attention gate, read lazily so tests and the
    bench A/B can flip it per engine: ``auto`` (single TPU device, silent CPU
    fallback), ``on`` (degrade loudly if unusable), ``off``, ``interp``
    (force the kernel in Pallas interpret mode — CPU parity tests and
    the ``-fused4`` A/B)."""
    return os.environ.get(_FUSED_ENV, "auto").strip().lower() or "auto"


def _make_fused_kernel(hq: int, hkv: int, dh: int, ps: int, cp: int,
                       t: int, maxp: int, out_dtype, f: int = 1):
    """Build the fused page-walk kernel body for one (head/page/row)
    geometry; ``cp`` is the walk's chunk in pages, ``t`` the query tokens a
    slot, ``maxp`` the table's width, ``f`` the kv heads a row of the pool
    holds (:func:`pool_rows`; 1 for a pool of one head a row).

    Ref order: 3 scalar-prefetch refs (layer (1,), page table (B, maxp),
    per-row positions (B,)), then the q block, the K and V pools left in
    HBM, the output block, and the scratch: two chunk buffers per pool, one
    DMA semaphore per buffer, and one SMEM word.

    The scratch lives across the grid's steps, which run in order on one
    core (``"arbitrary"``), and carries two things from slot ``b`` to slot
    ``b + 1``: the copy of slot ``b + 1``'s first chunk, in flight or landed
    in the buffer slot ``b``'s last fold did not read, and in the SMEM word
    which buffer that is (it follows the chunks the slots before walked).
    Who starts which copy: grid step 0 primes its own chunk 0 into buffer
    0; every fold starts ONE chunk into the other buffer before it waits for
    its own, chunk ``c + 1`` of its slot or, at the slot's last chunk, chunk
    0 of the next slot (table and positions of every slot are
    scalar-prefetched); the last fold of the last slot starts nothing.  So a
    slot's first chunk is copied behind the previous slot's last fold, every
    ``wait`` answers exactly one ``start``, and no copy is in flight when
    the kernel ends.  The fold is what it was: a slot's output does not
    depend on the slots beside it, bit for bit."""
    rows = hq * t
    inv_sqrt = np.float32(1.0 / math.sqrt(dh))
    # a token's keys are the ``kvr`` rows of ``width`` lanes it has in the
    # pool, and ``gr`` query heads read each: the kv heads and their groups
    # for a pool of one head a row, ``f`` kv heads' groups a row otherwise
    kvr, gr, width = hkv // f, hq // hkv * f, f * dh
    n_keys = cp * ps * kvr

    def kernel(layer_ref, ptab_ref, pos_ref, q_ref, *rest):
        from jax.experimental import pallas as plx
        from jax.experimental.pallas import tpu as pltpu
        pools, o_ref, bufs = rest[:2], rest[2], rest[3:5]
        sem, first = rest[5], rest[6]
        b = plx.program_id(0)
        nxt = jax.lax.min(b + 1, plx.num_programs(0) - 1)
        has_next = b < nxt
        pos = pos_ref[b]
        layer = layer_ref[0]

        # Scalar index arithmetic goes through ``jax.lax``: a ``jnp`` function
        # (``//``, ``minimum``, ``where``) is a ``jit`` of its own, and every
        # call of one in here is a nested trace, at every site of every
        # program of every start (positions are never negative, so ``div``
        # is the floor)
        def last_page(p):
            # a row's last live page: its last query token's (the step's own
            # keys are in the pool before the read), inside the table
            return jax.lax.div(p, ps) if t == 1 else \
                jax.lax.min(jax.lax.div(p + (t - 1), ps), maxp - 1)

        last, last_nxt = last_page(pos), last_page(pos_ref[nxt])
        n_chunks = jax.lax.div(last, cp) + 1

        def start(r, c, last, slot):
            # chunk ``c`` of row ``r``, whose last live page is ``last``.  The
            # last chunk's pages past ``last`` read the last live page
            # again: their keys are masked, and a buffer never holds bytes
            # that were not a page's
            for i in range(cp):
                page = ptab_ref[r, jax.lax.min(c * cp + i, last)]
                for pool, buf in zip(pools, bufs):
                    pltpu.make_async_copy(pool.at[layer, page],
                                          buf.at[slot, i],
                                          sem.at[slot]).start()

        def wait(slot):
            for i in range(cp):
                for pool, buf in zip(pools, bufs):
                    pltpu.make_async_copy(pool.at[0, 0], buf.at[slot, i],
                                          sem.at[slot]).wait()

        # (Hq*T, width): head-major rows, a head's T tokens together.  Key
        # column ``k`` of a chunk is token ``k // kvr`` of pool row ``k %
        # kvr``; a query row keeps the keys of the pool row its kv head lies
        # in, up to its ceiling.  Where that row holds ``f`` heads the query
        # came widened to it, zero in the other heads' lanes, so the 128-lane
        # contraction adds exactly 0 for them and the score is the own head's
        qb = q_ref[0]
        if t == 1:
            row = jax.lax.broadcasted_iota(jnp.int32, (hq, n_keys), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (hq, n_keys), 1)
            own = jax.lax.rem(col, kvr) == jax.lax.div(row, gr)
            tok = jax.lax.div(col, kvr)  # a key's token, within its chunk

            def live(c):
                return own & (c * (cp * ps) + tok <= pos)
        else:
            # token ``j`` of the block sees key positions <= pos + j.  At
            # hundreds of rows the mask is the loop's VPU work, so what does
            # not depend on the chunk is folded into one threshold outside
            # it: a key is live from chunk ``c`` on iff ``c * (cp * ps) <=
            # pos + j - its token``, another head's key never
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, n_keys), 1)
            own = jax.lax.rem(col, kvr) == jax.lax.div(jax.lax.div(row, t), gr)
            thr = jnp.where(own, pos + jax.lax.rem(row, t)
                            - jax.lax.div(col, kvr), -1)

            def live(c):
                return thr >= c * (cp * ps)

        def fold(c, carry):
            m_prev, l_prev, acc = carry
            slot = jax.lax.rem(begin + c, 2)
            more = c + 1 < n_chunks

            # one copy site for both: the slot's next chunk, else the next
            # slot's first (the copies are unrolled where this is traced)
            @plx.when(more | has_next)
            def _ahead():
                start(jax.lax.select(more, b, nxt),
                      jax.lax.select(more, c + 1, jnp.zeros_like(c)),
                      jax.lax.select(more, last, last_nxt), 1 - slot)

            wait(slot)
            k = bufs[0][slot]    # (cp, ps, kvr, width) or (cp, ps * kvr, width)
            v = bufs[1][slot]
            # the pages stay token-major: a chunk's (cp, ps, Hkv) rows are
            # one operand of n_keys keys, every query row is scored
            # against all of them in ONE dot, and a row keeps only the
            # columns of its own kv head.  The masked columns weigh
            # exactly 0 in the second dot, so the result is the per-head
            # read; the MXU does Hkv times the useful work, which at one
            # query row is nothing beside a relayout of every page to
            # head-major and Hkv dots of one row each, and at a chunk's
            # rows is still the faster form at every width but one
            # (tools/sweep_attn.py --paged; PERF.md §6, PR 37)
            kf = k.reshape(n_keys, width)
            vf = v.reshape(n_keys, width)
            sc = jax.lax.dot_general(
                qb.astype(kf.dtype), kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * inv_sqrt
            keep = live(c)
            sc = jnp.where(keep, sc, _NEG)              # (Hq*T, n_keys)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                pexp.astype(vf.dtype), vf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (Hq*T, width)
            return m_new, l_new, alpha * acc + pv

        @plx.when(b == 0)
        def _prime():
            first[0] = 0
            start(0, 0, last, 0)

        begin = first[0]   # the buffer this slot's first chunk is in
        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, fold,
            (jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, width), jnp.float32)))
        # the buffer the next slot's first chunk went to
        first[0] = jax.lax.rem(begin + n_chunks, 2)
        out = acc / jnp.maximum(l, 1e-38)
        if f > 1:
            # the second dot gave every head of the row; a query row takes
            # the lanes of its own
            head = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0),
                t * (hq // hkv))
            mine = out[:, :dh]
            for i in range(1, f):
                mine = jnp.where(jax.lax.rem(head, f) == i,
                                 out[:, i * dh:(i + 1) * dh], mine)
            out = mine
        o_ref[0] = out.astype(out_dtype)

    return kernel


def fused_paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                          layer: jax.Array, page_table: jax.Array,
                          pos_rows: jax.Array,
                          *, interpret: bool = False) -> jax.Array:
    """Paged GQA of ``T >= 1`` query tokens a slot over a dense pool
    ``(L, P, ps, G, W)`` whose rows fill whole lanes (``W`` = the head size,
    or 128 with ``128 // Dh`` heads a row, :func:`pool_rows`) as ONE
    kernel: page-table walk and online-softmax fold in a single
    pallas_call, under :func:`_rows_ceiling_attention`'s per-row causal
    ceiling (token ``j`` of row ``r`` sees key positions ``<= pos_rows[r]
    + j``).  Numerics mirror :func:`paged_decode_attention`'s fold (same
    operand dtypes, f32 accumulation, ``_NEG`` mask fill); traffic is the
    live pages of each row up to its last query token's, rounded up to the
    walk's chunk.  Tokens past a row's ``n_valid`` read what the gather
    form reads for them (their pages past the row's reservation are
    scratch page 0) and the caller drops them.

    The grid is one slot a step, in order on one core, and the chunk
    buffers, their two DMA semaphores and one SMEM word are scratch that
    lives across the steps: a slot's last fold starts the copy of the next
    slot's first chunk into the buffer it is not reading, and the word says
    which buffer that was (:func:`_make_fused_kernel`).  A slot's rows are
    bit for bit what a call of that slot alone gives.
    """
    from jax.experimental import pallas as plx
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, dh = q.shape
    ps, kvr, width = pool_k.shape[2:]
    f = width // dh          # kv heads a row of the pool (pool_rows)
    hkv = kvr * f
    maxp = page_table.shape[1]
    cp = min(_WALK_PAGES, maxp)
    pools = [pool_k, pool_v]
    if f > 1:
        # a folded row is a token's slab reshaped; the page goes the rest of
        # the way, ``(ps * kvr, 128)``: the same bytes in the same order (XLA
        # makes the reshape a bitcast of the resident pool), and a page whose
        # second-minor axis fills the chip's sublane tile where ``kvr`` = 4
        # rows would be padded to it in VMEM (0.62 against 0.83 us a chunk of
        # 8 pages, PERF.md §6, PR 48)
        pools = [pool.reshape(*pool.shape[:2], ps * kvr, width)
                 for pool in pools]

    qr = q.reshape(b, hq * t, dh)
    if f > 1:
        # each query row widened to the pool's row: its head's lanes where
        # its kv head lies in the row, zero in the others
        lane = np.arange(hq)[:, None] // (hq // hkv) % f == np.arange(f)
        qr = jnp.where(np.repeat(lane, t, axis=0)[None, :, :, None],
                       qr[:, :, None, :], 0).reshape(b, hq * t, width)

    def row_map(bi, *_):
        return (bi, 0, 0)

    hbm = plx.BlockSpec(memory_space=plx.ANY)
    kernel = _make_fused_kernel(hq, hkv, dh, ps, cp, t, maxp, q.dtype, f)
    out = plx.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[plx.BlockSpec((1, hq * t, width), row_map)]
            + [hbm] * len(pools),
            out_specs=plx.BlockSpec((1, hq * t, dh), row_map),
            scratch_shapes=[pltpu.VMEM((2, cp, *pool.shape[2:]), pool.dtype)
                            for pool in pools]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, hq * t, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attn_fused",
    )(jnp.atleast_1d(layer).astype(jnp.int32),
      page_table.astype(jnp.int32), pos_rows.astype(jnp.int32),
      qr, *pools)
    return out.reshape(b, hq, t, dh)


# JAX traces a Pallas kernel anew at every call site, and a start of a served
# LFM2 has fourteen (two attention sites a step program): under a jit the
# sites of one step width share one trace, 4 s of that cell's ``setup_s`` in
# the server's process (PERF.md §6, PR 48).  The walk over a pool of one head
# a row is called bare: its programs hold ONE site each (a scan over the
# layers), so there is no trace to share, and the wrapper costs each of them
# 0.02 s of trace and lowering and counts the nested trace once more in
# ``jaxpr_trace_seconds`` (``setup_trace_s`` 20.8-21.6 s with it against
# 18.5-19.6 without, in Mistral's served cell; PR 64 tried it, PERF.md §6).
_folded_walk = jax.jit(fused_paged_attention, static_argnames=("interpret",))


def _fused_choice(t: int, hq: int, hkv: int, dh: int = 128,
                  quantized: bool = False, ps: int = 16,
                  maxp: int = _WALK_PAGES,
                  row: int | None = None) -> tuple[bool, bool]:
    """Resolve the fused-vs-fallback decision for one call site from
    static facts only (mode, platform, mesh, the block's query tokens
    ``t``, head counts and size, the pool's codec, the page size ``ps``,
    the table's width ``maxp``, which give the walk's chunk, and the width
    ``row`` of the pool's rows: its minor axis, ``dh`` where not given), so
    it is the same inside and outside a jit trace.  Returns ``(use_fused,
    interpret)``.  On a single TPU device ``auto``/``on`` mean the fused
    kernel at every ``t`` (the pure-decode step's one token, a mixed
    step's chunk, a verify step's ``spec_k + 1``) for a dense pool whose
    ROWS fill whole lanes: heads of 128, or narrower heads that
    :func:`pool_rows` folded ``row // dh`` to a row (a page is copied as it
    lies, and a copy of part of a 128-lane row is refused, so a pool that
    keeps a narrow head a row stays on the gather form), and whose score
    tile fits ``_SCORE_TILE_MAX``, reckoned with the keys a token really
    has in the pool, ``hkv * dh // row`` rows; nothing is executed to
    decide, so a Mosaic lowering or runtime error propagates and fails the
    run (values are checked on the chip by chip_smoke.py, at heads of 128
    and at heads of 64 on a folded pool).  A ``pallas_call`` is not
    partitioned by GSPMD, so on a multi-device mesh the TPU path stays
    the gather form.  ``auto`` off-TPU falls
    back silently (the clean-run ledger contract); ``on`` where the
    kernel cannot run degrades loudly (warn-once)."""
    mode = fused_mode()
    row = dh if row is None else row
    tile = hq * t * min(_WALK_PAGES, maxp) * ps * (hkv * dh // row)
    if mode == "off" or hq % hkv != 0 or quantized or tile > _SCORE_TILE_MAX:
        return False, False
    if mode == "interp":
        return True, True
    backend = jax.default_backend()
    mesh = get_active_mesh()
    n_dev = mesh.size if mesh is not None else 1
    if backend == "tpu" and n_dev == 1:
        return row % _LANES == 0, False
    if mode == "on":
        from ..obs import dispatch as obs_dispatch
        obs_dispatch.record_degrade(
            "attn", "fused_needs_tpu", warn_key=(backend, n_dev),
            backend=backend, mesh_size=n_dev)
    return False, False


def paged_gqa_attention_at(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           pos_rows: jax.Array,
                           scales: tuple[jax.Array, jax.Array] | None = None
                           ) -> jax.Array:
    """Causal GQA read through the page-table indirection at ``layer``,
    with the slot path's per-row causal ceiling.  A dense pool prefers the
    fused page-walk megakernel at every ``T``
    (:func:`fused_paged_attention` — one dispatch, no materialized gather)
    when the ``DLLAMA_FUSED_ATTN`` ladder resolves to it; otherwise
    dispatch mirrors the contiguous path: long-cache single-token decode
    walks live pages (:func:`paged_decode_attention`, O(max pos)
    traffic); everything else gathers the logical view and reuses the
    one-shot slot math, so paged and contiguous reads are the same
    computation over the same logical keys.

    Every arm records its dispatch family at trace time (the PR 4
    ledger): ``paged-fused`` is one attention-family dispatch; the
    unfused one-shot arm is the materialized gather (``paged-gather``)
    plus the score/softmax pass (``attn-score``), plus a ``dequant``
    record for int8 pools whose scale multiply rides the gathered view.

    ``scales``: the int8-pool (k, v) scale planes (L, P, ps, Hkv, 1);
    every unfused arm dequantizes after the int8-sized page read."""
    from ..obs import dispatch as obs_dispatch
    t, dh = q.shape[2], q.shape[3]
    ps, hkv = pool_k.shape[2], pool_k.shape[3] * pool_k.shape[4] // dh
    s = page_table.shape[1] * ps
    codec = "kv_int8" if scales is not None else "kv_dense"
    use_fused, interp = _fused_choice(t, q.shape[1], hkv, q.shape[3],
                                      scales is not None, ps,
                                      page_table.shape[1], pool_k.shape[4])
    if use_fused:
        # ``ahead``: what a slot's last fold copies behind it, the next
        # slot's first chunk, by construction in b - 1 of b grid steps
        obs_dispatch.record_dispatch(codec, "paged-fused", t=t, s=s,
                                     page_size=ps, interpret=interp,
                                     ahead="slot")
        walk = fused_paged_attention if pool_k.shape[4] == dh else _folded_walk
        return walk(q, pool_k, pool_v, layer, page_table, pos_rows,
                    interpret=interp)
    if t == 1 and _use_live_walk(q.shape[1] // hkv, t, s):
        obs_dispatch.record_dispatch(codec, "paged-decode", t=t, s=s,
                                     page_size=ps)
        return paged_decode_attention(q, pool_k, pool_v, layer, page_table,
                                      pos_rows, scales=scales)
    obs_dispatch.record_dispatch(codec, "paged-gather", t=t, s=s,
                                 page_size=ps)
    obs_dispatch.record_dispatch(codec, "attn-score", t=t, s=s, page_size=ps)
    if scales is not None:
        obs_dispatch.record_dispatch("kv_int8", "dequant", t=t, s=s,
                                     page_size=ps)
    ks, vs = scales if scales is not None else (None, None)
    k_l = paged_gather_layer(pool_k, layer, page_table, scale_pool=ks, dh=dh)
    v_l = paged_gather_layer(pool_v, layer, page_table, scale_pool=vs, dh=dh)
    return _rows_ceiling_attention(q, k_l, v_l, pos_rows)


# Above this many score elements per kv-head group the one-shot path's full
# (B, Hkv, G, T, S) f32 score tensor is the HBM wall at long context
# (VERDICT r01 weak #5): such a call walks KV blocks with an online softmax
# instead (see _use_live_walk).
_BLOCKED_THRESHOLD = 1 << 21
# Over caches at least this long every call (any T) walks only the live
# prefix of the cache instead of reading the whole preallocated buffer;
# below it, one-shot attention is cheaper than the loop overhead.
_WALK_MIN_S = 4096
# numpy (not jnp): a module-level device constant would initialize the XLA
# backend at import time, breaking jax.distributed.initialize ordering
_NEG = np.float32(-1e30)  # finite -inf stand-in: keeps the running max


def _kv_chunk(s: int) -> int:
    """KV block width of the live walk over ``s`` positions, one for every
    ``T``: against 1024, a 256-key block saves under 1 ms of a 256-token
    prompt at position 0 and costs 8 ms of one at 16k (7B shapes on the
    chip, PERF.md §6, PR 29)."""
    for c in (1024, 512, 256, 128):
        if s % c == 0:
            return c
    return s


def _use_live_walk(g: int, t: int, s: int) -> bool:
    """The one dispatch rule of the contiguous-cache attention, made from
    shapes only so the stacked-cache, per-layer and sequence-parallel entry
    points can never diverge on which algorithm serves the same shapes:
    walk live blocks when the cache is blockable and either long (any
    ``t``) or the one-shot score tensor would pass ``_BLOCKED_THRESHOLD``.
    ``_kv_chunk(s) == s`` would be one loop step over the whole cache: all
    the loop overhead, none of the O(pos) traffic win."""
    return _kv_chunk(s) < s and (s >= _WALK_MIN_S
                                 or g * t * s > _BLOCKED_THRESHOLD)


def _online_fold(qf, kb, vb, mask, m, l, acc, scale):
    """One flash-softmax block fold of the live walk: fold block scores
    masked by ``mask`` (``(T, S)`` broadcast over (B, Hkv, G), or
    ``(B, T, S)`` for per-row ragged-batch masks) into the running (max,
    denom, numerator).

    Dots keep the cache's dtype as operand type with f32 *accumulation*
    (bf16 in, f32 out on the MXU): widening a bf16 cache to f32 first makes
    XLA lower cast+dot+mask as one VPU loop fusion — measured ~8 GB/s
    effective on the decode score read, ~50× off the HBM rate the dot-form
    achieves."""
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qf.astype(kb.dtype), kb,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, _NEG)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = alpha * l + p.sum(axis=-1)
    acc_new = alpha[..., None] * acc + jnp.einsum(
        "bhgts,bhsd->bhgtd", p.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _fold_init(b, hkv, g, t, dh):
    return (jnp.full((b, hkv, g, t), _NEG),
            jnp.zeros((b, hkv, g, t), jnp.float32),
            jnp.zeros((b, hkv, g, t, dh), jnp.float32))


def blocked_live_fold(qf, slice_block, k_cache, v_cache, pos, base, c,
                      wrap=lambda x: x, row_start: jax.Array | None = None,
                      row_pos: jax.Array | None = None,
                      block: int | None = None):
    """The length-aware online-softmax core: walk only the KV blocks of a
    chunk of length ``c`` (global position offset ``base``) that cover
    live positions — those up to the last query's, ``pos + T - 1`` —
    folding each into the running (max, denom, numerator); query row ``i``
    masks at its own ceiling ``pos + i``.  Shared by
    :func:`live_gqa_attention` (base 0, whole cache), the
    sequence-parallel per-shard partials (base = the shard's chunk start),
    and the paged decode walk (block = one KV page) so the block walk
    cannot drift between them.

    ``slice_block(cache, start, length)`` cuts one (B, Hkv, length, Dh)
    block; ``wrap`` marks fresh accumulators (shard_map bodies pass a
    device-varying cast).  ``row_start`` (B,) is the ragged batch's
    per-row key floor.  ``row_pos`` (B,) replaces the scalar causal
    ceiling with a per-row one (T must be 1): ``pos`` then only bounds
    the walk — pass its row max — while each row masks at its own
    ceiling.  ``block`` overrides :func:`_kv_chunk` when the storage
    layout fixes the granularity (paged pools walk page-sized blocks).
    Returns raw ``(m, l, acc)`` — callers gated on a non-empty live region
    fold at least one block, so ``m`` is a real max.  The caller
    normalizes (``acc / l``) or combines partials."""
    b, hkv, g, t, dh = qf.shape
    if row_pos is not None and t != 1:
        raise ValueError("per-row ceilings (row_pos) need T == 1")
    if block is None:
        block = _kv_chunk(c)
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    local_last = jnp.clip(pos + (t - 1) - base, 0, c - 1)
    n_live = local_last // block + 1
    t_idx = pos + jnp.arange(t)[:, None]  # (T, 1): each row's causal ceiling

    def cond(carry):
        return carry[0] < n_live

    def body(carry):
        i, m, l, acc = carry
        start = i * block
        kb = slice_block(k_cache, start, block)
        vb = slice_block(v_cache, start, block)
        s_idx = base + start + jnp.arange(block)
        if row_pos is not None:  # slot batch: per-row causal ceiling
            mask = s_idx[None, None, :] <= row_pos[:, None, None]  # (B, 1, blk)
        else:
            mask = s_idx[None, :] <= t_idx  # (T, blk)
        if row_start is not None:  # ragged batch: per-row key floor
            floor = s_idx[None, None] >= row_start[:, None, None]
            mask = (mask if mask.ndim == 3 else mask[None]) & floor
        m, l, acc = _online_fold(qf, kb, vb, mask, m, l, acc, scale)
        return i + 1, m, l, acc

    m0, l0, acc0 = _fold_init(b, hkv, g, t, dh)
    init = (jnp.int32(0), wrap(m0), wrap(l0), wrap(acc0))
    _, m, l, acc = jax.lax.while_loop(cond, body, init)
    return m, l, acc


def live_gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                       pos: jax.Array,
                       layer: jax.Array | None = None,
                       start: jax.Array | None = None,
                       scales: tuple[jax.Array, jax.Array] | None = None,
                       block: int | None = None) -> jax.Array:
    """Causal GQA for ``T`` query rows at ``pos..pos+T-1`` that reads only
    the KV blocks covering positions ``0..pos+T-1``: the flash-style
    online softmax (peak memory O(T·block), numerically the one-shot path:
    same f32 accumulation, association differs only within the rescale
    chain) over the live length of the cache, not its capacity.

    A static-shape einsum over the full cache costs O(S) HBM traffic per
    call no matter where in the sequence it stands — at 64k context that
    is ~32 GB/token for 7B shapes, dwarfing the weights, and a 256-token
    prompt at position 0 of a 32k cache scores 32× the keys it can see.
    The reference's attention loop is O(pos) (llama2-tasks.cpp:68-92);
    this restores that bound under XLA's static shapes with a
    ``lax.while_loop`` whose trip count is ``(pos+T-1)//block + 1``: each
    step dynamic-slices one KV block and folds it into the online-softmax
    accumulator, so traffic is proportional to the live prefix.

    With ``layer`` the caches are the *stacked* (L, B, Hkv, S, Dh) buffers
    and each block is sliced at ``(layer, ..., start, ...)`` directly —
    slicing out the layer first would materialize the whole layer slab
    (O(S) again, e.g. 128 MB per layer-step at 16k) before the loop reads
    its first block.  ``start`` (B,) is the ragged batch's key floor;
    ``scales`` the int8 cache's (k, v) dequant planes, sliced block-wise
    beside the values; ``block`` overrides the fold's width
    (tools/sweep_attn.py times the candidates through it).
    """
    from ..obs import dispatch as obs_dispatch
    b, hq, t, dh = q.shape
    seq_ax = 2 if layer is None else 3
    hkv = k_cache.shape[seq_ax - 1]
    s = k_cache.shape[seq_ax]
    g = hq // hkv
    obs_dispatch.record_dispatch("attn", "live-walk", t=t, s=s)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, t, dh)

    def slice_block(cache, start, length):
        # last dim from the array itself: serves both (…, Dh) value blocks
        # and (…, 1) scale columns with one index recipe
        if layer is None:
            return jax.lax.dynamic_slice_in_dim(cache, start, length, axis=2)
        zero = jnp.zeros((), jnp.int32)
        blk = jax.lax.dynamic_slice(
            cache, (layer.astype(jnp.int32), zero, zero, start, zero),
            (1, b, hkv, length, cache.shape[-1]))
        return blk[0]

    if scales is None:
        kc_arg, vc_arg = k_cache, v_cache
        sl = slice_block
    else:
        # int8 cache: slice the value block AND its per-position scale
        # column, dequantize after the (int8-sized) HBM read
        ks, vs = scales

        def sl(pair, start, length):
            vals, sc = pair
            return dequant_kv(slice_block(vals, start, length),
                              slice_block(sc, start, length))

        kc_arg, vc_arg = (k_cache, ks), (v_cache, vs)

    _, l, acc = blocked_live_fold(qf, sl, kc_arg, vc_arg, pos,
                                  jnp.int32(0), s, row_start=start, block=block)
    out = acc / jnp.maximum(l, 1e-38)[..., None]
    return out.reshape(b, hq, t, dh).astype(q.dtype)


def gqa_attention_at(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     layer: jax.Array, pos: jax.Array, q_len: int,
                     start: jax.Array | None = None,
                     scales: tuple[jax.Array, jax.Array] | None = None
                     ) -> jax.Array:
    """:func:`gqa_attention` over the *stacked* (L, B, Hkv, S, Dh) caches
    at ``layer``.

    The live walk (decode and prefill alike, see :func:`_use_live_walk`)
    slices its KV blocks straight out of the stacked buffer, O(pos + T)
    traffic end to end; only the short-cache one-shot path reads the
    layer slice, which XLA fuses into the score dot rather than
    materializing (observed in the 7B decode xplane).

    ``scales``: the int8-cache dequant planes (Lk, Lv stacked,
    (L, B, Hkv, S, 1) f32).  The walk dequantizes block-wise (the HBM
    read stays int8-sized — the point of the quantized cache); the
    one-shot path dequantizes the layer slice, which XLA fuses into the
    dot like the plain cast.
    """
    if _use_live_walk(q.shape[1] // ck.shape[2], q.shape[2], ck.shape[3]):
        return live_gqa_attention(q, ck, cv, pos, layer=layer, start=start,
                                  scales=scales)
    k_l = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    if scales is not None:
        ks, vs = scales
        k_l = dequant_kv(k_l, jax.lax.dynamic_index_in_dim(ks, layer, 0,
                                                           keepdims=False))
        v_l = dequant_kv(v_l, jax.lax.dynamic_index_in_dim(vs, layer, 0,
                                                           keepdims=False))
    return gqa_attention(q, k_l, v_l, pos, q_len, start=start)


def gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  pos: jax.Array, q_len: int,
                  start: jax.Array | None = None) -> jax.Array:
    """Causal GQA over the cache.

    q:        (B, Hq, T, Dh) — already RoPE'd
    k_cache:  (B, Hkv, S, Dh) — positions ≥ pos+T are garbage and masked out
    v_cache:  (B, Hkv, S, Dh)
    pos:      scalar, index of q's first token
    returns:  (B, Hq, T, Dh)

    Scale is 1/sqrt(head_size) (llama2-tasks.cpp:67).  GQA head grouping
    ``kvMul = nHeads/nKvHeads`` (llama2-tasks.cpp:58) becomes a reshape to
    (B, Hkv, G, T, Dh) so each kv head serves G query heads in one einsum.

    A long cache (any ``T``) and a score tensor past
    ``_BLOCKED_THRESHOLD`` elements per batch×kv-head dispatch to the
    length-aware :func:`live_gqa_attention` (:func:`_use_live_walk`); the
    rest is the one-shot form below.  Either way the call site records
    its family in the dispatch ledger at trace time: ``attn/live-walk``
    or ``attn/one-shot``.

    ``start`` (B,) is the ragged-batch key floor: row ``b`` may only see
    key positions ``>= start[b]`` (its left-padding slots hold other
    prompts' alignment garbage).  The mask fill is the finite ``_NEG``,
    not -inf: a fully-masked query row (a pad position) then softmaxes to
    uniform garbage instead of NaN — its output is never read (the head
    picks the common last index; pad slots stay masked forever), and for
    live rows ``exp(_NEG - m)`` underflows to exactly 0.0, so the result
    is bit-identical to the -inf fill.
    """
    from ..obs import dispatch as obs_dispatch
    b, hq, t, dh = q.shape
    hkv = k_cache.shape[1]
    s = k_cache.shape[2]
    g = hq // hkv

    if _use_live_walk(g, t, s):
        return live_gqa_attention(q, k_cache, v_cache, pos, start=start)
    obs_dispatch.record_dispatch("attn", "one-shot", t=t, s=s)

    # operands in cache dtype, f32 accumulation — see _online_fold for why
    qc = q.reshape(b, hkv, g, t, dh).astype(k_cache.dtype)
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qc, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(dh))

    # causal + validity mask: key position s_idx is visible to query t_idx
    # iff s_idx <= pos + t_idx (and, ragged, s_idx >= start[row])
    s_idx = jnp.arange(s)[None, :]
    t_idx = pos + jnp.arange(t)[:, None]
    mask = s_idx <= t_idx  # (T, S)
    if start is None:
        scores = jnp.where(mask[None, None, None], scores, _NEG)
    else:
        mask = mask[None] & (s_idx[None] >= start[:, None, None])  # (B, T, S)
        scores = jnp.where(mask[:, None, None], scores, _NEG)

    probs = softmax_f32(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, t, dh).astype(q.dtype)
