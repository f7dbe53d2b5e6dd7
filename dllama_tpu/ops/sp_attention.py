"""Sequence-parallel attention: seq-sharded KV cache + distributed softmax.

Long-context capability the reference does not have (SURVEY §5: its only
long-context lever is TP's 1/n KV shrink; seqLen is a hard per-node
ceiling, commands.hpp:12).  Here the KV cache's sequence axis is sharded
over the mesh's ``sp`` axis, so max context scales with sp × per-chip HBM.

Algorithm (flash-attention softmax decomposition across shards):
each sp shard holds KV positions ``[i·C, (i+1)·C)`` and computes, for the
(replicated) queries, its local masked scores, local running max ``m_i``,
partial denominator ``l_i = Σ exp(s−m_i)`` and partial numerator
``o_i = exp(s−m_i)·V_i``.  The global softmax is reassembled with one
``all_gather`` of the (tiny) ``m_i`` plus two ``psum``s:

    M = max_i m_i;   out = Σ_i e^{m_i−M}·o_i  /  Σ_i e^{m_i−M}·l_i

— a single ICI round regardless of sequence length, versus the
O(n_shards) steps of a rotation-based ring.  (A ppermute ring variant
makes sense for sharded-Q prefill; for decode and replicated-Q prefill
the one-round combine is strictly better.)

Prefill KV cache *updates* stay with GSPMD (``ops.attention.
update_kv_cache_at``'s plain dynamic_update_slice — the block write is
amortized over the whole prompt); the per-step decode write uses
:func:`sp_update_kv_cache_at`, whose shard_map makes the write shard-local
by construction instead of trusting GSPMD's lowering choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import _use_live_walk, blocked_live_fold

NEG_BIG = -1e30  # stand-in for -inf that keeps exp() NaN-free on empty shards


def sp_update_kv_cache_at(k_cache: jax.Array, v_cache: jax.Array,
                          k_new: jax.Array, v_new: jax.Array,
                          layer: jax.Array, pos: jax.Array, mesh,
                          kv_spec: P = P(None, "dp", "tp", "sp", None),
                          new_spec: P = P("dp", "tp", None, None)
                          ) -> tuple[jax.Array, jax.Array]:
    """Decode-step KV write for *stacked* (L, B, Hkv, S, Dh) caches carried
    through the layer scan: writes one layer's decode-step row at
    ``(layer, pos)``, shard-local by construction (see
    ops.attention.update_kv_cache_at for why the caches are carried).

    A plain ``dynamic_update_slice`` on an sp-sharded cache leaves the
    lowering to GSPMD, which is *correct* but free to insert a
    gather/scatter per step.  Under ``shard_map`` the write is explicit:
    every shard runs the same update with the position clamped into its
    local range, and a mask keeps non-owning shards' rows unchanged — no
    communication by construction (the new row is replicated over ``sp``).

    Decode-only: exactly one token (T == 1) per call — a T-token window
    could straddle an ``sp`` shard boundary, which this single-row
    ownership logic does not implement (prefill block writes go through
    the GSPMD path in the transformer instead)."""
    if k_new.shape[2] != 1:
        raise ValueError(
            f"sp_update_kv_cache_at writes one decode step, got T={k_new.shape[2]}")
    sp = mesh.shape.get("sp", 1)
    chunk = k_cache.shape[3] // sp

    def shard_fn(kc, vc, kn, vn):
        i = jax.lax.axis_index("sp")
        local = pos - i * chunk
        owned = (local >= 0) & (local < chunk)
        idx = jnp.clip(local, 0, chunk - 1)
        zero = jnp.zeros((), layer.dtype)
        start = (layer, zero, zero, idx.astype(layer.dtype), zero)

        def write(cache, new):
            row = jax.lax.dynamic_slice(cache, start, (1,) + new.shape[:2] + (1, new.shape[-1]))
            new = jnp.where(owned, new[None, :, :, :1].astype(cache.dtype), row)
            return jax.lax.dynamic_update_slice(cache, new, start)

        return write(kc, kn), write(vc, vn)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(kv_spec, kv_spec, new_spec, new_spec),
        out_specs=(kv_spec, kv_spec))(k_cache, v_cache, k_new, v_new)


def _varying(x):
    """Mark a freshly-created accumulator as device-varying over the mesh
    (shard_map branch/carry types must match the computed side)."""
    return jax.lax.pcast(x, ("dp", "sp", "tp"), to="varying")


def _empty_partials(shape, dh):
    """The (o_i, l_i, m_i) triple a fully-masked chunk produces — shared by
    the ring accumulator init and the one-round path's skip branch."""
    return (_varying(jnp.zeros(shape + (dh,), jnp.float32)),
            _varying(jnp.zeros(shape, jnp.float32)),
            _varying(jnp.full(shape, NEG_BIG, jnp.float32)))


def _local_partials(q, k, v, pos, q_len, chunk_start):
    """Per-shard partial attention.

    q: (B, Hkv, G, T, Dh) f32 — grouped queries
    k/v: (B, Hkv, C, Dh) — this shard's chunk
    Returns (o_i (B,Hkv,G,T,Dh), l_i (B,Hkv,G,T), m_i (B,Hkv,G,T)).
    """
    c = k.shape[2]
    # cache-dtype operands + f32 accumulation (see attention._online_fold)
    scores = jnp.einsum("bhgtd,bhsd->bhgts", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))

    s_idx = chunk_start + jnp.arange(c)[None, :]          # global key positions
    t_idx = pos + jnp.arange(q_len)[:, None]
    mask = s_idx <= t_idx                                  # (T, C) causal+validity
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)

    m_i = jnp.maximum(jnp.max(scores, axis=-1), NEG_BIG)   # (B,Hkv,G,T)
    p = jnp.exp(scores - m_i[..., None])                   # masked → exp(-inf)=0
    l_i = jnp.sum(p, axis=-1)
    o_i = jnp.einsum("bhgts,bhsd->bhgtd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return o_i, l_i, m_i


def _local_partials_blocked(q, k, v, pos, chunk_start):
    """Decode-step (T==1) per-shard partials that read only the KV blocks
    covering this shard's *live* positions — the within-shard analogue of
    ops.attention.live_gqa_attention (same shared block-walk core), so
    sp long-context decode is O(live prefix) per shard instead of
    O(chunk): at 128k context over sp=8, a shard whose live region is 4k
    reads 4k positions, not its whole 16k chunk.  Produces the same
    (o_i, l_i, m_i) convention as :func:`_local_partials` (the caller
    gates on a non-empty live region, so at least one block folds and
    ``m_i`` is a real max)."""
    def slice_block(cache, start, length):
        return jax.lax.dynamic_slice_in_dim(cache, start, length, axis=2)

    # accumulators marked device-varying so the while_loop carry type
    # matches the body's shard-varying values (same trick as _empty_partials)
    m, l, acc = blocked_live_fold(q, slice_block, k, v, pos, chunk_start,
                                  k.shape[2], wrap=_varying)
    return acc, l, m


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh,
                   pos0: jax.Array | int = 0,
                   q_spec: P = P("dp", "tp", "sp", None),
                   kv_spec: P = P("dp", "tp", "sp", None)) -> jax.Array:
    """Causal GQA ring attention for a sequence-sharded *from-scratch*
    prefill.

    Blockwise ring attention (Liu & Abbeel's ring attention shape, built
    from the same flash softmax decomposition as the decode combine
    above): queries AND keys/values are sharded on the sequence axis over
    ``sp``; each of the sp steps computes local partials against the
    currently-held KV block, folds them into a running (max, denominator,
    numerator) accumulator, and rotates the KV block to the next shard
    with ``ppermute`` — XLA overlaps the rotation with the next block's
    compute on ICI; the last block is consumed without a rotation
    (sp−1 rotations total).  Blocks that are entirely in a query shard's
    future are skipped under ``lax.cond`` — they are fully causally
    masked, and skipping recovers the ~half of block-pair FLOPs a plain
    ring wastes.  Peak per-chip memory is O(T/sp), which is what lets a
    prompt longer than one chip's HBM prefill at all; the reference has
    no analogue (its seqLen is a hard per-node ceiling, commands.hpp:12).

    q: (B, Hq, T, Dh), k/v: (B, Hkv, T, Dh), all with T sharded on
    ``sp``.  ``pos0`` offsets the global RoPE-free position bookkeeping
    only; attention covers *only these q/k/v* — any cached KV prefix is
    NOT read, so callers continuing a sequence (pos0 > 0 with earlier
    cache content) must use :func:`sp_gqa_attention` instead (the engine
    gates the ring on ``pos == 0``).  Returns (B, Hq, T, Dh) sharded
    like q.
    """
    b, hq, t, dh = q.shape
    sp = mesh.shape.get("sp", 1)
    t_local = t // sp
    perm = [(i, (i + 1) % sp) for i in range(sp)]  # ring: shard i → i+1

    def shard_fn(q, k, v):
        hq_l, hkv_l = q.shape[1], k.shape[1]
        g = hq_l // hkv_l
        qf = q.astype(jnp.float32).reshape(q.shape[0], hkv_l, g, t_local, dh)
        my = jax.lax.axis_index("sp")
        q_start = pos0 + my * t_local

        def accumulate(i, out, lsum, m, kb, vb):
            # block held after i rotations originated at shard (my-i) mod sp
            owner = (my - i) % sp

            def fold(args):
                out, lsum, m = args
                o_i, l_i, m_i = _local_partials(
                    qf, kb, vb, q_start, t_local, pos0 + owner * t_local)
                m_new = jnp.maximum(m, m_i)
                s_old = jnp.exp(m - m_new)
                s_new = jnp.exp(m_i - m_new)
                return (out * s_old[..., None] + o_i * s_new[..., None],
                        lsum * s_old + l_i * s_new, m_new)

            # owner > my ⇔ every key in the block is a future position for
            # every query here ⇔ fully masked: skip the whole block
            return jax.lax.cond(owner <= my, fold, lambda a: a, (out, lsum, m))

        def step(i, carry):
            out, lsum, m, kb, vb = carry
            out, lsum, m = accumulate(i, out, lsum, m, kb, vb)
            kb = jax.lax.ppermute(kb, "sp", perm)
            vb = jax.lax.ppermute(vb, "sp", perm)
            return out, lsum, m, kb, vb

        # accumulators start as a fully-masked chunk's partials, marked
        # device-varying so the fori_loop carry type matches the body's
        o0, l0, m0 = _empty_partials((q.shape[0], hkv_l, g, t_local), dh)
        init = (o0, l0, m0, k, v)
        out, lsum, m, kb, vb = jax.lax.fori_loop(0, sp - 1, step, init)
        # final block: consume without the (discarded) sp-th rotation
        out, lsum, m = accumulate(sp - 1, out, lsum, m, kb, vb)
        out = out / jnp.maximum(lsum[..., None], 1e-38)
        return out.reshape(q.shape[0], hq_l, t_local, dh).astype(q.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec)(q, k, v)


def sp_gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, q_len: int, mesh,
                     q_spec: P = P("dp", "tp", None, None),
                     kv_spec: P = P("dp", "tp", "sp", None),
                     layer: jax.Array | None = None) -> jax.Array:
    """Causal GQA over a seq-sharded cache (drop-in for
    ops.attention.gqa_attention when the mesh has an ``sp`` axis).

    q: (B, Hq, T, Dh); k_cache/v_cache: (B, Hkv, S, Dh) with S sharded on
    ``sp``; returns (B, Hq, T, Dh) sharded like q.

    With ``layer`` the caches are the stacked (L, B, Hkv, S, Dh) buffers —
    ``kv_spec`` stays the per-layer 4-axis spec and the unsharded layer
    axis is prepended here — and the layer is sliced *inside* the shard
    body: slicing before the shard_map would materialize the full layer
    slab per layer-step, since shard_map is a fusion barrier (the same
    O(S) copy gqa_attention_at avoids on the single-chip path).
    """
    b, hq, t, dh = q.shape
    seq_ax = 2 if layer is None else 3
    hkv = k_cache.shape[seq_ax - 1]
    g = hq // hkv
    sp = mesh.shape.get("sp", 1)
    chunk = k_cache.shape[seq_ax] // sp
    if layer is not None:
        kv_spec = P(None, *kv_spec)

    def shard_fn(q, k, v):
        if layer is not None:
            k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        # local shapes: q (b/dp, hq/tp, T, Dh), k/v (b/dp, hkv/tp, C, Dh)
        hq_l = q.shape[1]
        hkv_l = k.shape[1]
        qf = q.astype(jnp.float32).reshape(q.shape[0], hkv_l, hq_l // hkv_l, t, dh)
        chunk_start = jax.lax.axis_index("sp") * chunk

        def compute(_):
            # decode over a long local chunk: walk only the blocks covering
            # this shard's live positions (O(live) per shard, not O(chunk))
            if q_len == 1 and _use_live_walk(g, q_len, chunk):
                return _local_partials_blocked(qf, k, v, pos, chunk_start)
            return _local_partials(qf, k, v, pos, q_len, chunk_start)

        def empty(_):
            return _empty_partials(qf.shape[:3] + (t,), dh)

        # a shard whose whole chunk is in the queries' future is fully
        # masked: skip its scores/einsums and its KV chunk read.  Step
        # latency is unchanged (every shard still meets the collective
        # below, paced by the shards that do compute) — the saving is the
        # idle shards' HBM reads and FLOPs, not wall clock.
        o_i, l_i, m_i = jax.lax.cond(
            chunk_start <= pos + q_len - 1, compute, empty, None)

        m = jnp.max(jax.lax.all_gather(m_i, "sp"), axis=0)   # global max
        scale = jnp.exp(m_i - m)
        out = jax.lax.psum(o_i * scale[..., None], "sp")
        denom = jax.lax.psum(l_i * scale, "sp")
        out = out / jnp.maximum(denom[..., None], 1e-38)
        return out.reshape(q.shape[0], hq_l, t, dh).astype(q.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )(q, k_cache, v_cache)
