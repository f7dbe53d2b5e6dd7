"""The length-aware block walk (flash-style online softmax over the live
KV blocks) ≡ the one-shot path.

The one-shot path materializes the full (B, Hkv, G, T, S) f32 score tensor
— the long-context HBM wall (VERDICT r01 weak #5) — and reads the cache's
whole capacity; the walk folds only the blocks up to the last query's
position and must be numerically equivalent for every T."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.ops import attention
from dllama_tpu.ops.attention import (gqa_attention, live_gqa_attention,
                                      update_kv_cache_at)


def _setup(b=1, hq=4, hkv=2, s=256, t=8, dh=16, pos=64, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, hq, t, dh).astype(np.float32))
    k = jnp.asarray(rng.randn(b, hkv, s, dh).astype(np.float32))
    v = jnp.asarray(rng.randn(b, hkv, s, dh).astype(np.float32))
    return q, k, v, jnp.int32(pos)


@pytest.mark.parametrize("kw", [
    dict(),                         # mid-sequence, s=256
    dict(t=16, s=512, pos=0),       # from position zero
    dict(s=96, pos=10, t=4),        # s=96 falls through the divisor ladder
                                    # to a single 96-wide block
    dict(t=1, pos=100),             # one decode row on a short cache
], ids=["mid_sequence", "from_zero", "ragged_chunking", "decode_row"])
def test_walk_matches_oneshot_on_short_caches(kw):
    """Called directly the walk serves any cache, also the short ones
    gqa_attention keeps one-shot."""
    q, k, v, pos = _setup(**kw)
    ref = gqa_attention(q, k, v, pos, q.shape[2])
    out = live_gqa_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_long_prefill_4k_dispatches_walk():
    """A 4k-token prefill runs through gqa_attention's auto dispatch (the
    score tensor would be g·t·s = 2·4096·4096 = 32M > threshold) and
    matches the explicit one-shot computation on a spot block."""
    b, hq, hkv, dh, s = 1, 4, 2, 16, 4096
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, hq, s, dh).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, hkv, s, dh).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, hkv, s, dh).astype(np.float32) * 0.3)
    out = jax.jit(gqa_attention, static_argnums=(4,))(q, k, v, jnp.int32(0), s)
    assert out.shape == (b, hq, s, dh)
    assert np.all(np.isfinite(np.asarray(out)))
    # spot-check the first 32 queries against the one-shot path on a
    # truncated cache (those queries only see keys < 32... actually ≤ 31)
    ref = gqa_attention(q[:, :, :32], k[:, :, :128], v[:, :, :128], jnp.int32(0), 32)
    np.testing.assert_allclose(np.asarray(out[:, :, :32]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_update_then_attend_roundtrip():
    """update_kv_cache_at + attention sees exactly the written keys: the
    stacked-cache layer write lands in the right (layer, pos) window."""
    L, b, hkv, s, dh = 3, 1, 2, 64, 8
    kc = jnp.zeros((L, b, hkv, s, dh))
    vc = jnp.zeros((L, b, hkv, s, dh))
    rng = np.random.RandomState(2)
    kn = jnp.asarray(rng.randn(b, hkv, 4, dh).astype(np.float32))
    vn = jnp.asarray(rng.randn(b, hkv, 4, dh).astype(np.float32))
    kc, vc = update_kv_cache_at(kc, vc, kn, vn, jnp.int32(1), jnp.int32(0))
    # untouched layers stay zero; the written layer holds kn/vn at pos 0
    assert float(jnp.abs(kc[0]).sum()) == 0.0 and float(jnp.abs(kc[2]).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(kc[1, :, :, :4]), np.asarray(kn))
    q = jnp.asarray(rng.randn(b, 4, 4, dh).astype(np.float32))
    out1 = gqa_attention(q, kc[1], vc[1], jnp.int32(0), 4)
    out2 = live_gqa_attention(q, kc, vc, jnp.int32(0), layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_decode_blocked_matches_one_shot(monkeypatch):
    """The length-aware decode path (while_loop over live KV blocks) must
    equal full-cache one-shot attention at every position class."""
    r = np.random.RandomState(0)
    b, hq, hkv, s, dh = 1, 4, 2, 8192, 8
    q = jnp.asarray(r.randn(b, hq, 1, dh), jnp.float32)
    k = jnp.asarray(r.randn(b, hkv, s, dh), jnp.float32)
    v = jnp.asarray(r.randn(b, hkv, s, dh), jnp.float32)
    fn = jax.jit(live_gqa_attention)
    for pos in (0, 1, 1023, 1024, 5000, s - 1):
        got = fn(q, k, v, jnp.int32(pos))
        # the reference must be the genuine one-shot full-cache path, not a
        # re-dispatch into the blocked implementation
        monkeypatch.setattr(attention, "_WALK_MIN_S", 1 << 30)
        ref = attention.gqa_attention(q, k, v, jnp.int32(pos), 1)
        monkeypatch.undo()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_gqa_dispatches_walk_for_long_cache():
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(1, 4, 1, 8), jnp.float32)
    k = jnp.asarray(r.randn(1, 2, 4096, 8), jnp.float32)
    v = jnp.asarray(r.randn(1, 2, 4096, 8), jnp.float32)
    got = attention.gqa_attention(q, k, v, jnp.int32(77), 1)
    ref = live_gqa_attention(q, k, v, jnp.int32(77))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_stacked_decode_blocked_matches_per_layer():
    """gqa_attention_at over a long stacked cache (blocks sliced straight
    from the 5-D buffer — no layer-slab materialization) must equal the
    per-layer length-aware path on that layer's slice."""
    r = np.random.RandomState(3)
    L, b, hq, hkv, s, dh = 3, 1, 4, 2, 4096, 8
    q = jnp.asarray(r.randn(b, hq, 1, dh), jnp.float32)
    ck = jnp.asarray(r.randn(L, b, hkv, s, dh), jnp.float32)
    cv = jnp.asarray(r.randn(L, b, hkv, s, dh), jnp.float32)
    for layer in range(L):
        for pos in (0, 1023, 1024, s - 1):
            got = attention.gqa_attention_at(
                q, ck, cv, jnp.int32(layer), jnp.int32(pos), 1)
            ref = live_gqa_attention(
                q, ck[layer], cv[layer], jnp.int32(pos))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---- the walk for every T, against the one-shot reference -----------------

_S = 2048  # short enough that gqa_attention itself stays one-shot
_FORMS = ("plain", "layer", "int8", "ragged")


@functools.lru_cache(maxsize=None)
def _walk_case(t, form):
    """Inputs and the two jitted sides of one (t, form): ``pos`` is traced,
    so every position class reuses one compile."""
    r = np.random.RandomState(7 + t)
    b = 2 if form == "ragged" else 1
    hq, hkv, dh = 4, 2, 16
    q = jnp.asarray(r.randn(b, hq, t, dh), jnp.float32)
    k = jnp.asarray(r.randn(b, hkv, _S, dh), jnp.float32)
    v = jnp.asarray(r.randn(b, hkv, _S, dh), jnp.float32)
    ref = jax.jit(lambda pos, start: gqa_attention(q, k, v, pos, t,
                                                   start=start))
    if form == "layer":
        ck = jnp.stack([k * 0, k, k * 0 + 1])
        cv = jnp.stack([v * 0, v, v * 0 + 1])
        walk = jax.jit(lambda pos, start: live_gqa_attention(
            q, ck, cv, pos, layer=jnp.int32(1)))
    elif form == "int8":
        (kq, ks), (vq, vs) = attention.quantize_kv(k), attention.quantize_kv(v)
        kd, vd = attention.dequant_kv(kq, ks), attention.dequant_kv(vq, vs)
        ref = jax.jit(lambda pos, start: gqa_attention(q, kd, vd, pos, t))
        walk = jax.jit(lambda pos, start: live_gqa_attention(
            q, kq, vq, pos, scales=(ks, vs)))
    else:
        walk = jax.jit(lambda pos, start: live_gqa_attention(
            q, k, v, pos, start=start))
    return ref, walk


def _pos_classes(t):
    blk = attention._kv_chunk(_S)
    return {"zero": 0, "mid_block": blk // 2 + 3, "edge_minus_1": blk - 1,
            "edge": blk, "end": _S - t}


@pytest.mark.parametrize("form", _FORMS)
@pytest.mark.parametrize("where", ["zero", "mid_block", "edge_minus_1", "edge",
                                   "end"])
@pytest.mark.parametrize("t", [1, 4, 16, 64])
def test_walk_matches_oneshot(t, where, form):
    """The walk over T rows equals one-shot attention over the whole cache
    at every position class of its block, plain, sliced from the stacked
    cache at a layer, through the int8 scales, and under a ragged floor."""
    pos = _pos_classes(t)[where]
    assert not attention._use_live_walk(2, t, _S)  # the reference is one-shot
    ref, walk = _walk_case(t, form)
    # a floor at or below pos: every query row keeps a live key
    start = jnp.asarray([0, min(pos, 37)], jnp.int32) if form == "ragged" \
        else None
    got, want = walk(jnp.int32(pos), start), ref(jnp.int32(pos), start)
    # int8 dequantizes to bf16 dot operands: the probabilities round to
    # bf16 (eps 2^-8) against another running max on the two sides
    tol = 5e-3 if form == "int8" else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("t,pos", [(1, 0), (1, 1023), (1, 1024), (4, 1021),
                                   (16, 1009), (64, 0), (64, 961), (64, 1500),
                                   (64, _S - 64)])
def test_walk_trip_count_is_the_live_length(monkeypatch, t, pos):
    """The walk folds ``(pos + t - 1) // block + 1`` blocks — the live
    length, not the cache's capacity (counted, no clock)."""
    folds = []
    fold = attention._online_fold
    monkeypatch.setattr(attention, "_online_fold",
                        lambda *a: folds.append(1) or fold(*a))
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(1, 4, t, 8), jnp.float32)
    k = jnp.asarray(r.randn(1, 2, _S, 8), jnp.float32)
    with jax.disable_jit():
        live_gqa_attention(q, k, k, jnp.int32(pos))
    assert len(folds) == (pos + t - 1) // attention._kv_chunk(_S) + 1


@pytest.mark.parametrize("g,t,s,walk", [
    (4, 1, 32768, True), (4, 256, 32768, True), (4, 5, 4096, True),
    (4, 1, 2048, False), (4, 256, 2048, False), (4, 512, 2048, True),
    (4, 512, 1000, False),  # not blockable: one step over the whole cache
])
def test_dispatch_rule_and_ledger(g, t, s, walk):
    """One rule from shapes only; the call site records its family."""
    from dllama_tpu.obs import dispatch as obs_dispatch

    assert attention._use_live_walk(g, t, s) is walk
    sd = jax.ShapeDtypeStruct
    obs_dispatch.reset()
    try:
        jax.eval_shape(
            lambda q, ck: attention.gqa_attention_at(
                q, ck, ck, jnp.int32(0), jnp.int32(0), t),
            sd((1, 2 * g, t, 8), jnp.float32), sd((1, 1, 2, s, 8), jnp.float32))
        assert obs_dispatch.dispatches() == {
            "attn/live-walk" if walk else "attn/one-shot": 1}
    finally:
        obs_dispatch.reset()
