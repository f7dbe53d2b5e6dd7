"""Packed-Q40 MoE experts (VERDICT r01 #3).

The reference keeps MoE expert weights Q40 end-to-end
(transformer.cpp:299-317); round 1 dequantized every expert to dense f32 on
host, making Mixtral-8x7B unloadable.  These tests cover the packed expert
path: quantized-vs-dense numerics, the decode expert-select path, `.m`
loading without f32 materialization, and N-shard ≡ 1-shard equivalence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params, load_params, quantize_matmuls
from dllama_tpu.models.transformer import forward, init_kv_cache
from dllama_tpu.ops import q40
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.sampling import Sampler
from fixtures import bf16_exact_scales


MOE_CFG = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2,
                      dim=64, hidden_dim=96, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=128, seq_len=64)


def _dequant_all(params):
    return {k: (q40.dequantize(v, jnp.float32) if isinstance(v, q40.QTensor) else v)
            for k, v in params.items()}


def test_quantize_matmuls_packs_experts():
    qparams = quantize_matmuls(init_params(MOE_CFG, seed=0), MOE_CFG)
    for k in ("up", "gate", "down"):
        assert isinstance(qparams[k], q40.QTensor), k
    assert qparams["up"].qpacked.shape == (2, 4, 32, 96)   # (L, E, n/2, F)
    assert qparams["down"].qpacked.shape == (2, 4, 48, 64)  # (L, E, F/2, D)
    assert isinstance(qparams["router"], jnp.ndarray)  # router stays dense


def test_quantized_moe_prefill_matches_dense_dequant():
    """Prefill (masked static expert loop) ≡ the dense einsum dispatch on
    the same dequantized values."""
    qparams = quantize_matmuls(init_params(MOE_CFG, seed=1), MOE_CFG)
    dparams = _dequant_all(qparams)
    tokens = jnp.asarray([[1, 9, 33, 7, 2]], jnp.int32)
    cfg_q = MOE_CFG.with_(quant_impl="xla")
    lq, _ = forward(qparams, cfg_q, tokens, init_kv_cache(MOE_CFG, 1), jnp.int32(0))
    ld, _ = forward(dparams, MOE_CFG, tokens, init_kv_cache(MOE_CFG, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=0, atol=5e-2 + 2e-2 * np.abs(np.asarray(ld)).max())


def test_quantized_moe_decode_matches_prefill():
    """The decode path (per-token expert select on packed planes) must
    agree with the prefill path (masked loop) — same model, positions fed
    one at a time vs all at once."""
    cfg = MOE_CFG.with_(quant_impl="xla")
    qparams = quantize_matmuls(init_params(cfg, seed=2), cfg)
    prompt = [3, 17, 29, 5]

    e_pre = Engine(cfg, qparams)
    l_pre, _ = e_pre.prefill(prompt)

    e_dec = Engine(cfg, qparams)
    for t in prompt[:-1]:
        e_dec.decode_one(t)
    l_dec, _ = e_dec.decode_one(prompt[-1])
    np.testing.assert_allclose(l_pre, l_dec,
                               rtol=0, atol=1e-3 + 1e-3 * np.abs(l_pre).max())


def test_mixtral_q40_mfile_end_to_end(tmp_path):
    """Q40 Mixtral .m → packed expert load (no dense f32) → generation."""
    from tests.fixtures import write_tiny_model

    path = tmp_path / "tiny-mixtral-q40.m"
    write_tiny_model(str(path), arch=mfile.ARCH_MIXTRAL, ftype=quants.Q40,
                     n_experts=4, vocab_size=64, seq_len=64)
    mf = mfile.MFile(str(path))

    cfg_q, qparams = load_params(mf, keep_quantized=True)
    for k in ("up", "gate", "down"):
        assert isinstance(qparams[k], q40.QTensor), k
    assert qparams["up"].qpacked.dtype == jnp.uint8

    cfg_d, dparams = load_params(mf, keep_quantized=False)
    eq = Engine(cfg_q.with_(quant_impl="xla"), qparams)
    ed = Engine(cfg_d, dparams)
    lq, _ = eq.prefill([1, 5, 9])
    ld, _ = ed.prefill([1, 5, 9])
    np.testing.assert_allclose(lq, ld, rtol=0, atol=5e-2 + 2e-2 * np.abs(ld).max())

    # generation runs on the packed path without error
    toks = [t for t, _ in eq.generate([1, 5, 9], steps=8,
                                      sampler=Sampler(cfg_q.vocab_size, 0.0, 0.9, 0))]
    assert len(toks) == 8


def test_ep_sharded_packed_experts_match_tp1():
    """Expert-PARALLEL packed experts (ep shards the expert axis of the
    (L, E, n/2, d) stacks in HBM — q40._sharded_matmul_ep): ep4×tp2 and
    ep2×tp2 must reproduce the 1-shard logits on both the fused interpret
    path and the XLA fallback, for prefill and decode.  This is the layout
    that lets packed Grok-1-314B fit its 16-chip plan (docs/MEMORY.md).

    The one shard decodes its row on the fused kernel's one-row body, which
    rounds no weight to bf16, the XLA engines round each (PR 50): the weights'
    scales are exact in bf16 times a nibble, so all of them hold the same
    weights and what is compared is the sharding, at the bound it always had."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2,
                      dim=256, hidden_dim=256, n_layers=2, n_heads=8,
                      n_kv_heads=8, vocab_size=128, seq_len=32,
                      ).with_(quant_impl="pallas_interpret")
    qparams = bf16_exact_scales(quantize_matmuls(init_params(cfg, seed=4), cfg))
    prompt = [1, 2, 3]
    e1 = Engine(cfg, qparams, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    l1, _ = e1.prefill(prompt)
    d1, _ = e1.decode_one(7)
    for impl in ("pallas_interpret", "xla"):
        for ep, tp in ((4, 2), (2, 2)):
            e = Engine(cfg.with_(quant_impl=impl), qparams,
                       mesh=make_mesh(tp=tp, ep=ep))
            le, _ = e.prefill(prompt)
            np.testing.assert_allclose(
                l1, le, rtol=0, atol=1e-3 + 1e-3 * np.abs(l1).max(),
                err_msg=f"prefill impl={impl} ep={ep} tp={tp}")
            de, _ = e.decode_one(7)
            np.testing.assert_allclose(
                d1, de, rtol=0, atol=1e-3 + 1e-3 * np.abs(d1).max(),
                err_msg=f"decode impl={impl} ep={ep} tp={tp}")


def test_moe_prefill_scan_matches_unroll(monkeypatch):
    """Past MOE_PREFILL_UNROLL_MAX experts the quantized prefill switches
    to a lax.scan with a traced expert index (VERDICT r04 Weak #3); it
    must produce the unrolled path's numbers exactly."""
    import dllama_tpu.models.transformer as tr
    cfg = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=16,
                      n_active_experts=2, dim=64, hidden_dim=96, n_layers=1,
                      n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=32,
                      ).with_(quant_impl="xla")
    qparams = quantize_matmuls(init_params(cfg, seed=5), cfg)
    tokens = jnp.asarray([[1, 9, 33, 7, 2]], jnp.int32)
    l_scan, _ = forward(qparams, cfg, tokens, init_kv_cache(cfg, 1), jnp.int32(0))
    monkeypatch.setattr(tr, "MOE_PREFILL_UNROLL_MAX", 64)  # force unroll
    l_unroll, _ = forward(qparams, cfg, tokens, init_kv_cache(cfg, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(l_scan), np.asarray(l_unroll),
                               rtol=0, atol=1e-5)


def test_moe_prefill_program_size_flat_in_experts():
    """Compile-scaling guard: the traced program for a 32-expert model must
    not be materially larger than for 16 experts (the scan bounds it; the
    old unroll grew linearly and would double the equation count)."""
    import dllama_tpu.models.transformer as tr

    def n_eqns(e):
        cfg = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=e,
                          n_active_experts=2, dim=64, hidden_dim=96,
                          n_layers=1, n_heads=4, n_kv_heads=2, vocab_size=128,
                          seq_len=32).with_(quant_impl="xla")
        qparams = quantize_matmuls(init_params(cfg, seed=5), cfg)
        tokens = jnp.asarray([[1, 9, 33, 7, 2]], jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, t: forward(p, cfg, t, init_kv_cache(cfg, 1),
                                 jnp.int32(0)))(qparams, tokens)
        return sum(1 for _ in jaxpr.jaxpr.eqns)

    assert n_eqns(32) <= n_eqns(16) + 8  # flat, not linear


def test_ep_non_owner_shards_skip_expert_reads():
    """Non-owner shards must perform NO packed-tile reads (VERDICT r04
    Weak #2): every expert EXCEPT the selected one carries NaN scale bits,
    so any shard that still streams its clamped local expert (the old
    masked-input variant: 0·NaN = NaN through the dot) poisons the psum.
    A finite, correct product proves only the owner's lax.cond branch ran
    the kernel."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    rng = np.random.RandomState(0)
    L, E, n, d = 1, 2, 64, 128
    w = (rng.randn(L, E, n, d) * 0.1).astype(np.float32)
    qt = q40.quantize(w)
    nan16 = np.uint16(0x7e00)  # f16 NaN bits
    scales = np.asarray(qt.scales).copy()
    scales[:, 1:] = nan16  # poison every expert but expert 0
    x = jnp.asarray(rng.randn(1, n).astype(np.float32), jnp.bfloat16)
    mesh = make_mesh(tp=1, ep=2, devices=jax.devices()[:2])
    out = q40._sharded_matmul_ep(
        x, jnp.asarray(qt.qpacked), jnp.asarray(scales),
        jnp.int32(0),  # layer 0 · E + expert 0 → owned by ep shard 0
        "row", mesh, interp=True)
    ref = x.astype(jnp.float32) @ q40.dequantize(
        q40.QTensor(qt.qpacked[0, 0], qt.scales[0, 0], qt.logical_nd),
        jnp.float32)
    assert np.isfinite(np.asarray(out)).all(), \
        "NaN product: a non-owner shard read its packed tiles"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-2 * float(np.abs(ref).max()))


@pytest.mark.parametrize("rows", [1, 3])
def test_the_chosen_launch_reads_the_chosen_experts_alone(rows):
    """The poison of the test above through ``moe_ffn``'s one-launch form
    (``select-chosen``): in all three stacks every expert that no row routes
    to carries NaN scale bits, which the kernel's f16 decode turns into
    weights of ~1e5.  The output is bit for bit what the clean stacks give,
    so the k planes a launch walks are the router's and no other."""
    from dllama_tpu.models.transformer import moe_ffn
    from dllama_tpu.obs import dispatch as obs_dispatch

    cfg = tiny_config(arch=mfile.ARCH_OLMOE, n_experts=16, n_active_experts=4,
                      n_layers=3).with_(quant_impl="pallas_interpret")
    p = quantize_matmuls(init_params(cfg, seed=6, scale=0.2), cfg)
    layer = 2
    x = np.random.RandomState(rows).randn(rows, cfg.dim).astype(np.float32)
    router = np.asarray(p["router"][layer], np.float32)
    routed = np.unique(np.argsort(-(x @ router), axis=-1)[:, :cfg.n_active_experts])
    assert 0 < len(routed) < cfg.n_experts

    def lp_of(poison):
        lp = {"router": jnp.asarray(router)}
        for key in ("up", "gate", "down"):
            scales = np.asarray(p[key].scales).copy()
            if poison:
                keep = scales[layer, routed].copy()
                scales[:] = np.uint16(0x7e00)  # f16 NaN bits, every layer
                scales[layer, routed] = keep
            lp[key] = q40.QLayerView(q40.QTensor(p[key].qpacked, jnp.asarray(scales),
                                                 p[key].logical_nd), jnp.int32(layer))
        return lp

    before = obs_dispatch.dispatches().get("moe/select-chosen", 0)
    clean = np.asarray(moe_ffn(jnp.asarray(x), lp_of(False), cfg))
    poisoned = np.asarray(moe_ffn(jnp.asarray(x), lp_of(True), cfg))
    assert obs_dispatch.dispatches()["moe/select-chosen"] == before + 2
    assert np.isfinite(poisoned).all() and np.abs(poisoned).max() < 1e3
    np.testing.assert_array_equal(poisoned, clean)


def test_tp8_quantized_moe_matches_tp1():
    """N-shard ≡ 1-shard with packed experts on the pallas-interpret
    shard_map path (shard-clean shapes)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2,
                      dim=256, hidden_dim=256, n_layers=2, n_heads=8,
                      n_kv_heads=8, vocab_size=128, seq_len=32,
                      ).with_(quant_impl="pallas_interpret")
    # a toy shard's reduction tile of 32 rows keeps the dot body (a weight
    # rounded to bf16) where the whole matrix's takes the sliced body at the
    # prompt's three rows (none rounded, PR 62): weights exact in bf16 compute
    # one function on both
    qparams = bf16_exact_scales(quantize_matmuls(init_params(cfg, seed=3), cfg))
    prompt = [1, 2, 3]
    e1 = Engine(cfg, qparams, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    e8 = Engine(cfg, qparams, mesh=make_mesh(tp=8))
    l1, _ = e1.prefill(prompt)
    l8, _ = e8.prefill(prompt)
    np.testing.assert_allclose(l1, l8, rtol=0, atol=1e-3 + 1e-3 * np.abs(l1).max())
