"""The all-experts launch of the Q40 kernel (``q40_mm_experts``) and its one
caller, ``moe_ffn``'s ``all-experts`` strategy.

CPU, ``pallas_interpret``.  The kernel's contract is bit equality with one
``q40_mm_stacked`` call an expert: the expert index moved from a traced loop
into the grid, the tile math did not move.  ``moe_ffn`` on the new path is
compared with ``quant_impl="xla"`` (the scan / unrolled loop) within the
tolerance test_moe_q40 uses for quantized-against-dense.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.io import mfile
from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params, quantize_matmuls
from dllama_tpu.models.transformer import forward, init_kv_cache
from dllama_tpu.obs import dispatch as obs_dispatch
from dllama_tpu.ops import q40
from dllama_tpu.parallel.mesh import active_mesh, make_mesh

LAYERS, LAYER = 3, 2  # the layer index read is > 0


def _stack(experts, n, d, seed):
    rng = np.random.default_rng(seed)
    qt = q40.quantize(rng.standard_normal((LAYERS, experts, n, d)).astype(np.float32))
    return qt, rng


SMALL, RAGGED_D, TWO_N_STEPS = (64, 96), (64, 1152), (2048, 1152)
# experts x rows (256: the row-blocked form) x both activation forms at one
# tile; a ragged last d tile (1024 + 128) at every expert count; two n steps
# (the accumulator carried across them) at 4 and 8 experts, where interpret
# mode takes seconds and not minutes
CASES = [(e, rows, per, SMALL) for e in (4, 8, 64) for rows in (5, 16, 256)
         for per in (False, True)]
CASES += [(e, 16, per, RAGGED_D) for e in (4, 8, 64) for per in (False, True)]
CASES += [(4, 256, False, TWO_N_STEPS), (4, 256, True, TWO_N_STEPS),
          (8, 5, False, TWO_N_STEPS), (8, 16, True, TWO_N_STEPS)]


@pytest.mark.parametrize("experts,rows,per_expert,nd", CASES, ids=lambda v: str(v))
def test_all_experts_launch_is_bit_equal_to_one_launch_an_expert(experts, rows,
                                                                 per_expert, nd):
    n, d = nd
    qt, rng = _stack(experts, n, d, seed=experts + rows)
    view = q40.QLayerView(qt, jnp.int32(LAYER))
    qp, sc = view.flat_planes()
    x = jnp.asarray(rng.standard_normal(((experts,) if per_expert else ()) + (rows, n)),
                    jnp.bfloat16)
    out = q40._pallas_matmul_experts(x, qp, sc, view.layer, experts=experts,
                                     interpret=True)
    assert out.shape == (experts, rows, d) and out.dtype == jnp.float32
    for e in range(experts):
        ref = q40._pallas_matmul_stacked(x[e] if per_expert else x, qp, sc,
                                         view.select(jnp.int32(e), experts).layer,
                                         interpret=True)
        np.testing.assert_array_equal(np.asarray(out[e]), np.asarray(ref), err_msg=str(e))


def test_ragged_last_row_block_is_masked():
    """300 rows in blocks of 128: the third block is ragged, like the d edge."""
    qt, rng = _stack(4, 512, 256, seed=7)
    qp, sc = q40.QLayerView(qt, jnp.int32(1)).flat_planes()
    x = jnp.asarray(rng.standard_normal((4, 300, 512)), jnp.bfloat16)
    out = q40._pallas_matmul_experts(x, qp, sc, jnp.int32(1), experts=4,
                                     interpret=True, row_block=128)
    ref = jnp.stack([q40._pallas_matmul_stacked(x[e], qp, sc, jnp.int32(4 + e),
                                                interpret=True, row_block=128)
                     for e in range(4)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_matmul_experts_pads_the_input_dim_and_records_its_site():
    """An input dim that the tile rule cannot cut into healthy tiles (2752 =
    86 blocks of 32: no divisor is a multiple of 256, and the whole axis
    leaves a 256-wide output tile) is padded at pack time (zero scales); the
    activation is padded to match, once a call."""
    n, d, experts = 2752, 128, 4
    qt, rng = _stack(experts, n, d, seed=3)
    assert qt.qpacked.shape[-2] * 2 == q40.padded_n(n) == 3072
    view = q40.QLayerView(qt, jnp.int32(LAYER))
    x = jnp.asarray(rng.standard_normal((6, n)), jnp.bfloat16)
    before = obs_dispatch.dispatches().get("q40/pallas-fused", 0)
    out = q40.matmul_experts(x, view, experts, "pallas_interpret", out_dtype=jnp.float32)
    assert obs_dispatch.dispatches()["q40/pallas-fused"] == before + 1
    for e in range(experts):
        ref = q40.matmul(x, view.select(jnp.int32(e), experts), impl="pallas_interpret",
                         out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(out[e]), np.asarray(ref))


# DeepSeek-V2's expert shapes at a few experts: gate / up (1280 x 768 tiles,
# four reduction steps) from the shared activation, down from one an expert at
# an unpadded 1536 (one step of 1536 x 640, eight output tiles)
@pytest.mark.parametrize("rows", [5, 16])
@pytest.mark.parametrize("n,d,per_expert,tiles", [
    (5120, 1536, False, (1280, 768)), (1536, 5120, True, (1536, 640))],
    ids=["gate", "down"])
def test_all_experts_launch_matches_xla_at_the_rules_new_tiles(n, d, per_expert,
                                                                tiles, rows):
    experts = 3
    rng = np.random.default_rng(n + rows)
    qt = q40.quantize(rng.standard_normal((2, experts, n, d)).astype(np.float32) * 0.1)
    assert qt.qpacked.shape[-2] * 2 == n == q40.padded_n(n)
    assert q40._tiles(n, d) == tiles
    view = q40.QLayerView(qt, jnp.int32(1))
    x = jnp.asarray(rng.standard_normal(((experts,) if per_expert else ()) + (rows, n)),
                    jnp.bfloat16)
    out = np.asarray(q40.matmul_experts(x, view, experts, "pallas_interpret",
                                        out_dtype=jnp.float32))
    for e in range(experts):
        ref = np.asarray(q40.matmul(x[e] if per_expert else x,
                                    view.select(jnp.int32(e), experts), impl="xla",
                                    out_dtype=jnp.float32))
        np.testing.assert_allclose(out[e], ref, rtol=0, atol=1e-4 * np.abs(ref).max())


def _views(experts=4, n=64, d=96):
    qt, _ = _stack(experts, n, d, seed=1)
    return [q40.QLayerView(qt, jnp.int32(0))] * 3


@pytest.mark.parametrize("impl,rows,want", [
    ("pallas_interpret", 16, "pallas_interpret"), ("pallas", 256, "pallas"),
    ("xla", 16, None), ("auto", 16, None)],  # auto off the TPU is the XLA path
    ids=["interpret", "pallas", "xla", "auto-on-cpu"])
def test_the_static_rule_that_takes_the_all_experts_path(impl, rows, want):
    assert q40.all_experts_impl(_views(), rows, impl) == want


def test_a_mesh_and_q80_experts_keep_the_loop_over_experts():
    from dllama_tpu.ops import q8

    views = _views()
    with active_mesh(make_mesh(tp=2)):
        assert q40.all_experts_impl(views, 16, "pallas_interpret") is None
    qt = views[0].qt
    q8v = q40.QLayerView(q8.Q8Tensor(qt.qpacked, qt.scales, qt.logical_nd), jnp.int32(0))
    assert q40.all_experts_impl([views[0], q8v, views[0]], 16, "pallas_interpret") is None
    with pytest.raises(ValueError):
        q40.all_experts_impl(views, 16, "mosaic")


MOE = {
    # OLMoE: unnormalised top-k, q/k norm; past MOE_PREFILL_UNROLL_MAX the XLA form is the scan
    "olmoe": (dict(arch=mfile.ARCH_OLMOE, n_experts=16, n_active_experts=4), "scan"),
    # Mixtral: renormalised top-k; the XLA form is the static unroll
    "mixtral": (dict(arch=mfile.ARCH_MIXTRAL, n_experts=8, n_active_experts=2), "unrolled"),
    "grok1": (dict(arch=mfile.ARCH_GROK1, n_experts=4, n_active_experts=2,
                   hidden_act=mfile.ACT_GELU), "unrolled"),
}


@pytest.mark.parametrize("rows", [5, 16])
@pytest.mark.parametrize("name", sorted(MOE))
def test_moe_ffn_all_experts_matches_the_xla_strategies(name, rows):
    kw, xla_path = MOE[name]
    cfg = tiny_config(dim=64, hidden_dim=96, n_layers=2, n_heads=4, n_kv_heads=2,
                      vocab_size=128, seq_len=64, **kw)
    params = quantize_matmuls(init_params(cfg, seed=1), cfg)
    tokens = jnp.asarray(np.random.default_rng(rows).integers(1, 128, (1, rows)), jnp.int32)
    logits, sites = {}, {}
    for impl in ("xla", "pallas_interpret"):
        before = obs_dispatch.dispatches()
        out, _ = forward(params, cfg.with_(quant_impl=impl), tokens,
                         init_kv_cache(cfg, 1), jnp.int32(0))
        after = obs_dispatch.dispatches()
        logits[impl] = np.asarray(out)
        sites[impl] = {k for k in after if k.startswith("moe/")
                       and after[k] > before.get(k, 0)}
    assert sites == {"xla": {"moe/" + xla_path}, "pallas_interpret": {"moe/all-experts"}}
    ref = logits["xla"]
    np.testing.assert_allclose(logits["pallas_interpret"], ref, rtol=0,
                               atol=5e-2 + 2e-2 * np.abs(ref).max())
    assert np.abs(logits["pallas_interpret"] - ref).max() < 1e-3 * np.abs(ref).max()


def test_four_rows_still_select_and_the_experts_unread_stay_unread():
    """Up to 4 rows ``select`` runs the k chosen experts only, whatever the
    kernel path: the all-experts launch starts at 5 rows."""
    cfg = tiny_config(arch=mfile.ARCH_OLMOE, n_experts=16, n_active_experts=4,
                      n_layers=1).with_(quant_impl="pallas_interpret")
    params = quantize_matmuls(init_params(cfg, seed=2), cfg)
    before = obs_dispatch.dispatches()
    forward(params, cfg, jnp.zeros((1, 4), jnp.int32), init_kv_cache(cfg, 1), jnp.int32(0))
    after = obs_dispatch.dispatches()
    assert after.get("moe/select", 0) == before.get("moe/select", 0) + 1
    assert after.get("moe/all-experts", 0) == before.get("moe/all-experts", 0)


# ---- the dense cells' kernel programs did not move ------------------------

# sha256 of str(jax.make_jaxpr(...)) at Mistral-7B's fused gate+up weight
# (4096 -> 2 x 14336, 32 layers) on the parent of the PR that added the
# expert axis to _mm_call: operand lists, grids, block shapes, compiler
# parameters and kernel bodies of the two older entry points are in that text
# (PERF.md §6, PR 28: one more operand cost the dense cells 1.3-1.8%).
PARENT_KERNEL_JAXPRS = {
    (False, 1): "18d88f23b5d364c9", (False, 16): "ccb1fcd718310986",
    (False, 256): "14ade32625b455dc", (True, 1): "46beebf4eda2d534",
    (True, 16): "84fc4ea7ac6207fc", (True, 256): "021db3e6d99976f8",
}


@pytest.mark.parametrize("stacked,rows", sorted(PARENT_KERNEL_JAXPRS), ids=lambda v: str(v))
def test_flat_and_stacked_kernel_programs_are_the_parents(stacked, rows):
    n, d, layers = 4096, 2 * 14336, 32
    s = jax.ShapeDtypeStruct
    lead = (layers,) if stacked else ()
    args = [s((rows, n), jnp.bfloat16), s((*lead, n // 2, d), jnp.uint8),
            s((*lead, n // 32, d), jnp.uint16)]
    if stacked:
        jaxpr = jax.make_jaxpr(q40._pallas_matmul_stacked)(*args, s((), jnp.int32))
    else:
        jaxpr = jax.make_jaxpr(q40._pallas_matmul)(*args)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        PARENT_KERNEL_JAXPRS[(stacked, rows)]
