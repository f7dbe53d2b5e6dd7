"""The experts launches of the Q40 kernel, all of a layer (``q40_mm_experts``)
and a row's chosen ones (``q40_mm_chosen``), and their one caller, ``moe_ffn``'s
``all-experts`` and ``select-chosen`` strategies.

CPU, ``pallas_interpret``.  The kernel's contract is bit equality with one
``q40_mm_stacked`` call an expert: the expert index moved from a traced loop
into the grid, the tile math did not move.  ``moe_ffn`` on the new paths is
compared with ``quant_impl="xla"`` (the scan / unrolled loop) within the
tolerance test_moe_q40 uses for quantized-against-dense, and with a float32
loop over the chosen experts at the weights the packed tensors hold.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from fixtures import bf16_exact_scales
from dllama_tpu.io import mfile
from dllama_tpu.models.config import tiny_config, tiny_deepseek2, tiny_smallthinker
from dllama_tpu.models.params import init_params, quantize_matmuls
from dllama_tpu.models.transformer import forward, init_kv_cache, moe_ffn
from dllama_tpu.obs import dispatch as obs_dispatch
from dllama_tpu.ops import q8, q40
from dllama_tpu.parallel.mesh import active_mesh, make_mesh
from dllama_tpu.runtime.engine import Engine

LAYERS, LAYER = 3, 2  # the layer index read is > 0


def _stack(experts, n, d, seed):
    rng = np.random.default_rng(seed)
    qt = q40.quantize(rng.standard_normal((LAYERS, experts, n, d)).astype(np.float32))
    return qt, rng


def _exact(rows, n, d) -> bool:
    """Does a block of ``rows`` rows against an ``(n, d)`` matrix at the rule's
    tiles round no weight (the grouped and the sliced body: the float32
    reference within 5e-6) or each one to bf16 (the dot body: the XLA path
    within 1e-4)?"""
    return q40._body(rows, q40._tiles(q40.padded_n(n), d)[0]) != "dot"


def _assert_same_launch(out, ref, rows, n, d, err_msg=""):
    """Two launches of one kernel body over the same plane: bit equal, but at
    the sliced body's rows, where the interpreter's CPU program contracts the
    partials' multiply and add into one fma or not by what surrounds them (an
    ulp of a sum; on the chip both are the same Mosaic body)."""
    out, ref = np.asarray(out), np.asarray(ref)
    if q40._body(rows, q40._tiles(q40.padded_n(n), d)[0]) == "sliced":
        np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6 * np.abs(ref).max(),
                                   err_msg=err_msg)
    else:
        np.testing.assert_array_equal(out, ref, err_msg=err_msg)


def _f32_reference(x, w):
    return np.asarray(x, np.float64) @ np.asarray(q40.dequantize(w.sliced()), np.float64)


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
        _assert_same_launch(out[e], ref, rows, n, d, err_msg=str(e))


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
    assert _exact(rows, n, d)  # the sliced body (PR 62): the float32 reference
    for e in range(experts):
        ref = _f32_reference(x[e] if per_expert else x,
                             view.select(jnp.int32(e), experts))
        np.testing.assert_allclose(out[e], ref, rtol=0, atol=5e-6 * np.abs(ref).max())


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


def _moe_sites(before):
    after = obs_dispatch.dispatches()
    return {k for k in after if k.startswith("moe/") and after[k] > before.get(k, 0)}


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
        logits[impl] = np.asarray(out)
        sites[impl] = _moe_sites(before)
    assert sites == {"xla": {"moe/" + xla_path}, "pallas_interpret": {"moe/all-experts"}}
    ref = logits["xla"]
    np.testing.assert_allclose(logits["pallas_interpret"], ref, rtol=0,
                               atol=5e-2 + 2e-2 * np.abs(ref).max())
    assert np.abs(logits["pallas_interpret"] - ref).max() < 1e-3 * np.abs(ref).max()


def test_four_rows_still_choose_and_the_all_experts_launch_starts_at_five():
    """Up to 4 rows the k chosen experts alone are run (on the kernel path,
    one launch a matmul a row: ``select-chosen``); more rows read them all."""
    cfg = tiny_config(arch=mfile.ARCH_OLMOE, n_experts=16, n_active_experts=4,
                      n_layers=1).with_(quant_impl="pallas_interpret")
    params = quantize_matmuls(init_params(cfg, seed=2), cfg)
    for rows, path in ((4, "select-chosen"), (5, "all-experts")):
        before = obs_dispatch.dispatches()
        forward(params, cfg, jnp.zeros((1, rows), jnp.int32), init_kv_cache(cfg, 1),
                jnp.int32(0))
        assert _moe_sites(before) == {"moe/" + path}


# ---- the chosen launch (q40_mm_chosen): a row's k routed experts ----------

EXPERTS = 8
# a repeated index, and at the last layer the last plane of the flat stack
CHOSEN = (EXPERTS - 1, 0, EXPERTS - 1, 3)


@pytest.mark.parametrize("per_expert", [False, True], ids=["shared-x", "x-an-expert"])
@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("nd", [SMALL, RAGGED_D, TWO_N_STEPS], ids=str)
def test_chosen_launch_equals_one_launch_an_expert_and_the_xla_reference(
        nd, rows, per_expert):
    n, d = nd
    qt, rng = _stack(EXPERTS, n, d, seed=n + rows)
    view = q40.QLayerView(qt, jnp.int32(LAYERS - 1))
    qp, sc = view.flat_planes()
    x = jnp.asarray(rng.standard_normal(
        ((len(CHOSEN),) if per_expert else ()) + (rows, n)), jnp.bfloat16)
    out = q40._pallas_matmul_experts(x, qp, sc, view.layer, experts=EXPERTS,
                                     interpret=True, chosen=jnp.asarray(CHOSEN))
    assert out.shape == (len(CHOSEN), rows, d) and out.dtype == jnp.float32
    for j, e in enumerate(CHOSEN):
        xe, one = x[j] if per_expert else x, view.select(jnp.int32(e), EXPERTS)
        ref_k = q40._pallas_matmul_stacked(xe, qp, sc, one.layer, interpret=True)
        _assert_same_launch(out[j], ref_k, rows, n, d, err_msg=str(j))
        # few rows are contracted a quantization block at a time (PRs 50, 62):
        # no weight rounded to bf16, so the float32 reference; a toy's tile of
        # 64 rows keeps the dot above one row
        if _exact(rows, n, d):
            ref_x, tol = _f32_reference(xe, one), 5e-6
        else:
            ref_x, tol = np.asarray(q40.matmul(
                xe, one, impl="xla", out_dtype=jnp.float32)), 1e-4
        np.testing.assert_allclose(np.asarray(out[j]), ref_x, rtol=0,
                                   atol=tol * np.abs(ref_x).max())
    assert int(view.select(jnp.int32(CHOSEN[0]), EXPERTS).layer) == qp.shape[0] - 1


@pytest.mark.parametrize("per_expert", [False, True], ids=["shared-x", "x-an-expert"])
def test_chosen_launch_takes_traced_indices_inside_a_scan_over_layers(per_expert):
    """The model's shape of the call: the layer is the scan's counter, the
    chosen indices come out of a top-k of traced values."""
    n, d, k = 64, 96, 3
    qt, rng = _stack(EXPERTS, n, d, seed=5)
    x = jnp.asarray(rng.standard_normal(((k,) if per_expert else ()) + (1, n)),
                    jnp.bfloat16)
    scores = jnp.asarray(rng.standard_normal((LAYERS, EXPERTS)), jnp.float32)

    def body(_, xs):
        layer, row = xs
        idx = jax.lax.top_k(row, k)[1]
        return None, (idx, q40.matmul_experts(
            x, q40.QLayerView(qt, layer), EXPERTS, "pallas_interpret",
            out_dtype=jnp.float32, chosen=idx))

    _, (idx, out) = jax.jit(lambda sc: jax.lax.scan(
        body, None, (jnp.arange(LAYERS, dtype=jnp.int32), sc)))(scores)
    assert out.shape == (LAYERS, k, 1, d)
    np.testing.assert_array_equal(np.asarray(idx), np.argsort(-np.asarray(scores))[:, :k])
    for layer in range(LAYERS):
        for j in range(k):
            one = q40.QLayerView(qt, jnp.int32(layer)).select(idx[layer, j], EXPERTS)
            want = q40.matmul(x[j] if per_expert else x, one, impl="pallas_interpret",
                              out_dtype=jnp.float32)
            np.testing.assert_array_equal(np.asarray(out[layer, j]), np.asarray(want))


def test_chosen_launch_pads_the_input_dim_and_records_its_site(caplog, monkeypatch):
    import logging
    monkeypatch.setattr(logging.getLogger("dllama"), "propagate", True)
    n, d = 2752, 128  # stored as 3072 rows: _pad_x pads the activation
    qt, rng = _stack(EXPERTS, n, d, seed=3)
    view = q40.QLayerView(qt, jnp.int32(LAYER))
    x = jnp.asarray(rng.standard_normal((1, n)), jnp.bfloat16)
    with caplog.at_level(logging.DEBUG, logger="dllama"):
        out = q40.matmul_experts(x, view, EXPERTS, "pallas_interpret",
                                 out_dtype=jnp.float32, chosen=jnp.asarray(CHOSEN))
    site = [r for r in caplog.records if getattr(r, "path", "") == "pallas-fused"][-1]
    assert (site.rows, site.experts, site.stored_n) == (1, len(CHOSEN), 3072)
    for j, e in enumerate(CHOSEN):
        want = q40.matmul(x, view.select(jnp.int32(e), EXPERTS), impl="pallas_interpret",
                          out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(out[j]), np.asarray(want))


def test_chosen_launch_is_named_for_the_trace():
    view = q40.QLayerView(_stack(EXPERTS, 64, 96, seed=1)[0], jnp.int32(0))
    x = jnp.zeros((1, 64), jnp.bfloat16)
    for name, other, chosen in (("q40_mm_chosen", "q40_mm_experts", jnp.asarray(CHOSEN)),
                                ("q40_mm_experts", "q40_mm_chosen", None)):
        text = str(jax.make_jaxpr(lambda c: q40.matmul_experts(
            x, view, EXPERTS, "pallas_interpret", chosen=c))(chosen))
        assert name in text and other not in text


# ---- moe_ffn at 1, 2 and 4 rows on the chosen launch ----------------------

TOYS = {
    # unnormalised top-k of a softmax over all
    "olmoe": lambda: tiny_config(arch=mfile.ARCH_OLMOE, n_experts=16,
                                 n_active_experts=4, n_layers=1),
    # renormalised top-2
    "mixtral": lambda: tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=8,
                                   n_active_experts=2, n_layers=1),
    # 3 of 8 groups, top-6, scaled by 16, two shared experts
    "deepseek2": lambda: tiny_deepseek2(n_layers=1, n_dense_layers=0),
    # ReLU, 6 of 64 renormalised, the router's logits handed in
    "smallthinker": lambda: tiny_smallthinker(n_layers=4),
}
ACTS = {mfile.ACT_GELU: ref.gelu_tanh, mfile.ACT_SILU: ref.silu,
        mfile.ACT_RELU: ref.relu}
MOE_KEYS = ("router", "up", "gate", "down", "shared_w1", "shared_w2", "shared_w3")


def _toy_layer(name, codec=q40):
    """Layer 0 of a toy's expert FFN: the packed views ``moe_ffn`` takes and,
    for the reference, the float32 weights those tensors hold.  The Q40
    tensors' scales are exact in bf16 times a nibble
    (:func:`fixtures.bf16_exact_scales`), so the chosen launch's one-row body,
    which rounds no weight, and the XLA path, which rounds each, hold the same
    weights."""
    cfg = TOYS[name]()
    p = init_params(cfg, seed=7, scale=0.2)
    lp_np = {k: np.asarray(p[k][0], np.float32) for k in MOE_KEYS if k in p}
    lp = {"router": jnp.asarray(lp_np["router"])}
    for k in ("up", "gate", "down"):
        qt = bf16_exact_scales(codec.quantize(np.asarray(p[k], np.float32)))
        lp[k] = q40.QLayerView(qt, jnp.int32(0))
        lp_np[k] = np.asarray(codec.dequantize(qt, jnp.float32))[0]
    if "shared_w2" in lp_np:
        qp = bf16_exact_scales(quantize_matmuls(p, cfg))
        for k in ("shared_w13", "shared_w2"):
            lp[k] = q40.QLayerView(qp[k], jnp.int32(0))
        w13 = np.asarray(q40.dequantize(qp["shared_w13"]))[0]
        lp_np["shared_w1"], lp_np["shared_w3"] = np.split(w13, 2, axis=-1)
        lp_np["shared_w2"] = np.asarray(q40.dequantize(qp["shared_w2"]))[0]
    return cfg, lp, lp_np


def _moe_reference(x, logits, lp, cfg):
    """A float32 loop over each row's chosen experts."""
    act, k = ACTS[cfg.hidden_act], cfg.n_active_experts
    probs = ref.softmax(logits.astype(np.float64))
    out = np.zeros_like(x)
    for i in range(len(x)):
        if cfg.n_groups > 1:
            idx, w = ref.grouped_choice(probs[i], cfg.n_groups, cfg.topk_groups, k)
        else:
            idx = np.argsort(-probs[i], kind="stable")[:k]
            w = probs[i, idx]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for wj, e in zip(w * cfg.routed_scale, idx):
            h = act(x[i] @ lp["gate"][e]) * (x[i] @ lp["up"][e])
            out[i] += wj * (h @ lp["down"][e])
    if "shared_w2" in lp:
        out += (act(x @ lp["shared_w1"]) * (x @ lp["shared_w3"])) @ lp["shared_w2"]
    return out


def _rows_and_logits(name, cfg, lp_np, rows):
    rng = np.random.RandomState(rows)
    x = rng.randn(rows, cfg.dim).astype(np.float32)
    if name != "smallthinker":
        return x, None, x @ lp_np["router"]
    # its router reads the layer's input, not the FFN's: other rows
    logits = rng.randn(rows, cfg.dim).astype(np.float32) @ lp_np["router"]
    return x, jnp.asarray(logits), logits


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("name", sorted(TOYS))
def test_moe_ffn_on_the_chosen_launch_matches_the_float32_reference(name, rows):
    """Compared at the weights the Q40 tensors hold, so what is left is the
    bf16 rounding of activations inside the Q40 matmul: held to 3% of the
    output's spread, as tests/test_deepseek_v2.py holds every strategy; the
    loop of one launch an expert (``quant_impl="xla"``: ``select``) agrees to
    a tenth of that."""
    cfg, lp, lp_np = _toy_layer(name)
    x, handed, logits = _rows_and_logits(name, cfg, lp_np, rows)
    wanted = _moe_reference(x, logits, lp_np, cfg)
    got = {}
    for impl, path in (("pallas_interpret", "select-chosen"), ("xla", "select")):
        before = obs_dispatch.dispatches()
        got[impl] = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg.with_(quant_impl=impl),
                                       handed))
        assert _moe_sites(before) == {"moe/" + path}
    tol = 0.03 * wanted.std()
    assert np.abs(got["pallas_interpret"] - wanted).max() < tol
    assert np.abs(got["pallas_interpret"] - got["xla"]).max() < 0.1 * tol
    wrong = _moe_reference(x, np.roll(logits, 1, axis=-1), lp_np, cfg)
    assert np.abs(wrong - wanted).max() > 10 * tol  # other experts would be seen


def test_q80_experts_keep_the_loop_and_the_ledger_says_select():
    cfg, lp, lp_np = _toy_layer("olmoe", codec=q8)
    x, _, logits = _rows_and_logits("olmoe", cfg, lp_np, 2)
    before = obs_dispatch.dispatches()
    got = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg.with_(quant_impl="pallas_interpret")))
    assert _moe_sites(before) == {"moe/select"}
    wanted = _moe_reference(x, logits, lp_np, cfg)
    assert np.abs(got - wanted).max() < 0.03 * wanted.std()


def test_a_mesh_keeps_the_loop_and_the_ledger_says_select():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2,
                      dim=256, hidden_dim=256, n_layers=1, n_heads=8, n_kv_heads=8,
                      vocab_size=128, seq_len=32).with_(quant_impl="pallas_interpret")
    qparams = quantize_matmuls(init_params(cfg, seed=4), cfg)
    logits, sites = {}, {}
    for tp in (1, 2):
        before = obs_dispatch.dispatches()
        engine = Engine(cfg, qparams, mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]))
        logits[tp], _ = engine.decode_one(7)
        sites[tp] = _moe_sites(before)
    assert sites == {1: {"moe/select-chosen"}, 2: {"moe/select"}}
    np.testing.assert_allclose(logits[1], logits[2], rtol=0,
                               atol=1e-3 + 1e-3 * np.abs(logits[1]).max())


# sha256 of str(jax.make_jaxpr(...)) of the all-experts launch as PR 41 left
# it (one activation operand, the body's one dot): (n, d, experts, layers, an
# activation block an expert, rows) of OLMoE's, DeepSeek-V2's and
# SmallThinker's gate and down at their cells' rows.  PR 62 moved the four
# 16-row programs on purpose (the sliced body: every served pure-decode step
# compiles anew once); at 256 and 512 rows the hashes are PR 41's
PARENT_EXPERTS_JAXPRS = {
    (2048, 1024, 64, 16, False, 16): "595cedda1000454f",
    (1024, 2048, 64, 16, True, 16): "e91b318000b85276",
    (2048, 1024, 64, 16, False, 256): "598580865085514f",
    (1024, 2048, 64, 16, True, 256): "914674feab236910",
    (5120, 1536, 160, 4, False, 16): "b22c32c13f160e3e",
    (1536, 5120, 160, 4, True, 16): "6b9a5c5ae5b03c1f",
    (2560, 768, 64, 52, False, 512): "f20f86ec89de5502",
    (768, 2560, 64, 52, True, 512): "d33aeb5b92a78cc8",
}


@pytest.mark.parametrize("case", sorted(PARENT_EXPERTS_JAXPRS), ids=str)
def test_all_experts_kernel_programs_are_the_parents(case):
    """Grid, index maps, operands and body of ``q40_mm_experts`` as the three
    cells that run it had them: the chosen form is a keyword beside it."""
    n, d, experts, layers, per_expert, rows = case
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda x, qp, sc, layer: q40._pallas_matmul_experts(
        x, qp, sc, layer, experts=experts))(
        s(((experts,) if per_expert else ()) + (rows, n), jnp.bfloat16),
        s((layers * experts, n // 2, d), jnp.uint8),
        s((layers * experts, n // 32, d), jnp.uint16), s((), jnp.int32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        PARENT_EXPERTS_JAXPRS[case]


# ---- the dense cells' kernel programs did not move ------------------------

# sha256 of str(jax.make_jaxpr(...)) at Mistral-7B's fused gate+up weight
# (4096 -> 2 x 14336, 32 layers) as PR 41 left them: operand lists, grids,
# block shapes, compiler parameters and kernel bodies of the two older entry
# points are in that text (PERF.md §6, PR 28: one more operand cost the dense
# cells 1.3-1.8%; PR 41 took one away).  A PR that moves a hash on purpose
# re-pins it and says what every cell's programs paid.  PR 50 moved the two
# one-row programs on purpose (the raw nibbles contracted a quantization block
# at a time: every one-stream decode program compiles anew once), and PR 58
# again (the packed tile becomes the dot's operand as 32-bit words: 770a7b64 ->
# 347b3509, 8fd90d1e -> 7d725ac7), and PR 62 the two 16-row programs (the
# sliced body: 05cb10f1 -> 364cc7cc, 0b6ec8b3 -> 8aeb6963; the one-row pins did
# not move); at 256 rows the hashes are PR 41's.
PARENT_KERNEL_JAXPRS = {
    (False, 1): "347b35095f1be2aa", (False, 16): "364cc7cca1abe3ff",
    (False, 256): "3967bc32ae344097", (True, 1): "7d725ac7dbb00725",
    (True, 16): "8aeb6963852edd67", (True, 256): "0dce88ed8621a5ef",
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
