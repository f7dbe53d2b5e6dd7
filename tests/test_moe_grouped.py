"""``moe_ffn``'s strategy ``grouped`` (PR 53): above 16 rows the (row, expert)
pairs are sorted by expert into blocks of rows and the experts are three
launches of ``q40_mm_grouped`` over the blocks that hold rows.

The plan's arithmetic against a numpy walk over the same pairs; the launch
against one launch an expert; ``moe_ffn`` on the path against ``all-experts``
(the path every such call took) and against the float32 loops of
``reference_impl`` for every router the configurations have; the rule (16 rows
and fewer trace to the parent's program, more record ``moe/grouped``); and how
full the blocks were, from the engine's prefill call to the counter.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu.io import mfile
from dllama_tpu.models import grouping
from dllama_tpu.models.config import (tiny_config, tiny_deepseek2, tiny_exaone_moe,
                                      tiny_lfm2_moe)
from dllama_tpu.models.params import init_params
from dllama_tpu.models.transformer import moe_ffn
from dllama_tpu.obs import dispatch as obs_dispatch
from dllama_tpu.obs import metrics as obs_metrics
from dllama_tpu.ops import q40

# ---- the plan -----------------------------------------------------------------

# (rows, k, held, tr, how the pairs are drawn)
PLANS = {
    "even": (40, 4, 16, 16, "random"),
    "ragged": (37, 3, 8, 16, "random"),           # rows * k not a multiple of tr
    "one-expert": (48, 1, 8, 16, "all-to-5"),     # one run of three blocks
    "nobody-chose-some": (24, 2, 16, 16, "low-half"),
    "repeats": (20, 4, 4, 32, "repeat"),          # a row's pairs on one plane
    "share": (32, 8, 4, 16, "share"),             # most pairs held elsewhere
    "none-here": (20, 2, 4, 16, "none"),          # used = 0
}


def _pairs(rows, k, held, how):
    rng = np.random.default_rng(rows * 7 + k)
    keep = None
    if how == "random":
        idx = np.stack([rng.permutation(held)[:k] for _ in range(rows)])
    elif how == "all-to-5":
        idx = np.full((rows, k), 5)
    elif how == "low-half":
        idx = np.stack([rng.permutation(held // 2)[:k] for _ in range(rows)])
    elif how == "repeat":
        idx = np.repeat(rng.integers(0, held, (rows, 1)), k, axis=1)
    else:
        wide = np.stack([rng.permutation(held * 8)[:k] for _ in range(rows)])
        keep = (wide < held) & (how == "share")
        idx = np.where(keep, wide, 0)
    return idx.astype(np.int32), keep


@pytest.mark.parametrize("case", sorted(PLANS))
def test_plan_puts_every_pair_in_a_block_of_its_expert(case):
    rows, k, held, tr, how = PLANS[case]
    idx, keep = _pairs(rows, k, held, how)
    gp = jax.jit(lambda i, kp: grouping.plan(i, held, tr, kp))(
        jnp.asarray(idx), None if keep is None else jnp.asarray(keep))
    planes, used, gather, slot = (np.asarray(v) for v in gp)
    m = grouping.blocks(rows, k, held, tr)
    assert planes.shape == (m,) and gather.shape == (m * tr,) and slot.shape == (rows, k)
    here = np.ones_like(idx, bool) if keep is None else keep
    counts = np.bincount(idx[here], minlength=held)
    assert used == sum(-(-c // tr) for c in counts) <= m
    # blocks run through the experts in order, an expert nobody chose has none
    assert list(planes[:used]) == [e for e in range(held) for _ in range(-(-counts[e] // tr))]
    # the blocks past them stand on one held plane: the last one read
    assert (planes[used:] == (planes[used - 1] if used else planes[-1])).all()
    assert ((0 <= planes) & (planes < held)).all()
    # a kept pair's slot lies in a block of its expert and holds its row, and
    # no two pairs share a slot; every other slot holds row 0
    taken = slot[here]
    assert len(set(taken.tolist())) == here.sum()
    assert (taken < used * tr).all()
    assert (planes[taken // tr] == idx[here]).all()
    assert (gather[taken] == np.nonzero(here)[0]).all()
    rest = np.ones(m * tr, bool)
    rest[taken] = False
    assert (gather[rest] == 0).all()
    assert ((0 <= slot) & (slot < m * tr)).all()


@pytest.mark.parametrize("rows,k,experts,want", [
    (16, 8, 64, None), (1, 6, 64, None), (4, 4, 64, None),   # every pure-decode step
    (17, 4, 64, 16), (64, 6, 160, 16), (64, 8, 64, 16), (128, 4, 64, 16),
    (256, 4, 64, 32), (256, 8, 64, 64), (512, 6, 64, 64), (256, 6, 160, 16),
    (256, 8, 128, 32), (1024, 8, 64, 128), (4096, 8, 64, 128),
])
def test_the_rule_for_the_rows_of_a_block(rows, k, experts, want):
    assert grouping.block_rows(rows, k, experts) == want


# ---- the launch -----------------------------------------------------------------

@pytest.mark.parametrize("used", [0, 3, 5])
@pytest.mark.parametrize("n,d", [(64, 96), (512, 200)], ids=str)
def test_grouped_launch_is_one_launch_an_expert_over_the_blocks_used(n, d, used):
    """Block ``b`` against plane ``planes[b]`` of the layer, bit for bit what
    ``q40_mm_stacked`` gives that block alone (to an ulp of a sum where the
    block's 16 rows take the sliced body, PR 62: the interpreter's CPU program
    contracts the partials' multiply and add into one fma or not by what
    surrounds them, here a ``pl.when``); blocks past ``used`` are not computed
    (and not compared)."""
    experts, layers, m, tr = 4, 2, 5, 16
    rng = np.random.RandomState(n + used)
    qt = jax.tree.map(jnp.asarray, q40.quantize(
        rng.standard_normal((layers * experts, n, d)).astype(np.float32)))
    x = jnp.asarray(rng.standard_normal((m, tr, n)), jnp.bfloat16)
    planes = jnp.asarray([0, 0, 2, 3, 3], jnp.int32)
    tiles = (256, 128) if n == 512 else None   # two reduction steps, a ragged d
    got = np.asarray(q40._pallas_matmul_experts(
        x, qt.qpacked, qt.scales, jnp.int32(1), experts=experts, chosen=planes,
        used=jnp.int32(used), interpret=True, tiles=tiles))
    assert got.shape == (m, tr, d)
    for b in range(used):
        want = q40._pallas_matmul_stacked(x[b], qt.qpacked, qt.scales,
                                          experts + planes[b], interpret=True,
                                          tiles=tiles)
        if q40._body(tr, (tiles or q40._tiles(n, d))[0]) == "sliced":
            np.testing.assert_allclose(got[b], np.asarray(want), rtol=0,
                                       atol=1e-6 * np.abs(want).max())
        else:
            np.testing.assert_array_equal(got[b], np.asarray(want))


def test_grouped_launch_is_named_for_the_trace():
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda x, qp, sc, layer, planes, used: q40._pallas_matmul_experts(
        x, qp, sc, layer, experts=4, chosen=planes, used=used))(
        s((3, 16, 64), jnp.bfloat16), s((8, 32, 96), jnp.uint8), s((8, 2, 96), jnp.uint16),
        s((), jnp.int32), s((3,), jnp.int32), s((), jnp.int32))
    assert "q40_mm_grouped" in str(jaxpr) and "q40_mm_chosen" not in str(jaxpr)


# ---- moe_ffn on the path, every router ---------------------------------------

ACTS = {mfile.ACT_GELU: ref.gelu_tanh, mfile.ACT_SILU: ref.silu, mfile.ACT_RELU: ref.relu}


def _softmax_ref(x, lp, cfg):
    return ref.moe(x, lp["router"], lp["up"], lp["gate"], lp["down"],
                   cfg.n_active_experts, ACTS[cfg.hidden_act], cfg.norm_topk_prob)


ROUTERS = {
    # unnormalised top-k of a softmax over all (OLMoE)
    "softmax-topk": (lambda: tiny_config(arch=mfile.ARCH_OLMOE, n_experts=16,
                                         n_active_experts=4, n_layers=1), _softmax_ref),
    # 3 of 8 groups, top-6, scaled by 16, two shared experts (DeepSeek-V2)
    "grouped-topk": (lambda: tiny_deepseek2(n_layers=1, n_dense_layers=0),
                     lambda x, lp, cfg: ref.deepseek2_moe(x, lp, cfg, ACTS[cfg.hidden_act])),
    # a sigmoid, a bias in the choice only, one shared expert (K-EXAONE, uncut)
    "sigmoid-bias": (lambda: tiny_exaone_moe(experts_held=0, first_expert=0),
                     ref.exaone_moe_layer),
    # the second of eight shares: 4 of 32 experts held here
    "share": (tiny_exaone_moe, lambda x, lp, cfg: ref.exaone_moe_layer(
        x, lp, cfg, (cfg.first_expert, cfg.n_experts_held))),
    # the chosen scores over their sum + 1e-6 (LFM2)
    "norm-eps": (tiny_lfm2_moe, ref.lfm2_moe_layer),
}
MOE_KEYS = ("router", "router_bias", "up", "gate", "down", "shared_w1", "shared_w2",
            "shared_w3")


def _layer(name):
    """One expert layer of the toy: what ``moe_ffn`` takes (packed Q40 views)
    and, for the reference, the float32 weights those tensors hold."""
    cfg = ROUTERS[name][0]()
    p = init_params(cfg, seed=5, scale=0.08)
    if "router_bias" in p:
        bias = np.random.RandomState(6).standard_normal(p["router_bias"].shape)
        p = dict(p, router_bias=jnp.asarray(0.05 * bias, jnp.float32))
    lp = {k: np.asarray(p[k][0], np.float32) for k in MOE_KEYS if k in p}
    run, want = {}, {}
    for k, v in lp.items():
        if k.startswith("router"):
            run[k], want[k] = jnp.asarray(v), v
            continue
        qt = q40.quantize(v[None])
        want[k] = np.asarray(q40.dequantize(qt))[0]
        run[k] = q40.QLayerView(jax.tree.map(jnp.asarray, qt), jnp.int32(0))
    if "shared_w1" in run:  # the program holds the shared gate and up fused
        qt = q40.quantize(np.concatenate([lp["shared_w1"], lp["shared_w3"]], -1)[None])
        run["shared_w13"] = q40.QLayerView(jax.tree.map(jnp.asarray, qt), jnp.int32(0))
        w13 = np.asarray(q40.dequantize(qt))[0]
        want["shared_w1"], want["shared_w3"] = np.split(w13, 2, axis=-1)
        del run["shared_w1"], run["shared_w3"]
    return cfg.with_(quant_impl="pallas_interpret"), run, want


def _site():
    return [k for k in obs_dispatch.dispatches() if k.startswith("moe/")]


@pytest.mark.parametrize("rows", [24, 130])
@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_grouped_matches_all_experts_and_the_float32_reference(name, rows, monkeypatch):
    """The same weights and the same choice on both paths: what differs is
    the order of a float32 sum over a row's k terms, against a sum over all
    the held experts of which the others were zeros."""
    cfg, lp, want_lp = _layer(name)
    x = np.random.RandomState(rows).standard_normal((rows, cfg.dim)).astype(np.float32)
    obs_dispatch.reset()
    got = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg))
    assert _site() == ["moe/grouped"]
    monkeypatch.setattr(grouping, "block_rows", lambda *a: None)
    obs_dispatch.reset()
    masked = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg))
    assert _site() == ["moe/all-experts"]
    wanted = ROUTERS[name][1](x, want_lp, cfg)
    # the bf16 rounding of activations in a Q40 matmul: 3% of the output's
    # spread at the 16 rows the strategies' own tests run; the maximum over
    # eight times the rows lies higher, the same on both paths
    tol = 0.05 * wanted.std()
    assert np.abs(got - wanted).max() < tol
    assert np.abs(got - masked).max() < 1e-3 * tol


@pytest.mark.parametrize("case", ["one-expert", "nobody-chose-some", "ragged"])
def test_grouped_under_lopsided_routing(case, monkeypatch):
    """Logits handed in, so the routing is the test's: every row to the same
    k experts (runs of several blocks and experts with none), the low half
    only, and a row count that fills no block."""
    cfg, lp, want_lp = _layer("softmax-topk")
    rows = {"one-expert": 40, "nobody-chose-some": 48, "ragged": 21}[case]
    rng = np.random.RandomState(rows)
    x = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
    logits = rng.standard_normal((rows, cfg.n_experts)).astype(np.float32)
    if case == "one-expert":
        logits[:] = logits[0]
    elif case == "nobody-chose-some":
        logits[:, cfg.n_experts // 2:] -= 20.0
    obs_dispatch.reset()
    got = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg, jnp.asarray(logits)))
    assert _site() == ["moe/grouped"]
    monkeypatch.setattr(grouping, "block_rows", lambda *a: None)
    masked = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg, jnp.asarray(logits)))
    assert np.abs(got - masked).max() < 1e-4 * masked.std()
    assert masked.std() > 0


# ---- the rule: 16 rows and fewer are the parent's programs --------------------

# sha256 of str(jax.make_jaxpr(moe_ffn)) on the parent of this PR (a261a5c) for
# the toys above at the rows of a pure-decode step, where ``all-experts`` stays
PARENT_JAXPRS = {
    ("softmax-topk", 5): "f5155cc8210ddcda", ("softmax-topk", 16): "b285c32c09664511",
    ("grouped-topk", 16): "3972421d97874bb4", ("sigmoid-bias", 16): "a891beda80bdd86c",
    ("share", 16): "285367f288eda25a", ("norm-eps", 16): "431723bb26d561d2",
}


def _jaxpr_hash(name, rows):
    cfg, lp, _ = _layer(name)
    views = {k: v for k, v in lp.items() if isinstance(v, q40.QLayerView)}
    rest = {k: v for k, v in lp.items() if k not in views}

    def f(x, rest, planes):
        full = dict(rest, **{k: q40.QLayerView(planes[k], jnp.int32(0)) for k in planes})
        return moe_ffn(x, full, cfg)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((rows, cfg.dim), jnp.float32), rest,
                              {k: v.qt for k, v in views.items()})
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(PARENT_JAXPRS), ids=str)
def test_sixteen_rows_and_fewer_trace_to_the_parents_program(case):
    obs_dispatch.reset()
    assert _jaxpr_hash(*case) == PARENT_JAXPRS[case]
    assert _site() == ["moe/all-experts"]


@pytest.mark.parametrize("rows,path", [(4, "select-chosen"), (16, "all-experts"),
                                       (17, "grouped"), (64, "grouped")])
def test_the_ledger_records_the_strategy_and_its_blocks(rows, path, monkeypatch):
    cfg, lp, _ = _layer("share")
    seen = []
    monkeypatch.setattr(obs_dispatch._log, "debug",
                        lambda msg, extra=None: seen.append(extra))
    moe_ffn(jnp.ones((rows, cfg.dim)), lp, cfg)
    moe = [s for s in seen if s["codec"] == "moe"]
    assert [s["path"] for s in moe] == [path]
    assert moe[0]["experts"] == 32 and moe[0]["held"] == 4
    if path == "grouped":
        tr = grouping.block_rows(rows, cfg.n_active_experts, cfg.n_experts)
        assert moe[0]["tr"] == tr and moe[0]["blocks"] == grouping.blocks(
            rows, cfg.n_active_experts, 4, tr)
        launches = [s for s in seen if s["codec"] == "q40" and s.get("experts")]
        assert [s["experts"] for s in launches] == [moe[0]["blocks"]] * 3
        assert [s["rows"] for s in launches] == [tr] * 3
        # a second program of the same shape finds the jitted block traced,
        # and its sites are recorded all the same
        del seen[:]
        moe_ffn(jnp.ones((rows, cfg.dim)), lp, cfg)
        assert [(s["codec"], s["path"]) for s in seen
                if s["codec"] == "moe" or s["codec"] == "q40" and s.get("experts")
                ] == [("moe", "grouped")] + [("q40", "pallas-fused")] * 3


# ---- how full the blocks were ---------------------------------------------------

def test_a_scan_over_layers_hands_the_notes_up():
    def layers(x):
        def body(c, i):
            grouping.note(10, 16, i)
            return c + 1, None
        return grouping.scan(body, x, jnp.arange(3))[0]

    assert str(jax.make_jaxpr(layers)(0.0)) == str(jax.make_jaxpr(
        lambda x: jax.lax.scan(lambda c, i: (c + 1, None), x, jnp.arange(3))[0])(0.0))
    with grouping.collecting() as notes:
        grouping.note(10, 16, jnp.int32(2))
        layers(0.0)
    assert np.asarray(grouping.total(notes)).tolist() == [10 + 30, 16 * (2 + 0 + 1 + 2)]
    assert grouping.total([]) is None


@pytest.mark.parametrize("toy", ["softmax-topk", "norm-eps", "share"])
def test_a_prefill_call_counts_its_pairs_and_slots(toy):
    """The engine's prefill program returns one pair of integers where a layer
    grouped, and the counter takes them; a decode step returns none."""
    from dllama_tpu.models.params import quantize_matmuls
    from dllama_tpu.runtime.engine import Engine

    cfg = ROUTERS[toy][0]().with_(quant_impl="pallas_interpret")
    engine = Engine(cfg, quantize_matmuls(init_params(cfg, seed=2), cfg), seq_len=128)
    before = dict(obs_metrics.MOE_GROUPED_ROWS.json_value())
    engine.prefill(list(range(1, 41)))            # a bucket of 64 rows
    after = obs_metrics.MOE_GROUPED_ROWS.json_value()
    pairs = after["pairs"] - before.get("pairs", 0)
    slots = after["slots"] - before.get("slots", 0)
    held = cfg.n_experts_held
    if held == cfg.n_experts:
        assert pairs == 64 * cfg.n_active_experts * cfg.n_moe_layers
    else:  # the pairs of the experts held here alone, so the fill stays under 100%
        assert 0 < pairs < 64 * cfg.n_active_experts * cfg.n_moe_layers
    tr = grouping.block_rows(64, cfg.n_active_experts, cfg.n_experts)
    assert pairs <= slots <= cfg.n_moe_layers * tr * grouping.blocks(
        64, cfg.n_active_experts, held, tr) and slots % tr == 0
    engine.prefill([5])                           # one row: nothing grouped
    assert obs_metrics.MOE_GROUPED_ROWS.json_value() == after
