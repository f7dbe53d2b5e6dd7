"""``ARCH_EXAONE_MOE`` (0xABCD06), K-EXAONE at a toy size that keeps every ratio
(periods of three window layers and then a full one, a window of 16 under
sequences of 60, 8 query heads a kv head, a head of 8 on a hidden size of 64
that 16 heads do not divide, a dense first layer, 32 experts of which 8 a token
and 4 held here, one shared expert, a choice bias that is not 0): the format,
the program against the plain reference of ``tests/reference_impl.py`` (logits,
not tokens) in a one-pass prefill, a chunked prefill and decoding through the
contiguous cache, and the slot path through the pool per layer kind past a
wrapped ring of pages; every ``moe_ffn`` strategy against a float32 loop; the
share test; a slot held at 40 times the window; the engine and the scheduler;
the converter's round trip and refusals; the tracing names.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models import config as config_mod
from dllama_tpu.models import windowed
from dllama_tpu.models.config import ModelConfig, tiny_exaone_moe
from dllama_tpu.models.params import (init_params, load_params, param_shapes,
                                      quantize_matmuls)
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool, moe_ffn)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import q40, window
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.pagepool import ring_pages_recycled
from dllama_tpu.runtime.scheduler import SlotScheduler

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "converter"))

CFG = tiny_exaone_moe()
TOKS = np.random.RandomState(0).randint(3, 128, (60,)).astype(np.int32)
TOKS2 = TOKS[::-1].copy()
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums, 2e-6 of logits whose spread is 0.63.  Each of the
# wrong computations below moves some position by 0.03 or more.
TOL = 2e-5
# the held experts' float32 product of 16 rows: a prefill chunk of 16, so a
# window layer's contiguous ring is 16 + 16 = 32 positions under sequences of 60
SMALL_PRODUCT = 4 * 4 * 64 * 16


def _init(cfg, seed=5):
    """Random params whose choice bias is small and not 0."""
    p = init_params(cfg, seed=seed, scale=0.08)
    bias = np.random.RandomState(seed + 1).standard_normal(p["router_bias"].shape)
    return dict(p, router_bias=jnp.asarray(0.05 * bias, jnp.float32))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_exaone_moe(p, CFG, TOKS),
            "b": ref.np_forward_exaone_moe(p, CFG, TOKS2), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_chunk(monkeypatch):
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    assert CFG.prefill_chunk() == 16 and CFG.window_ring(96) == 32


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=cfg.n_experts, n_active_experts=cfg.n_active_experts,
        vocab_size=cfg.vocab_size, seq_len=cfg.seq_len,
        hidden_act=cfg.hidden_act, rope_theta=cfg.rope_theta,
        weights_ftype=ftype,
        **{name: getattr(cfg, name) for k, name, _ in mfile.ALL_EXT_KEYS
           if k in mfile.ARCH_EXT_KEYS[mfile.ARCH_EXAONE_MOE]})
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, params_np, cfg=CFG, ftype=quants.F32):
    """The runtime-layout ``params_np`` as a ``.m`` file: each plan tensor is
    its stack's slice (a segment's stack is indexed within the segment),
    transposed back to the file's (d_out, n_in)."""
    names = {"moe_router": "router", "moe_router_bias": "router_bias"}
    with mfile.MFileWriter(path, _spec(cfg, ftype=ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = params_np[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            else:
                leaf, li = parts[-1], int(parts[1])
                stack = params_np[names.get(leaf, leaf)]
                if stack.shape[0] != cfg.n_layers and leaf not in ("w1", "w2", "w3"):
                    li -= cfg.n_dense_layers
                x = stack[li]
                if parts[2] == "experts":
                    x = x[int(parts[3])]
                x = x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ------------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    path = tmp_path / "toy.m"
    _write_model(path, want["np"])
    spec = mfile.read_spec(path)
    assert spec.arch == mfile.ARCH_EXAONE_MOE == 0xABCD06
    assert spec.arch_name == "exaone_moe" and spec.hidden_act == mfile.ACT_SILU
    assert (spec.head_dim, spec.window, spec.window_period, spec.window_full_at
            ) == (8, 16, 4, 3)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.n_experts_held) == (32, 4, 4, 4)
    assert (spec.moe_hidden_dim, spec.n_shared_experts, spec.n_dense_layers,
            spec.n_groups, spec.topk_groups) == (32, 1, 1, 1, 1)
    assert spec.routed_scale == pytest.approx(2.5)
    assert spec.norm_eps == pytest.approx(1e-5)
    # the fourteen keys, six of DeepSeek-V2's, 31..37
    assert spec.header_size == 8 + 8 * (14 + 6 + 7)
    names = [t.name for t in mfile.tensor_plan(spec)]
    assert names[1:10] == [f"layers.0.{n}" for n in (
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w1", "w2", "w3")]
    assert "layers.0.moe_router" not in names and "layers.1.w1" not in names
    assert names.count("layers.1.moe_router_bias") == 1
    assert sum(n.startswith("layers.1.experts.") for n in names) == 3 * 4
    cfg = ModelConfig.from_spec(spec)
    assert cfg.with_(norm_eps=1e-5) == CFG.with_(dtype=cfg.dtype)
    assert cfg.qk_head_norm and cfg.router_sigmoid and cfg.norm_topk_prob
    assert not cfg.qk_norm and not cfg.rope_interleaved
    assert (cfg.n_full_layers, cfg.n_window_layers, cfg.n_moe_layers) == (2, 6, 7)


@pytest.mark.parametrize("kw,says", [
    (dict(window_full_at=4), "the full layer's place in a period"),
    (dict(experts_held=4, first_expert=30), "a run of the router's"),
    (dict(experts_held=40), "a run of the router's"),
    (dict(n_groups=2), "one group"),
    (dict(n_dense_layers=8), "expert layers follow"),
    (dict(moe_hidden_dim=0), "states its experts' width"),
    (dict(window_period=3), "whole periods"),
    (dict(arch=mfile.ARCH_MIXTRAL, head_dim=0, window=0, window_period=0,
          moe_hidden_dim=0, n_shared_experts=0, n_groups=0, topk_groups=0,
          n_dense_layers=0, window_full_at=0), "keys 35..37 describe an exaone_moe file"),
])
def test_header_rules_are_refused_by_name(tmp_path, kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), tmp_path / "x.m")


def test_published_widths_give_the_issues_chunk_and_ring():
    """The shapes the cell runs: the float32 product over the 16 HELD experts
    of 6144 gives chunks of 1024 rows; a slot's ring is ten pages."""
    cfg = tiny_exaone_moe(dim=6144, hidden_dim=18432, moe_hidden_dim=2048,
                          n_layers=24, n_heads=64, n_kv_heads=8, head_dim=128,
                          n_experts=128, experts_held=16, first_expert=0,
                          window=128, vocab_size=19200, seq_len=262144)
    assert cfg.prefill_chunk() == 1024 and cfg.window_ring(6144) == 1152
    assert window.window_pages(128, windowed.SLOT_ROWS, 16, 384) == 10
    shapes = param_shapes(cfg)
    assert shapes["router"] == (23, 6144, 128) and shapes["router_bias"] == (23, 128)
    assert shapes["up"] == (23, 16, 6144, 2048) and shapes["w1"] == (1, 6144, 18432)
    assert shapes["q_norm"] == (24, 128) and shapes["shared_w2"] == (23, 2048, 6144)


# ---- the program against the reference ------------------------------------------

def test_one_pass_prefill_is_the_reference(params, want):
    lg, _ = forward(params, CFG, jnp.asarray(TOKS)[None], init_kv_cache(CFG, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - want["a"]).max() < TOL


@pytest.mark.parametrize("wrong", [
    "rope_on_full", "full_first", "no_head_norm", "window_plus_one",
    "bias_in_weights", "softmax_router", "no_scale", "norm_over_held"])
def test_each_wrong_computation_is_seen(want, wrong):
    """The tolerance separates the architecture from its near misses."""
    bad = ref.np_forward_exaone_moe(want["np"], CFG, TOKS, wrong=wrong)
    assert np.abs(bad - want["a"]).max() > 1000 * TOL


def test_prefill_then_decode_through_the_contiguous_cache(params, want, small_chunk):
    """Chunks of 16 (the ring of 32 wraps inside the prompt), then one token at
    a time around the ring: every position's logits are the reference's."""
    cache = init_kv_cache(CFG, 1, 96)
    assert cache.k.shape == (2, 1, 2, 96, 8) and cache.wk.shape == (6, 1, 2, 32, 8)
    errs = []
    for lo in range(0, 48, 16):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[None, lo:lo + 16]), cache,
                            jnp.int32(lo))
        errs.append(np.abs(np.asarray(lg)[0] - want["a"][lo:lo + 16]).max())
    for i in range(48, 60):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[None, i:i + 1]), cache,
                            jnp.int32(i))
        errs.append(np.abs(np.asarray(lg)[0, 0] - want["a"][i]).max())
    assert max(errs) < TOL, errs


def test_chunked_prefill_equals_one_pass(params, want, small_chunk):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    one, _ = eng.prefill([int(t) for t in TOKS[:16]])
    assert np.abs(one[0] - want["a"][15]).max() < TOL
    eng.reset()
    chunked, _ = eng.prefill([int(t) for t in TOKS[:45]])
    assert np.abs(chunked[0] - want["a"][44]).max() < TOL
    for i in range(45, 60):
        lg, _ = eng.decode_one(int(TOKS[i]))
    assert np.abs(lg[0] - want["a"][59]).max() < TOL


def test_slot_path_through_the_pool_per_kind_past_a_wrapped_ring(params, want):
    """Two slots with clocks of their own on the paged engine's cache: the full
    layers' pool behind page tables out of order, the window layers' rings of 9
    pages of 4 (36 positions) under sequences of 53 and 57: chunks of 8 (slot
    b's first has 5 real tokens), a mixed step, then pure-decode steps."""
    cache = init_kv_pool(CFG, 40, 4, slots=2)
    assert cache.k.shape == (2, 40, 4, 2, 8)         # the 2 full layers' pool
    assert cache.wk.shape == (6, 2 * 9, 4, 2, 8)     # 6 window layers, 2 rings
    table = jnp.asarray(np.stack([
        np.random.RandomState(1).permutation(np.arange(1, 20)),
        np.arange(20, 39)]).astype(np.int32))
    srcs, wants = (TOKS, TOKS2), (want["a"], want["b"])
    done, errs = [0, 0], []

    def step(n_valid, t):
        nonlocal cache
        tk = np.zeros((2, t), np.int32)
        for r in range(2):
            tk[r, :n_valid[r]] = srcs[r][done[r]:done[r] + n_valid[r]]
        lg, cache = forward_slots(
            params, CFG, jnp.asarray(tk), cache, jnp.asarray(done, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), table)
        for r in range(2):
            done[r] += n_valid[r]
            errs.append(np.abs(np.asarray(lg)[r] - wants[r][done[r] - 1]).max())

    for nv in ([8, 5], [8, 8], [8, 8], [8, 8]):
        step(nv, 8)
    step([1, 8], 8)            # mixed: a decodes, b prefills
    for _ in range(20):
        step([1, 1], 1)        # pure decode
    assert done == [53, 57]
    assert max(errs) < TOL, errs


def test_verify_step_keeps_every_position(params, want):
    """``forward_slots_all`` (the speculative verify step) over 5 tokens past
    the window: every position's logits are the reference's."""
    pool = init_kv_pool(CFG, 12, 4, slots=1, max_pages=12)
    table = jnp.asarray(np.array([[3, 7, 1, 9, 5, 2, 8, 4, 6, 10, 11, 0]], np.int32))
    pos = 0
    for t in (12, 12):
        _, pool = forward_slots(params, CFG, jnp.asarray(TOKS[None, pos:pos + t]),
                                pool, jnp.full((1,), pos, jnp.int32),
                                jnp.full((1,), t, jnp.int32), table)
        pos += t
    lg, _ = forward_slots_all(params, CFG, jnp.asarray(TOKS[None, 24:29]), pool,
                              jnp.full((1,), 24, jnp.int32),
                              jnp.full((1,), 5, jnp.int32), table)
    assert np.abs(np.asarray(lg)[0] - want["a"][24:29]).max() < TOL


def test_a_slot_at_forty_windows_keeps_a_ring_of_window_pages(want):
    """A window layer's pages a slot are bounded by the window whatever the
    context: 640 positions (40 x the window of 16) through chunks of 16 on a
    ring of ``window_pages(16, 16, 4)`` = 9 pages, while the full layers' table
    grows to 160; the last chunk's logits are the reference's."""
    cfg = CFG.with_(seq_len=640)
    params = _init(cfg, seed=7)
    toks = np.random.RandomState(3).randint(3, 128, (640,)).astype(np.int32)
    ring = window.window_pages(cfg.window, windowed.SLOT_ROWS, 4, 160)
    cache = init_kv_pool(cfg, 161, 4, slots=1, max_pages=160)
    assert ring == 9 and cache.wk.shape == (6, ring, 4, 2, 8)
    assert cache.k.shape == (2, 161, 4, 2, 8)
    table = jnp.asarray(np.arange(1, 161, dtype=np.int32)[None])
    step = jax.jit(lambda c, tk, pos: forward_slots(
        params, cfg, tk, c, pos, jnp.full((1,), 16, jnp.int32), table))
    for lo in range(0, 640, 16):
        lg, cache = step(cache, jnp.asarray(toks[None, lo:lo + 16]),
                         jnp.full((1,), lo, jnp.int32))
    wanted = ref.np_forward_exaone_moe(
        {k: np.asarray(v) for k, v in params.items()}, cfg, toks)
    assert np.abs(np.asarray(lg)[0] - wanted[639]).max() < 5 * TOL
    # what the scheduler counts while that happens: every page past the ring's
    # first lap takes the place of the page nine behind it
    assert ring_pages_recycled(0, 640, 4, ring) == 160 - ring
    assert ring_pages_recycled(0, 36, 4, ring) == 0
    assert ring_pages_recycled(36, 37, 4, ring) == 1
    assert ring_pages_recycled(37, 40, 4, ring) == 0
    assert sum(ring_pages_recycled(p, p + 1, 4, ring) for p in range(640)) == 151


# ---- moe_ffn: every strategy, and the share ---------------------------------

def _layer_params(cfg, seed=11):
    """One expert layer's weights (the layer's slice of every MoE stack)."""
    p = {k: np.asarray(v) for k, v in _init(cfg, seed).items()}
    return p, {k: p[k][2] for k in ("router", "router_bias", "up", "gate", "down",
                                    "shared_w1", "shared_w2", "shared_w3")}


STRATEGIES = [
    # (quantized, quant_impl, cfg overrides, rows -> ledger path)
    ("dense", None, {}, {1: "select", 2: "select", 4: "select", 16: "dense"}),
    ("q40-kernel", "pallas_interpret", {},
     {1: "select-chosen", 2: "select-chosen", 4: "select-chosen", 16: "all-experts"}),
    ("q40-xla", "xla", {}, {1: "select", 2: "select", 4: "select", 16: "unrolled"}),
    ("q40-xla-scan", "xla", dict(experts_held=16, first_expert=8),
     {1: "select", 16: "scan"}),
]


@pytest.mark.parametrize("rows", [1, 2, 4, 16])
@pytest.mark.parametrize("name,impl,over,paths", STRATEGIES,
                         ids=[s[0] for s in STRATEGIES])
def test_every_strategy_gives_the_held_experts_part(name, impl, over, paths, rows):
    """``moe_ffn`` at 1, 2, 4 and 16 rows on every strategy against the float32
    loop of ``reference_impl.exaone_moe_layer`` over the same (dequantized)
    weights: the sigmoid scores, the bias in the choice only, the weights over
    all eight chosen times 2.5, the held experts' part, the shared expert."""
    if rows not in paths:
        pytest.skip("covered at 1 and 16 rows")
    cfg = tiny_exaone_moe(**over)
    _, lp = _layer_params(cfg)
    x = np.random.RandomState(rows).standard_normal((rows, cfg.dim)).astype(np.float32)
    run_cfg, run_lp, ref_lp = cfg, dict(lp), dict(lp)
    if impl:
        run_cfg = cfg.with_(quant_impl=impl)
        for k in ("up", "gate", "down", "shared_w1", "shared_w2", "shared_w3"):
            qt = q40.quantize(lp[k][None])
            ref_lp[k] = np.asarray(q40.dequantize(qt))[0]
            run_lp[k] = q40.QLayerView(jax.tree.map(jnp.asarray, qt), jnp.int32(0))
    obs_dispatch.reset()
    got = np.asarray(moe_ffn(jnp.asarray(x), {
        k: v if isinstance(v, q40.QLayerView) else jnp.asarray(v)
        for k, v in run_lp.items()}, run_cfg))
    site = [k for k in obs_dispatch.dispatches() if k.startswith("moe/")]
    assert site == ["moe/" + paths[rows]]
    wanted = ref.exaone_moe_layer(x, ref_lp, cfg, (cfg.first_expert,
                                                   cfg.n_experts_held))
    # packed: the kernel and the XLA path round activations to bfloat16
    tol = 2e-5 if impl is None else 0.03 * wanted.std()
    assert np.abs(got - wanted).max() < tol
    # a row whose chosen experts are all elsewhere gets the shared expert alone
    only_shared = ref.exaone_moe_layer(x, ref_lp, cfg, (0, 0))
    assert np.abs(wanted - only_shared).max() > 0  # some row routes here


def test_the_ledger_records_held_beside_experts(monkeypatch):
    cfg = tiny_exaone_moe()
    _, lp = _layer_params(cfg)
    seen = []
    monkeypatch.setattr(obs_dispatch._log, "debug",
                        lambda msg, extra=None: seen.append(extra))
    for rows in (2, 16):
        moe_ffn(jnp.ones((rows, cfg.dim)), {k: jnp.asarray(v) for k, v in lp.items()},
                cfg)
    moe = [s for s in seen if s["codec"] == "moe"]
    assert [s["path"] for s in moe] == ["select", "dense"]
    assert all(s["experts"] == 32 and s["held"] == 4 for s in moe), moe


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test.  An uncut layer (32 experts held) against eight chips'
    shares of four experts each, each share computed by ``moe_ffn`` on its own
    slice of the expert stacks with the whole router: the eight routed parts
    plus the shared expert counted once are the uncut layer's output, and the
    uncut layer is the reference's."""
    whole = tiny_exaone_moe(experts_held=0, first_expert=0)
    _, lp = _layer_params(whole, seed=13)
    x = np.random.RandomState(2).standard_normal((16, whole.dim)).astype(np.float32)

    def run(cfg, lp):
        return np.asarray(moe_ffn(jnp.asarray(x), {k: jnp.asarray(v)
                                                   for k, v in lp.items()}, cfg))

    uncut = run(whole, lp)
    assert np.abs(uncut - ref.exaone_moe_layer(x, lp, whole)).max() < TOL
    shared = ref.exaone_moe_layer(x, lp, whole, (0, 0))
    routed = np.zeros_like(uncut)
    for chip in range(8):
        cfg = tiny_exaone_moe(experts_held=4, first_expert=4 * chip)
        own = dict(lp, **{k: lp[k][4 * chip:4 * chip + 4]
                          for k in ("up", "gate", "down")})
        routed += run(cfg, own) - shared
    assert np.abs(routed + shared - uncut).max() < TOL
    # and a row's weights over ALL eight chosen sum to the routed scale
    assert np.abs(routed).max() > 100 * TOL


# ---- the loader, the engine, the scheduler ------------------------------------

@pytest.fixture(scope="module")
def q40_file(tmp_path_factory, want):
    path = tmp_path_factory.mktemp("ex") / "toy_q40.m"
    _write_model(path, want["np"], ftype=quants.Q40)
    return str(path)


def test_loader_packed_and_dense_agree_with_the_reference(q40_file):
    mf = mfile.MFile(q40_file)
    cfg, dense = load_params(mf, dtype=jnp.float32)
    assert cfg.window == 16 and cfg.experts_held == 4 and cfg.first_expert == 4
    assert {k: tuple(v.shape) for k, v in dense.items()} == param_shapes(cfg)
    wanted = ref.np_forward_exaone_moe(
        {k: np.asarray(v) for k, v in dense.items()}, cfg, TOKS)
    lg, _ = forward(dense, cfg, jnp.asarray(TOKS)[None], init_kv_cache(cfg, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - wanted).max() < TOL
    _, packed = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    assert packed["wqkv"].logical_nd == (64, 128 + 32)
    assert packed["up"].qpacked.shape[:2] == (7, 4)
    assert packed["w13"].qpacked.shape[0] == 1 and "shared_w13" in packed
    assert packed["router_bias"].dtype == np.float32
    lg, _ = forward(packed, cfg.with_(quant_impl="xla"), jnp.asarray(TOKS)[None],
                    init_kv_cache(cfg, 1), jnp.int32(0))
    worst = np.abs(np.asarray(lg)[0] - wanted).max(1) / wanted.std()
    assert np.median(worst) < 0.05 and (worst > 0.1).sum() <= 6, worst
    # quantize_matmuls makes the same pytree from dense params
    again = quantize_matmuls({k: np.asarray(v) for k, v in dense.items()}, cfg)
    assert set(again) == set(packed)


def test_engine_scheduler_contiguous_and_paged_serve_the_same_tokens(
        q40_file, monkeypatch):
    """Greedy tokens through the slot scheduler, on contiguous slots and on the
    pool per layer kind, are the one-stream engine's, token for token, past the
    window and around the rings; the gauges read the cache's own arrays and the
    scheduler counts the ring pages it recycles.  The file's weights loaded
    dense: on packed weights the few-row and many-row expert strategies round
    activations apart by 0.003 of a logit, and this toy's greedy stream has
    ties closer than that (the packed strategies have their own test above)."""
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    mf = mfile.MFile(q40_file)
    cfg, params = load_params(mf, dtype=jnp.float32)
    solo = Engine(cfg, params, mesh=_mesh(), batch=1)
    tok = 2 * cfg.kv_dim * 4
    assert solo.kv_bytes_per_token == cfg.n_layers * tok
    p1, p2 = [5, 9, 2], [int(t) for t in TOKS[:21]]
    wanted = []
    for p in (p1, p2):
        solo.reset()
        wanted.append([t for t, _ in solo.generate_stream(
            p, len(p) + 40, temperature=0.0, chunk=5)][len(p):])
    for kw in (dict(), dict(kv_pages=2 * (cfg.seq_len // 4) + 1, kv_page_size=4)):
        eng = Engine(cfg, params, mesh=_mesh(), batch=2, **kw)
        before = obs_metrics.KV_WINDOW_PAGES_RECYCLED.value
        sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0, decode_burst=4)
        try:
            tickets = [sched.submit(p, 40, temperature=0.0) for p in (p1, p2)]
            outs = [list(t.tokens()) for t in tickets]
        finally:
            sched.close()
        assert outs == wanted, kw
    # the paged engine: what a token adds is the 2 full layers' pages; the 6
    # window layers are 2 slots' rings of 9 pages whatever the context
    assert eng.kv_bytes_per_token == cfg.n_full_layers * tok and eng.ring_pages == 9
    assert eng.cache.k.shape == (2, 49, 4, 2, 8) and eng.cache.wk.shape == (6, 18, 4, 2, 8)
    assert obs_metrics.KV_CACHE_BYTES._values == {
        ("full",): 2 * 49 * 4 * tok, ("window",): 6 * 18 * 4 * tok, ("conv",): 0,
        ("retention",): 0, ("ssm",): 0}
    assert obs_metrics.KV_BYTES_PER_TOKEN.value == cfg.n_full_layers * tok
    assert sched.prefix_cache is None and not sched.preempt
    # 42 and 60 positions written (the last token out is not fed) on rings of
    # 36: logical pages 9.. of each slot took the place of an older page
    recycled = obs_metrics.KV_WINDOW_PAGES_RECYCLED.value - before
    assert recycled == (-(-42 // 4) - 9) + (-(-60 // 4) - 9) == 8, recycled
    sites = obs_dispatch.dispatches()
    assert "kv_dense/window-ring" in sites and "kv_dense/window-gather" not in sites


def test_snapshot_carries_both_kinds_of_plane(params, tmp_path, small_chunk):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert set(eng._cache_arrays()) == {"cache.k", "cache.v", "cache.wk", "cache.wv"}
    first = [t for t, _ in eng.generate_stream([int(t) for t in TOKS[:40]], 45,
                                               temperature=0.0, chunk=3)]
    path = str(tmp_path / "e.snap")
    eng.snapshot(path)
    rest = [t for t, _ in eng.generate_stream([first[-1]], 12, temperature=0.0,
                                              chunk=3)]
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng2.restore(path)
    again = [t for t, _ in eng2.generate_stream([first[-1]], 12, temperature=0.0,
                                                chunk=3)]
    assert again == rest


# ---- the tracing names --------------------------------------------------------

def test_scopes_name_the_head_norm_and_the_layer_kinds(params):
    cache = init_kv_pool(CFG, 12, 4, slots=1, max_pages=12)
    table = jnp.asarray(np.arange(12, dtype=np.int32)[None])
    text = jax.jit(lambda c: forward_slots(
        params, CFG, jnp.zeros((1, 4), jnp.int32), c, jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 4, jnp.int32), table)).lower(cache).as_text(debug_info=True)
    for name in ("qkv/qk_norm", "attn/window", "attn/full", "moe/router",
                 "moe/shared", "w1", "w3", "page_idx"):
        assert name in text, name


# ---- the converter ---------------------------------------------------------------

EX_HF = dict(
    model_type="exaone_moe", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=8, num_attention_heads=16,
    num_key_value_heads=2, head_dim=8, vocab_size=128,
    max_position_embeddings=96, num_experts=32, num_experts_per_tok=8,
    num_shared_experts=1, first_k_dense_replace=1, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    rms_norm_eps=1e-5, hidden_act="silu", sliding_window=16,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    sliding_windows=[16, 16, 16, 0] * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 7, num_nextn_predict_layers=1,
    tie_word_embeddings=False)


def _hf_checkpoint(p, cfg):
    """A toy checkpoint of the UNCUT model under the published tensor names,
    with one ``mtp.*`` tensor the converter must skip."""
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.norm.weight": p["rms_final"], "lm_head.weight": p["wcls"].T,
          "mtp.layers.0.self_attn.q_proj.weight": np.zeros((4, 4), np.float32)}
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                             ("wo", "o_proj")):
            hf[f"{base}self_attn.{theirs}.weight"] = p[ours][i].T
        hf[base + "self_attn.q_norm.weight"] = p["q_norm"][i]
        hf[base + "self_attn.k_norm.weight"] = p["k_norm"][i]
        hf[base + "input_layernorm.weight"] = p["rms_att"][i]
        hf[base + "post_attention_layernorm.weight"] = p["rms_ffn"][i]
        if i < cfg.n_dense_layers:
            for ours, theirs in (("w1", "gate_proj"), ("w2", "down_proj"),
                                 ("w3", "up_proj")):
                hf[f"{base}mlp.{theirs}.weight"] = p[ours][i].T
            continue
        m = i - cfg.n_dense_layers
        hf[base + "mlp.gate.weight"] = p["router"][m].T
        hf[base + "mlp.gate.e_score_correction_bias"] = p["router_bias"][m]
        for e in range(cfg.n_experts):
            for leaf in ("up", "gate", "down"):
                hf[f"{base}mlp.experts.{e}.{leaf}_proj.weight"] = p[leaf][m, e].T
        for ours, theirs in (("shared_w1", "gate_proj"), ("shared_w2", "down_proj"),
                             ("shared_w3", "up_proj")):
            hf[f"{base}mlp.shared_experts.{theirs}.weight"] = p[ours][m].T
    return {k: np.ascontiguousarray(v) for k, v in hf.items()}


def test_convert_names_a_share_and_the_logits(tmp_path, capsys):
    """A toy checkpoint of the uncut model through the converter with
    ``--experts-held 4 --first-expert 4``, the loader and ``forward`` against
    the numpy reference given that share; the mtp tensors are skipped with a
    message; without the flags the file holds every expert."""
    from safetensors.numpy import save_file

    import convert_hf

    whole = tiny_exaone_moe(experts_held=0, first_expert=0)
    p = {k: np.asarray(v, np.float32) for k, v in _init(whole, seed=9).items()}
    (tmp_path / "config.json").write_text(json.dumps(EX_HF))
    save_file(_hf_checkpoint(p, whole), str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "ex.m")
    convert_hf.convert(str(tmp_path), quants.F32, out, experts_held=4, first_expert=4)
    assert "skipping 1 mtp.* tensors" in capsys.readouterr().out
    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_EXAONE_MOE
    assert (mf.spec.experts_held, mf.spec.first_expert, mf.spec.window_full_at,
            mf.spec.n_experts, mf.spec.hidden_dim, mf.spec.moe_hidden_dim
            ) == (4, 4, 3, 32, 96, 32)
    got_cfg, params = load_params(mf)
    got_cfg = got_cfg.with_(dtype=jnp.float32)
    assert got_cfg.with_(norm_eps=1e-5) == CFG
    share = dict(p, **{k: p[k][:, 4:8] for k in ("up", "gate", "down")})
    toks = np.random.RandomState(4).randint(3, 128, (40,)).astype(np.int32)
    wanted = ref.np_forward_exaone_moe(share, CFG, toks)
    logits, _ = forward(params, got_cfg, jnp.asarray(toks)[None],
                        init_kv_cache(got_cfg, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits)[0], wanted, atol=2e-5, rtol=1e-4)
    spec = convert_hf.load_spec(str(tmp_path), quants.F32)
    assert (spec.experts_held, spec.first_expert, spec.n_experts_held) == (0, 0, 32)


@pytest.mark.parametrize("key,value,says", [
    ("scoring_func", "softmax", "scoring_func is 'softmax'"),
    ("n_group", 4, "n_group is 4"),
    ("norm_topk_prob", False, "norm_topk_prob is false"),
    ("layer_types", ["sliding_attention"] * 5 + ["full_attention"] * 3,
     "is not whole periods"),
    ("sliding_windows", [16] * 8, "sliding_windows is not sliding_window"),
    ("mlp_layer_types", ["sparse"] * 8, "mlp_layer_types is not"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}, "rope_type is 'yarn'"),
    ("tie_word_embeddings", True, "tie_word_embeddings is true"),
    ("hidden_act", "gelu", "hidden_act is 'gelu'"),
])
def test_convert_refuses_variants_by_name(tmp_path, key, value, says):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(EX_HF, **{key: value})))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)


def test_convert_refuses_a_share_that_is_no_run_and_a_share_of_another_arch(tmp_path):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(EX_HF))
    with pytest.raises(SystemExit, match="is not a run of the 32 experts"):
        convert_hf.load_spec(str(tmp_path), quants.F32, experts_held=8, first_expert=28)
    with pytest.raises(SystemExit, match="unknown arguments"):
        convert_hf.main([str(tmp_path), "f32", "x", "--experts", "4"])
    (tmp_path / "config.json").write_text(json.dumps(dict(
        model_type="llama", hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=128, max_position_embeddings=64)))
    with pytest.raises(SystemExit, match="write a share of an exaone_moe"):
        convert_hf.load_spec(str(tmp_path), quants.F32, experts_held=4)
