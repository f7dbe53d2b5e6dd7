"""``models/deepseek_v2.py`` against the program, on the CPU at toy widths with
the published 160 experts in 8 groups (3 kept, 6 a token), 2 shared experts and
a leading dense layer (these tests import JAX and ``dllama_tpu``).  Three
independent forward passes on one seeded file the module wrote: the program's
engine (prefill, then decode through its latent cache; then the paged slot
path), the module's own reference (``last_logits`` / ``routing_margins``), and
``tests/reference_impl.py np_forward_deepseek2`` on weights dequantized by
``mformat.dequantize``; the file's bytes against the program's own plan; the
cost arithmetic against the configuration's published sizes.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import ROOT
from harness import mformat, models

BENCH = os.path.join(ROOT, "benchmarks")
DS2_TOY = dict(
    dim=128, hidden_dim=192, n_layers=3, n_heads=4, n_kv_heads=4, n_experts=160,
    n_active_experts=6, vocab_size=288, seq_len=128, rope_theta=10000.0,
    q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, moe_hidden_dim=32, n_shared_experts=2, n_groups=8,
    topk_groups=3, n_dense_layers=1, routed_scale=16.0, rope_factor=40.0,
    rope_orig_seq_len=16, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale=0.707, rope_mscale_all_dim=0.707, norm_eps=1e-6)
DS2_SEED, DS2_PROMPT, DS2_DECODE = 33, 16, 24

# Logits are compared in sigmas: the reference's own spread over the
# vocabulary at that position, as harness/correct.py does on the chip.
#
# DS2_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone (the program's absorbed
# product and online softmax against the references' expanded form), and the
# experts' weights are scaled by 16.  Measured when this test was written,
# seeds 33-35, prefill and 24 decode steps: 2.6e-6 sigma between the two
# references, 2.0e-6 to 3.1e-6 between the engine and either; the limit
# leaves a factor of three.  The same engine with bfloat16 activations reads
# 2.6e-2 to 3.1e-2: the NEGATIVE CONTROL.
DS2_TOL_SIGMA = 1e-5
# DS2_TOL_Q40_SIGMA, the packed path the cell serves, on MARGIN-STEADY
# positions (both routing stages' margins over MARGIN_STEADY at both expert
# layers): the Q40 matmuls round both operands to bfloat16; a chosen expert's
# weight is its probability x 16, but so is its part of the reference, and
# in sigmas the rounding reads as OLMoE's did: 0.022 to 0.025 over the steady
# positions of seeds 33-35 (13, 16 and 20 of 25 positions), while the
# positions that are not steady read 0.12 to 0.31: a flipped expert, not a
# rounding.  The limit is OLMoE's; ``DS2_MAX_LEFT_OUT`` bounds the share left
# out, so the test cannot pass by comparing nothing.
DS2_TOL_Q40_SIGMA = 0.045
DS2_MAX_LEFT_OUT = 0.6


def _tests_reference():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ds2_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("deepseek_v2")
    path = str(tmp_path_factory.mktemp("ds2") / "ds2-toy.m")
    mformat.synthesize(path, model, DS2_TOY, DS2_SEED, workers=2)
    return model, path


def _ds2_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _ds2_dequantized(model, path: str) -> dict:
    """The file's weights as float32 in the runtime layout
    ``np_forward_deepseek2`` takes, through the benchmark's reader and plain
    numpy."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(DS2_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    def stack(key, layers, name=None):
        return np.stack([tensor(f"layers.{i}.{name or key}").T for i in layers])

    n, nd = DS2_TOY["n_layers"], DS2_TOY["n_dense_layers"]
    att, dense, moe = range(n), range(nd), range(nd, n)
    out = {k: stack(k, att) for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
    out.update({k: stack(k, dense) for k in ("w1", "w2", "w3")})
    out.update({k: stack(k, moe) for k in ("shared_w1", "shared_w2", "shared_w3")})
    out["router"] = stack("router", moe, "moe_router")
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack(
            [tensor(f"layers.{i}.experts.{e}.{key}").T
             for e in range(DS2_TOY["n_experts"])]) for i in moe])
    for key in ("rms_att", "rms_ffn", "q_a_norm", "kv_a_norm"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in att])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


@pytest.fixture(scope="module")
def ds2_references(ds2_toy):
    model, path = ds2_toy
    rng = np.random.RandomState(DS2_SEED)
    toks = [int(t) for t in rng.randint(3, DS2_TOY["vocab_size"],
                                        DS2_PROMPT + DS2_DECODE)]
    logits, margins = model.routing_margins(path, [toks])
    weights, cfg = _ds2_dequantized(model, path), _ds2_cfg(path)
    full = _tests_reference().np_forward_deepseek2(weights, cfg, np.asarray(toks))
    return toks, logits[0], margins[0], full, weights, cfg


def _ds2_engine_logits(path: str, toks: list[int], steps: int, dtype,
                       packed: bool) -> np.ndarray:
    """The program's logits after the prompt and after each of ``steps``
    decoded tokens (seeded, not greedy), ``(steps + 1, vocab)``."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    mf = mfile.MFile(path)
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                              dtype=dtype, keep_quantized=packed)
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 seq_len=DS2_TOY["seq_len"])
    logits, _ = eng.prefill(toks[:DS2_PROMPT])
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[DS2_PROMPT:DS2_PROMPT + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return np.stack(got)


def _sigmas(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.abs(got - ref).max(-1) / ref.std(-1)


def test_ds2_file_bytes_are_what_the_program_parses(ds2_toy):
    """Header keys 0..31 and every tensor's name, shape, type, offset and size
    as the program's own ``tensor_plan`` has them; the module's reader reads
    back what its packer wrote, floats to the bit."""
    from dllama_tpu.io import mfile

    model, path = ds2_toy
    mf = mfile.MFile(path)
    for key, want in dict(DS2_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        got = getattr(mf.spec, key)
        assert got == (np.float32(want) if isinstance(want, float)
                       and key != "rope_theta" else want), key
    assert mf.spec.arch == mfile.ARCH_DEEPSEEK2 == model.ARCH_DEEPSEEK2
    assert [k for k, _, _ in model.EXT_KEYS] == [k for k, _, _ in mfile.EXT_KEYS]
    assert [n for _, n, _ in model.EXT_KEYS] == [n for _, n, _ in mfile.EXT_KEYS]
    assert mf.spec.header_size == len(model.header(DS2_TOY)) == 8 + 8 * 32
    ours = model.plan(DS2_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)
    hd = model.read_header(path)
    for key in model.SHAPE_KEYS:
        want = DS2_TOY[key]
        assert hd[key] == (np.float32(want) if isinstance(want, float)
                           and key != "rope_theta" else want), key
    # the harness's own reader stops at key 13 and is not used for this file
    assert len(mformat.HEADER_KEYS) == 14


def test_ds2_shape_reads_the_published_config_and_refuses_others():
    model = models.load("deepseek_v2")
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        cfg = json.load(f)
    shp = model.shape(cfg)
    assert (shp["dim"], shp["n_heads"], shp["q_lora_rank"], shp["kv_lora_rank"]) == \
        (5120, 128, 1536, 512)
    assert (shp["qk_nope_head_dim"], shp["qk_rope_head_dim"], shp["v_head_dim"]) == \
        (128, 64, 128)
    assert (shp["n_experts"], shp["n_active_experts"], shp["n_groups"],
            shp["topk_groups"], shp["n_shared_experts"]) == (160, 6, 8, 3, 2)
    assert (shp["hidden_dim"], shp["moe_hidden_dim"], shp["n_dense_layers"],
            shp["n_layers"], shp["vocab_size"]) == (12288, 1536, 1, 5, 102400)
    assert (shp["rope_factor"], shp["rope_orig_seq_len"], shp["routed_scale"],
            shp["norm_eps"]) == (40.0, 4096, 16.0, 1e-6)
    size = model.plan(shp)[-1]
    assert 11.5e9 < size[3] + size[4] < 11.6e9      # 11.55 GB on disk
    for key, bad in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                     ("norm_topk_prob", True), ("moe_layer_freq", 2),
                     ("attention_bias", True)):
        with pytest.raises(SystemExit, match="deepseek_v2"):
            model.shape(dict(cfg, **{key: bad}))
    # the rehearsal keeps the experts, the groups and both layer kinds
    toy = dict(shp, **model.REHEARSE)
    assert (toy["n_experts"], toy["n_groups"], toy["topk_groups"],
            toy["n_dense_layers"], toy["n_layers"]) == (160, 8, 3, 1, 3)


def test_ds2_last_logits_is_the_every_position_pass(ds2_toy, ds2_references):
    model, path = ds2_toy
    toks, logits, margins, _, _, _ = ds2_references
    assert margins.shape == (DS2_PROMPT + DS2_DECODE, DS2_TOY["n_layers"])
    assert (margins[:, 0] == 1e9).all() and (margins[:, 1:] < 10).all()
    for n in (DS2_PROMPT, DS2_PROMPT + DS2_DECODE):
        last = model.last_logits(path, [toks[:n]])[0]
        assert _sigmas(last[None], logits[n - 1][None])[0] <= DS2_TOL_SIGMA


def test_ds2_engine_and_two_references_agree_in_float32(ds2_toy, ds2_references):
    import jax.numpy as jnp

    toks, logits, _, full, _, _ = ds2_references
    between = _sigmas(full, logits).max()
    assert between <= DS2_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _ds2_engine_logits(ds2_toy[1], toks, DS2_DECODE, jnp.float32, packed=False)
    at = slice(DS2_PROMPT - 1, DS2_PROMPT + DS2_DECODE)
    worst = max(_sigmas(engine, logits[at]).max(), _sigmas(engine, full[at]).max())
    assert worst <= DS2_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_ds2_tolerance_fails_bfloat16_activations(ds2_toy, ds2_references):
    """NEGATIVE CONTROL for ``DS2_TOL_SIGMA``: the program with bfloat16
    activations and a bfloat16 latent cache, the next precision below the
    float32 that run states."""
    import jax.numpy as jnp

    toks, logits, _, _, _, _ = ds2_references
    engine = _ds2_engine_logits(ds2_toy[1], toks, 0, jnp.bfloat16, packed=False)
    assert _sigmas(engine, logits[DS2_PROMPT - 1][None])[0] > 100 * DS2_TOL_SIGMA


@pytest.mark.parametrize("left_out", ("groups", "scale", "shared", "mscale", "yarn"))
def test_ds2_reference_with_a_piece_left_out_disagrees(ds2_references, left_out):
    """Each piece the block adds is live at these sizes: ``np_forward_deepseek2``
    without the group stage, the x16, the shared expert, ``mscale^2`` or the
    YaRN blend is no longer the module's block."""
    toks, logits, _, _, weights, cfg = ds2_references
    other = _tests_reference().np_forward_deepseek2(
        weights, cfg, np.asarray(toks), **{left_out: False})
    # from the second position on (the first attends to itself alone); the
    # YaRN blend shows once positions pass the original length of 16
    at = slice(DS2_PROMPT, None) if left_out in ("yarn", "mscale") else slice(1, None)
    assert _sigmas(other, logits)[at].max() > 1000 * DS2_TOL_SIGMA


def test_ds2_packed_engine_agrees_on_margin_steady_positions(ds2_toy, ds2_references):
    import jax.numpy as jnp

    model, path = ds2_toy
    toks, logits, margins, _, _, _ = ds2_references
    at = slice(DS2_PROMPT - 1, DS2_PROMPT + DS2_DECODE)
    steady = margins[at].min(-1) > model.MARGIN_STEADY
    left_out = 1.0 - steady.mean()
    assert left_out <= DS2_MAX_LEFT_OUT, (
        f"{left_out:.0%} of {steady.size} positions have a routing margin "
        f"under {model.MARGIN_STEADY}")
    engine = _ds2_engine_logits(path, toks, DS2_DECODE, jnp.float32, packed=True)
    worst = _sigmas(engine, logits[at])[steady].max()
    assert worst <= DS2_TOL_Q40_SIGMA, (
        f"{worst:.4f} sigma over {int(steady.sum())} margin-steady positions")


def test_ds2_paged_slots_match_the_contiguous_engine(ds2_toy):
    """Six greedy requests through the paged slot path (chunked prefill, mixed
    steps, pure decode over the latent pool; six rows a step, so ``moe_ffn``
    scans the 160 packed experts) against the same prompts alone on the
    contiguous engine: equal tokens, and a cached token is layers x 80 x 4
    bytes in both."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    mf = mfile.MFile(ds2_toy[1])
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=jnp.float32),
                              dtype=jnp.float32, keep_quantized=True)
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    rng = np.random.RandomState(DS2_SEED + 1)
    prompts = [[int(t) for t in rng.randint(3, DS2_TOY["vocab_size"], n)]
               for n in (5, 9, 6, 12, 7, 10)]
    new = 10
    solo = Engine(cfg, params, mesh=mesh, seq_len=DS2_TOY["seq_len"])
    want = []
    for p in prompts:
        solo.reset()
        want.append([t for t, _ in solo.generate_stream(
            p, len(p) + new, temperature=0.0, chunk=4)][len(p):])
    before = obs_dispatch.dispatches()
    page = 4
    paged = Engine(cfg, params, mesh=mesh, seq_len=DS2_TOY["seq_len"],
                   batch=len(prompts), kv_page_size=page,
                   kv_pages=len(prompts) * (DS2_TOY["seq_len"] // page) + 1)
    per_token = DS2_TOY["n_layers"] * (64 + 16) * 4
    assert solo.kv_bytes_per_token == paged.kv_bytes_per_token == per_token
    sched = SlotScheduler(paged, prefill_chunk=4, decode_burst=4)
    try:
        tickets = [sched.submit(p, new, temperature=0.0) for p in prompts]
        got = [list(t.tokens()) for t in tickets]
    finally:
        sched.close()
    assert got == want
    after = obs_dispatch.dispatches()
    assert after.get("moe/scan", 0) > before.get("moe/scan", 0)
    assert after.get("attn/mla-absorbed", 0) > before.get("attn/mla-absorbed", 0)


def test_ds2_cost_arithmetic_at_the_published_sizes():
    """The numbers ISSUE 33 and PERF.md reckon with, from the configuration
    file: bytes a parameter 18/32."""
    model = models.load("deepseek_v2")
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        cfg = json.load(f)
    q = 18 / 32
    att = 5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768 + 16384 * 5120
    assert att == 149_225_472
    expert = 3 * 5120 * 1536
    assert model.kv_bytes_per_token(cfg) == 5760
    assert model.kv_bytes_per_token(cfg, elem_bytes=4) == 11520
    assert model.experts_read(cfg, 1) == pytest.approx(6.0)
    assert model.experts_read(cfg, 16) == pytest.approx(73.2, abs=0.1)
    moe16 = 4 * (160 * 5120 + model.experts_read(cfg, 16) * expert + 2 * expert) * q
    assert model.moe_bytes(cfg, rows=16) == pytest.approx(moe16)
    assert 3.9e9 < model.moe_bytes(cfg, rows=16) < 4.0e9
    dense = (5 * att + 3 * 5120 * 12288 + 102400 * 5120) * q
    assert model.weight_bytes(cfg, rows=16) == pytest.approx(dense + moe16)
    assert model.step_bytes(cfg, 16 * 400, rows=16) == pytest.approx(
        dense + moe16 + 5760 * 6400)
    pair = 2 * 128 * (512 + 576)
    row = 5 * att + 3 * 5120 * 12288 + 4 * (160 * 5120 + 8 * expert) + 102400 * 5120
    assert model.step_flops(cfg, 16, 6400) == pytest.approx(
        2.0 * row * 16 + 5 * pair * 6400)
    # the latent walk and the absorb of a 16-row step at 400 tokens of context
    kvb = 512 * 128 * 256
    assert model.mla_bytes(cfg, 16, 400) == pytest.approx(
        5 * (16 * 400 * 576 * 2 + kvb * q + 16 * 128 * (192 + 128) * 2))
    assert model.mla_flops(cfg, 16, 400) == pytest.approx(
        5 * 16 * (pair * 400 + 2.0 * kvb))
    # 128 heads on one latent row: 2 x 128 x 1088 FLOP over 1152 bytes
    assert pair / (576 * 2) == pytest.approx(241.8, abs=0.1)


def test_ds2_readers_return_nothing_where_the_program_names_nothing():
    """The three readers on a context without a device plane, a program
    without the gauge, or a configuration without MLA arithmetic: ``None``,
    never an exception (the parent of the PR that added them)."""
    import serve_kv_bytes_per_tok
    import serve_mla_latent_roof_pct
    import serve_mla_ms_per_step

    ctx = {"trace": {"chips": 0}, "after": {}, "before": {}, "samples": [],
           "window": (0.0, 1.0), "traced_window": (0.2, 0.4), "records": [],
           "config": {"model": "deepseek_v2"}, "peaks": None, "cell": {}}
    assert serve_mla_ms_per_step.read(ctx) is None
    assert serve_mla_latent_roof_pct.read(ctx) is None
    assert serve_kv_bytes_per_tok.read(ctx) is None
    assert serve_kv_bytes_per_tok.read(dict(ctx, after={"kv_bytes_per_token": 5760})) == 5760
    dense = dict(ctx, config={})  # models/dense.py has no mla_bytes
    assert serve_mla_latent_roof_pct.read(dense) is None
    recs = [{"n_prompt": 100, "times": [0.1, 0.25, 0.35]},
            {"n_prompt": 50, "times": [0.9]}]
    assert serve_mla_latent_roof_pct._mean_context(dict(ctx, records=recs)) == 102
