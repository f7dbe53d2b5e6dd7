"""``models/exaone_moe.py`` against the program, on the CPU at toy widths that
keep every ratio (periods of three window layers and then a full one, a dense
first layer, 32 router outputs of which 8 a token and 4 held here from the
fourth, one shared expert, 8 query heads a kv head, a head size that is not
``dim / n_heads``; these tests import JAX and ``dllama_tpu``).  Three
independent forward passes on one seeded file the module wrote: the program
(a chunked prefill and decoding on the contiguous engine; the slot programs
over the pool per layer kind past a wrapped ring of pages), the module's own
reference (``last_logits`` / ``logits_at`` / ``routing_margins``), and
``tests/reference_impl.py np_forward_exaone_moe`` on weights dequantized by
``mformat.dequantize``; the configuration file against the catalog's rules; the
cost functions at the published sizes; the four new readers.
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

EX_TOY = dict(dim=64, hidden_dim=96, n_layers=8, n_heads=16, n_kv_heads=2,
              vocab_size=288, seq_len=96, rope_theta=1000000.0, n_experts=32,
              n_active_experts=8, moe_hidden_dim=32, n_shared_experts=1,
              n_groups=1, topk_groups=1, n_dense_layers=1, routed_scale=2.5,
              norm_eps=1e-5, head_dim=8, window=16, window_period=4,
              experts_held=4, first_expert=4, window_full_at=3)
EX_SEED, EX_PROMPT, EX_DECODE = 40, 41, 19
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# EX_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone.  Measured when this test was
# written: 4e-6 sigma between the two references, 5e-6 between the program and
# either.  The same engine with bfloat16 activations reads 1e-2 and more: the
# NEGATIVE CONTROL.  Each of eight wrong computations reads hundredths of a
# sigma or more (tests/test_exaone_moe.py).
EX_TOL_SIGMA = 2e-5
# EX_TOL_Q40_SIGMA, the packed path the cell serves, on MARGIN-STEADY positions
# (as SmallThinker's test); EX_MAX_LEFT_OUT bounds the share left out.
EX_TOL_Q40_SIGMA = 0.06
EX_MAX_LEFT_OUT = 0.75
# a prefill chunk of 16 rows: rings of 16 + 16 positions under sequences of 60
EX_SMALL_PRODUCT = 4 * 4 * 64 * 16
CONFIG = os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")


def _ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ex_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("exaone_moe")
    path = str(tmp_path_factory.mktemp("exaone") / "ex-toy.m")
    mformat.synthesize(path, model, EX_TOY, EX_SEED, workers=2)
    return model, path


def _ex_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _ex_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks (a segment's stack indexed
    within the segment), read by the benchmark's own reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(EX_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(EX_TOY["n_layers"])
    dense = range(EX_TOY["n_dense_layers"])
    moe = range(EX_TOY["n_dense_layers"], EX_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo")}
    for key in ("q_norm", "k_norm", "rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    for key in ("w1", "w2", "w3"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}").T for i in dense])
    out["router"] = np.stack([tensor(f"layers.{i}.moe_router").T for i in moe])
    out["router_bias"] = np.stack([tensor(f"layers.{i}.moe_router_bias") for i in moe])
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack(
            [tensor(f"layers.{i}.experts.{e}.{key}").T
             for e in range(EX_TOY["experts_held"])]) for i in moe])
    for key in ("shared_w1", "shared_w2", "shared_w3"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}").T for i in moe])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


@pytest.fixture(scope="module")
def ex_references(ex_toy):
    model, path = ex_toy
    rng = np.random.RandomState(EX_SEED)
    toks = [int(t) for t in rng.randint(3, EX_TOY["vocab_size"],
                                        EX_PROMPT + EX_DECODE)]
    logits, margins = model.routing_margins(path, [toks])
    weights, cfg = _ex_dequantized(model, path), _ex_cfg(path)
    full = _ref_impl().np_forward_exaone_moe(weights, cfg, np.asarray(toks))
    return toks, logits[0], margins[0], full, weights, cfg


def _ex_load(path: str, dtype, packed: bool):
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params

    mf = mfile.MFile(path)
    return load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                       dtype=dtype, keep_quantized=packed)


def _ex_engine_logits(path: str, toks: list[int], steps: int, dtype,
                      packed: bool, monkeypatch) -> np.ndarray:
    """The program's logits after a chunked prefill of the prompt and after
    each of ``steps`` decoded tokens (seeded, not greedy)."""
    import jax

    from dllama_tpu.models import config as config_mod
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", EX_SMALL_PRODUCT)
    cfg, params = _ex_load(path, dtype, packed)
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 seq_len=EX_TOY["seq_len"])
    assert eng.cache.wk.shape[3] == 32 and eng.cache.k.shape[3] == 96
    logits, _ = eng.prefill(toks[:EX_PROMPT])   # 16 + 16 + a tail of 9
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[EX_PROMPT:EX_PROMPT + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return np.stack(got)


def _ex_sigmas(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.abs(got - ref).max(-1) / ref.std(-1)


def test_exaone_header_and_plan_are_what_the_program_parses(ex_toy):
    from dllama_tpu.io import mfile

    model, path = ex_toy
    mf = mfile.MFile(path)
    for key, want in dict(EX_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want), key
    assert mf.spec.arch == mfile.ARCH_EXAONE_MOE == model.ARCH_EXAONE_MOE
    assert mf.spec.header_size == len(model.header(EX_TOY))
    assert model.read_header(path)["window_full_at"] == 3
    ours = model.plan(EX_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    shapes = dict((t[0], t[1]) for t in ours)
    assert shapes["layers.0.wq"] == (128, 64) and shapes["layers.0.w1"] == (96, 64)
    assert shapes["layers.1.moe_router"] == (32, 64)
    assert "layers.1.experts.3.up" in shapes and "layers.1.experts.4.up" not in shapes
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_exaone_configuration_keeps_every_published_key_but_the_reduced():
    """The catalog's rule: every number of the published config under the same
    key, but for the keys of ``reduced``, whose published values are kept
    beside them; nested groups whole."""
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                                 "num_nextn_predict_layers"]
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                   "vocab_size": 153600,
                                   "num_nextn_predict_layers": 1}
    assert [config[k] for k in config["reduced"]] == [24, 16, 19200, 0]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"K-EXAONE-236B-A23B"' in l)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert len(config["assumed"]) == 4 and "mtp" in config["left_out"]
    assert len(config["layer_types"]) == 48       # groups whole, as published
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "head_dim", "num_experts_per_tok", "num_attention_heads"):
        assert width not in config["reduced"]


def test_exaone_shape_reads_the_share_and_refuses_by_name():
    with open(CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["moe_hidden_dim"], shp["n_layers"],
            shp["n_heads"], shp["n_kv_heads"], shp["head_dim"], shp["window"],
            shp["window_period"], shp["window_full_at"], shp["n_experts"],
            shp["experts_held"], shp["first_expert"], shp["n_active_experts"],
            shp["n_dense_layers"], shp["n_shared_experts"], shp["vocab_size"],
            shp["seq_len"]) == (6144, 18432, 2048, 24, 64, 8, 128, 128, 4, 3, 128,
                                16, 0, 8, 1, 1, 19200, 262144)
    assert shp["routed_scale"] == 2.5 and shp["norm_eps"] == 1e-5
    size = model.plan(shp)[-1]
    assert 10.5e9 < size[3] + size[4] < 10.7e9      # the file: 10.6 GB
    for patch, says in (
            (dict(scoring_func="softmax"), "scoring_func is not sigmoid"),
            (dict(n_group=8), "n_group / topk_group are not 1"),
            (dict(norm_topk_prob=False), "norm_topk_prob is false"),
            (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
            (dict(tie_word_embeddings=True), "the head is tied"),
            (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
             "rope_type is not default"),
            (dict(layer_types=["full_attention"] * 48), "not whole periods"),
            (dict(mlp_layer_types=["sparse"] * 48), "mlp_layer_types is not"),
            (dict(sliding_windows=[128] * 48), "sliding_windows is not"),
            (dict(deployment_share={"first_expert": 120}), "not a run of the router's")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))


def test_exaone_last_logits_and_logits_at_are_the_every_position_pass(
        ex_toy, ex_references):
    model, path = ex_toy
    toks, logits, margins, _, _, _ = ex_references
    n = EX_PROMPT + EX_DECODE
    assert margins.shape == (n, 7)                  # the expert layers'
    last = model.last_logits(path, [toks[:EX_PROMPT]])[0]
    assert _ex_sigmas(last[None], logits[EX_PROMPT - 1][None])[0] <= EX_TOL_SIGMA
    some = model.logits_at(path, [toks], [5, EX_PROMPT, n - 1])[0]
    assert _ex_sigmas(some, logits[[5, EX_PROMPT, n - 1]]).max() <= EX_TOL_SIGMA


def test_exaone_engine_and_two_references_agree_in_float32(
        ex_toy, ex_references, monkeypatch):
    import jax.numpy as jnp

    toks, logits, _, full, _, _ = ex_references
    between = _ex_sigmas(full, logits).max()
    assert between <= EX_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _ex_engine_logits(ex_toy[1], toks, EX_DECODE, jnp.float32,
                               packed=False, monkeypatch=monkeypatch)
    at = slice(EX_PROMPT - 1, EX_PROMPT + EX_DECODE)
    worst = max(_ex_sigmas(engine, logits[at]).max(),
                _ex_sigmas(engine, full[at]).max())
    assert worst <= EX_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_exaone_slot_programs_over_the_pool_per_kind_agree_in_float32(
        ex_toy, ex_references):
    """The paged path the cell serves: chunks of 8 through ``forward_slots``
    over the full layers' pool and a ring of nine pages of 4 (36 positions)
    that wraps inside the 60 tokens, then one token a step."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.transformer import forward_slots, init_kv_pool

    toks, logits, _, _, _, _ = ex_references
    cfg, params = _ex_load(ex_toy[1], jnp.float32, packed=False)
    cache = init_kv_pool(cfg, 30, 4, slots=1, max_pages=24)
    assert cache.k.shape[0] == 2 and cache.wk.shape[:2] == (6, 9)
    table = jnp.asarray(np.random.RandomState(2).permutation(
        np.arange(1, 25)).astype(np.int32)[None])
    errs, pos = [], 0
    with jax.default_matmul_precision("highest"):
        for t in [8] * 5 + [1] * 20:
            lg, cache = forward_slots(
                params, cfg, jnp.asarray(np.asarray(toks[pos:pos + t], np.int32)[None]),
                cache, jnp.full((1,), pos, jnp.int32), jnp.full((1,), t, jnp.int32),
                table)
            pos += t
            errs.append(_ex_sigmas(np.asarray(lg), logits[pos - 1][None])[0])
    assert pos == 60 and max(errs) <= EX_TOL_SIGMA, errs


def test_exaone_tolerance_fails_bfloat16_activations(ex_toy, ex_references,
                                                     monkeypatch):
    """NEGATIVE CONTROL for ``EX_TOL_SIGMA``: the program with bfloat16
    activations, the next precision below the float32 that run states."""
    import jax.numpy as jnp

    toks, logits, _, _, _, _ = ex_references
    engine = _ex_engine_logits(ex_toy[1], toks, 0, jnp.bfloat16, packed=False,
                               monkeypatch=monkeypatch)
    assert _ex_sigmas(engine, logits[EX_PROMPT - 1][None])[0] > 100 * EX_TOL_SIGMA


@pytest.mark.parametrize("wrong", ["rope_on_full", "full_first", "no_head_norm",
                                   "window_plus_one", "bias_in_weights",
                                   "softmax_router", "no_scale", "norm_over_held"])
def test_exaone_reference_with_one_fault_disagrees(ex_references, wrong):
    toks, logits, _, _, weights, cfg = ex_references
    other = _ref_impl().np_forward_exaone_moe(weights, cfg, np.asarray(toks),
                                              wrong=wrong)
    assert _ex_sigmas(other, logits).max() > 100 * EX_TOL_SIGMA


def test_exaone_packed_engine_agrees_on_margin_steady_positions(
        ex_toy, ex_references, monkeypatch):
    import jax.numpy as jnp

    model, path = ex_toy
    toks, logits, margins, _, _, _ = ex_references
    at = slice(EX_PROMPT - 1, EX_PROMPT + EX_DECODE)
    steady = margins[at].min(-1) > model.MARGIN_STEADY
    left_out = 1.0 - steady.mean()
    assert left_out <= EX_MAX_LEFT_OUT, (
        f"{left_out:.0%} of {steady.size} positions have a routing margin "
        f"under {model.MARGIN_STEADY}")
    engine = _ex_engine_logits(path, toks, EX_DECODE, jnp.float32, packed=True,
                               monkeypatch=monkeypatch)
    worst = _ex_sigmas(engine, logits[at])[steady].max()
    assert worst <= EX_TOL_Q40_SIGMA, (
        f"{worst:.4f} sigma over {int(steady.sum())} margin-steady positions")


def test_exaone_cost_functions_at_the_published_sizes():
    with open(CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    q = 18 / 32
    att = 2 * 6144 * 8192 + 2 * 6144 * 1024          # 113.25 M
    one = 3 * 6144 * 2048                            # 37.75 M
    dense, head = 3 * 6144 * 18432, 19200 * 6144
    assert att == 113_246_208 and one == 37_748_736
    assert model.layer_kinds(cfg) == (6, 18)
    # 16 rows of 8 of 128 hit 10.3 of the 16 held under uniform routing
    assert 10.2 < model.experts_read(cfg, 16) < 10.4
    assert model.experts_read(cfg, 1) == pytest.approx(1.0)
    moe = 23 * (128 * 6144 + model.experts_read(cfg, 16) * one + one) * q
    assert model.moe_bytes(cfg, 1, 16) == pytest.approx(moe)
    assert 5.4e9 < moe < 5.7e9
    assert model.weight_bytes(cfg, 1, 16) == pytest.approx(
        (24 * att + dense + head) * q + moe)
    assert model.kv_bytes_per_token(cfg) == 6 * 4096 == 24576
    assert model.kv_read_bytes(cfg, 3900) == (6 * 3900 + 18 * 128) * 4096
    assert model.kv_read_bytes(cfg, 100, rows=16) == 16 * 24 * 100 * 4096
    assert model.step_bytes(cfg, 16 * 3900, 1, 16) == pytest.approx(
        model.weight_bytes(cfg, 1, 16) + 16 * model.kv_read_bytes(cfg, 3900))
    assert model.step_flops(cfg, 1, 3900) == pytest.approx(2.0 * (
        24 * att + dense + head + 23 * (128 * 6144 + one + one)
        + 2 * 64 * 128 * (6 * 3900 + 18 * 128)))


def _fake_parts(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip of a served step program."""
    meta = {1: {"tf_op": "jit(f)/while/body/attn/full/paged_attn_fused"},
            2: {"tf_op": "jit(f)/while/body/attn/window/dot_general"},
            3: {"tf_op": "jit(f)/while/body/attn/transpose"},
            4: {"tf_op": "jit(f)/while/body/moe/experts/q40_mm_experts"}}
    if not with_names:
        meta = {k: {} for k in meta}
    ops = [(1, 0.0, 6e6), (2, 6e6, 3e6), (3, 9e6, 1e6), (4, 10e6, 30e6)]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": []}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_exaone_readers_of_the_attention_parts_and_the_gauge(
        with_names, tmp_path, monkeypatch):
    """``serve_attn_full_ms_per_step`` / ``serve_attn_window_ms_per_step`` read
    the sub-names through ``_parts.py``, ``serve_attn_kv_roof_pct`` the whole
    ``attn`` scope against ``kv_read_bytes`` at the step's rows and mean
    context, ``serve_kv_cache_gb`` the gauge; a program without the names (the
    parent) gives nothing and does not raise."""
    parts = importlib.import_module("_parts")
    scopes = importlib.import_module("_scopes")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(parts.xplane, "find_xplane", lambda out: str(pb))
    monkeypatch.setattr(parts.xmeta, "load",
                        lambda path, keep_host: _fake_parts(with_names))
    parts._SECONDS.clear()
    tab = {"steps": 2, "busy_s": 0.04, "scopes": {"attn": 0.010, "moe": 0.030},
           "scoped": with_names}
    monkeypatch.setattr(scopes, "table", lambda ctx: tab)
    monkeypatch.setattr(parts, "table", lambda ctx: tab)
    monkeypatch.setattr(scopes, "scoped", lambda t: bool(t and t["scoped"]))
    monkeypatch.setattr(parts, "scoped", lambda t: bool(t and t["scoped"]))
    monkeypatch.setattr(scopes, "scope_s",
                        lambda t, names: sum(t["scopes"].get(n, 0.0) for n in names))
    with open(CONFIG) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "traced_window": (100.0, 105.0), "chips": 1,
           "window": (80.0, 125.0), "config": cfg,
           "samples": [(90.0, {"sched_slots_occupied": 16})],
           "records": [{"ok": True, "cut": False, "n_prompt": 3000,
                        "times": [99.0, 101.0, 104.0]}],
           "peaks": {"hbm_bytes_per_s": 819e9},
           "after": {"kv_cache_bytes": {"full": 2.44e9, "window": 0.19e9}}
           if with_names else {}}
    read = {n: importlib.reload(importlib.import_module(n)).read(ctx) for n in (
        "serve_attn_full_ms_per_step", "serve_attn_window_ms_per_step",
        "serve_attn_kv_roof_pct", "serve_kv_cache_gb")}
    if not with_names:
        assert set(read.values()) == {None}
        return
    assert read["serve_attn_full_ms_per_step"] == pytest.approx(3.0)
    assert read["serve_attn_window_ms_per_step"] == pytest.approx(1.5)
    need = models.for_config(cfg).kv_read_bytes(cfg, 3002, 1, rows=16)
    assert read["serve_attn_kv_roof_pct"] == pytest.approx(
        100 * need / 819e9 / 5e-3)
    assert read["serve_kv_cache_gb"] == pytest.approx(2.63)
