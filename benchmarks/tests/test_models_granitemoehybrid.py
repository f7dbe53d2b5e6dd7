"""``models/granitemoehybrid.py`` against the program, on the CPU at toy widths
that keep every ratio (periods of five with the attention layer at 2, two
periods, four query heads a kv head, a mixer of 8 heads of 8 in ONE group with a
state of 12 rows, 4 taps, 12 experts of which 3 a token beside a shared MLP two
experts wide, every multiplier the file carries off 1; these tests import JAX
and ``dllama_tpu``).  Three independent forward passes on one seeded file the
module wrote, its ``ssm_a_log`` / ``ssm_dt_bias`` redrawn as
``tools/check_ssm_layers.py`` draws them again (the seeded decay hides the
state): the program (a chunked prefill and decoding on the contiguous engine
past a fold of its lagged state), the module's own reference (``logits_at``:
the attention form, no state, no ring, no pages), and ``tests/reference_impl.py
np_forward_granite_hybrid`` on weights dequantized by ``mformat.dequantize``;
the configuration file against the catalog's rules; the cost functions at the
published sizes; the new reader.
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

GH_TOY = dict(dim=64, hidden_dim=64, n_layers=10, n_heads=8, n_kv_heads=2,
              n_experts=12, n_active_experts=3, vocab_size=288, seq_len=512,
              rope_theta=10000, moe_hidden_dim=32, n_shared_experts=2,
              norm_eps=1e-5, head_dim=8, window_period=5, window_full_at=2,
              ssm_heads=8, ssm_head_dim=8, ssm_state=12, ssm_groups=1, ssm_conv=4,
              mup_embedding=12.0, mup_head=0.25, mup_attn_out=0.5,
              mup_ssm_out=0.6, mup_key=0.3 * 8 ** 0.5, mup_down=0.7)
GH_SEED, GH_PROMPT, GH_DECODE = 65, 150, 12
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.  Float32 end to end
# (the engine loads the file dequantized, so all three sides read the same 4-bit
# weights exactly): they differ by the order of float32 sums alone, but for a
# row whose k-th and (k+1)-th router logits tie within those sums.  Read when
# this test was written: the two references 2e-5 sigma apart, the engine 2e-5
# from either; the reference with bfloat16 activations reads 3e-2, a softmax
# over all experts 1e-1: the NEGATIVE CONTROLS.
GH_TOL_SIGMA = 2e-4
GH_CONFIG = os.path.join(BENCH, "configs", "granite-4.0-h-small.json")
GH_CELL = os.path.join(BENCH, "cells", "granite-4.0-h-small.decode-heavy.json")


def _gh_ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gh_mixers():
    return [i for i in range(GH_TOY["n_layers"]) if i % 5 != 2]


@pytest.fixture(scope="module")
def gh_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote, its decay redrawn
    in the mixer layers)``: ``A`` in 0.5 .. 2, ``dt`` near 0.01 .. 0.1, so the
    state hundreds of positions deep matters."""
    model = models.load("granitemoehybrid")
    path = str(tmp_path_factory.mktemp("granite") / "gh-toy.m")
    mformat.synthesize(path, model, GH_TOY, GH_SEED, workers=2)
    rng = np.random.default_rng(5)
    raw = np.memmap(path, np.uint8, "r+")
    by_name = {t[0]: t for t in model.plan(GH_TOY)}
    for i in _gh_mixers():
        for name, vals in (("ssm_a_log", np.log(rng.uniform(0.5, 2.0, 8))),
                           ("ssm_dt_bias", np.log(np.expm1(rng.uniform(0.01, 0.1, 8))))):
            _, _, _, off, nbytes = by_name[f"layers.{i}.{name}"]
            raw[off:off + nbytes].view(np.float32)[:] = vals.astype(np.float32)
    raw.flush()
    return model, path


def _gh_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks (attention and mixer tensors
    by layer kind), read by the benchmark's own reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(GH_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    every, mix = range(GH_TOY["n_layers"]), _gh_mixers()
    att = [i for i in every if i not in mix]
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in att])
           for k in ("wq", "wk", "wv", "wo")}
    out.update({k: np.stack([tensor(f"layers.{i}.{k}").T for i in mix])
                for k in ("ssm_in", "ssm_dt", "ssm_out")})
    for key in ("ssm_conv_b", "ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in mix])
    out["ssm_conv_w"] = np.stack([tensor(f"layers.{i}.ssm_conv_w").reshape(-1, 4)
                                  for i in mix])
    for key in ("rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in every])
    out["router"] = np.stack([tensor(f"layers.{i}.moe_router").T for i in every])
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack([
            tensor(f"layers.{i}.experts.{e}.{key}").T for e in range(12)])
            for i in every])
    for key in ("shared_w1", "shared_w2", "shared_w3"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}").T for i in every])
    out["embedding"] = tensor("token_embedding")
    out["rms_final"] = tensor("rms_final")
    out["wcls"] = tensor("wcls").T
    return out


def _gh_sigmas(got, want):
    return np.abs(got - want).max(-1) / want.std(-1)


def _gh_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


@pytest.fixture(scope="module")
def gh_references(gh_toy):
    """``(tokens, the module's logits at every position, the numpy
    reference's)``."""
    model, path = gh_toy
    toks = np.random.default_rng(7).integers(3, GH_TOY["vocab_size"],
                                             GH_PROMPT + GH_DECODE).tolist()
    logits = model.logits_at(path, [toks], range(len(toks)))[0]
    full = _gh_ref_impl().np_forward_granite_hybrid(
        _gh_dequantized(model, path), _gh_cfg(path), np.asarray(toks))
    return toks, logits, full


def test_granite_header_and_plan_are_what_the_program_parses(gh_toy):
    from dllama_tpu.io import mfile

    model, path = gh_toy
    assert set(models.EXPORTS) <= set(vars(model))
    mf = mfile.MFile(path)
    for key, want in dict(GH_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want, rel=1e-6), key
    assert mf.spec.arch == mfile.ARCH_GRANITE_HYBRID == model.ARCH_GRANITE_HYBRID
    assert mf.spec.header_size == len(model.header(GH_TOY))
    assert tuple(k for k, _, _ in model.EXT_KEYS) \
        == mfile.ARCH_EXT_KEYS[mfile.ARCH_GRANITE_HYBRID]
    assert [n for _, n, _ in model.EXT_KEYS] == [
        n for k, n, _ in mfile.ALL_EXT_KEYS if k in
        mfile.ARCH_EXT_KEYS[mfile.ARCH_GRANITE_HYBRID]]
    assert model.read_header(path)["ssm_state"] == 12
    ours = model.plan(GH_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    shapes = dict((t[0], t[1]) for t in ours)
    assert shapes["layers.0.ssm_in"] == (64 + 88, 64) and "layers.0.wq" not in shapes
    assert shapes["layers.2.wq"] == (64, 64) and "layers.2.ssm_in" not in shapes
    assert shapes["layers.2.shared_w1"] == (64, 64) == shapes["layers.3.shared_w3"]
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_granite_configuration_keeps_every_published_key_but_the_depth():
    """The catalog's rule: every number of the published config under the same
    key, but for the keys of ``reduced``, whose published values are kept
    beside them; depth only: no width, no head, no expert, no vocabulary row."""
    with open(GH_CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    assert config["num_hidden_layers"] == 20          # a stage of two
    assert len(config["layer_types"]) == 40           # whole; the first 20 served
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"granite-4.0-h-small"' in l)
        assert config["source"] == row["source_url"]
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert {"in_proj_split", "gated_norm", "router", "experts", "multipliers",
            "positions", "head", "state_precision", "mamba_chunk_size",
            "seeded_decay", "seeded_logits"} <= set(config["assumed"])
    assert "two stages of 20 layers" in config["deployment"]
    with open(GH_CELL) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "cells", "lfm2-24b-a2b.decode-heavy.json")) as f:
        assert cell["argv"] == json.load(f)["argv"]   # letter for letter
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small", "decode-heavy", 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["workloads"][-1]["name"] == "granite-4.0-h-small.decode-heavy"
    assert manifest["configs"][-1]["name"] == "granite-4.0-h-small"
    assert manifest["per_layer"][-1]["name"] == "serve_ssm_share_pct"


def test_granite_shape_reads_the_published_keys_and_refuses_by_name(monkeypatch):
    with open(GH_CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["moe_hidden_dim"],
            shp["n_shared_experts"], shp["n_layers"], shp["n_experts"],
            shp["n_active_experts"], shp["vocab_size"], shp["head_dim"],
            shp["window_period"], shp["window_full_at"], shp["ssm_heads"],
            shp["ssm_head_dim"], shp["ssm_state"], shp["ssm_groups"]) == (
        4096, 1536, 768, 2, 20, 72, 10, 100352, 128, 10, 5, 128, 64, 128, 1)
    assert shp["mup_key"] == pytest.approx(128 ** -0.5) and shp["mup_head"] == 1 / 16
    assert shp["mup_attn_out"] == shp["mup_ssm_out"] == shp["mup_down"] == 0.22
    plan = model.plan(shp)
    assert 10.8e9 < plan[-1][3] + plan[-1][4] < 11.0e9      # 10.9 GB on disk

    def q40(prefix):
        return sum(int(np.prod(t[1])) for t in plan
                   if t[0].startswith(prefix) and t[2] == mformat.Q40)

    # the issue's table, a row at a time (W_in's 128 dt rows are float32 here)
    assert q40("layers.0.ssm_") == 101_711_872               # 101.71 M, 57.2 MB
    assert q40("layers.5.w") == 41_943_040                   # 41.94 M, 23.6 MB
    assert q40("layers.0.shared_") == 18_874_368             # 18.87 M, 10.6 MB
    assert q40("layers.0.experts.0.") == 9_437_184           # 9.437 M, 5.31 MB
    assert round(q40("layers.0.") * 18 / 32 / 1e6, 1) == 450.2   # a mixer layer
    assert round(q40("layers.5.") * 18 / 32 / 1e6, 1) == 416.6   # an attention layer
    for patch, says in (
            (dict(position_embedding_type="rope"), "is not nope"),
            (dict(mamba_n_groups=2), "mamba_n_groups is not 1"),
            (dict(attention_bias=True), "attention_bias"),
            (dict(shared_intermediate_size=1000), "whole number of experts"),
            (dict(mamba_conv_bias=False), "convolution bias"),
            (dict(layer_types=["mamba"] * 40), "does not cover")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))
    # a checkout whose program lacks the arch id fails at once, by name
    monkeypatch.setattr(model, "_program_has_the_arch", lambda: False)
    with pytest.raises(SystemExit, match="no arch id 0xABCD0B .unknown arch id."):
        model.shape(config)


def test_granite_engine_and_two_references_agree_in_float32(gh_toy, gh_references):
    """The contiguous engine (the prompt in chunks of 32 and a bucketed tail past
    a fold of its state, then token by token) against both references."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.runtime.engine import Engine

    model, path = gh_toy
    toks, logits, full = gh_references
    at = range(GH_PROMPT - 1, len(toks))
    assert _gh_sigmas(logits, full).max() <= GH_TOL_SIGMA
    last = model.last_logits(path, [toks[:GH_PROMPT]])[0]
    assert _gh_sigmas(last[None], logits[GH_PROMPT - 1][None])[0] <= GH_TOL_SIGMA
    with mfile.MFile(path) as mf:
        cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    with jax.default_matmul_precision("highest"):
        eng = Engine(cfg.with_(quant_impl="xla"), params, batch=1)
        rows = [eng.prefill(toks[:GH_PROMPT])[0][0]]
        for tok in toks[GH_PROMPT:]:
            rows.append(eng.decode_one(int(tok))[0][0])
        assert eng._state_lo >= 64                   # a block was folded
        assert (eng.cache.rs.shape[0], eng.cache.k.shape[0]) == (8, 2)
    assert _gh_sigmas(np.stack(rows), logits[at]).max() <= GH_TOL_SIGMA


@pytest.mark.parametrize("fault", ["softmax_all", "rope", "no_residual", "no_key",
                                   "bfloat16"])
def test_granite_tolerance_fails_each_wrong_computation(gh_toy, gh_references, fault):
    """The reference with one thing wrong (the softmax over ALL experts, q and k
    rotated, a multiplier set to 1) or with bfloat16 activations (the nearest
    precision below the configuration's float32 here) is out of the tolerance
    the engine is held to."""
    import jax.numpy as jnp

    model, path = gh_toy
    toks, logits, _ = gh_references
    at = [40, 99, GH_PROMPT]
    kw = dict(act_dtype=jnp.bfloat16) if fault == "bfloat16" else dict(wrong=fault)
    bad = model.logits_at(path, [toks], at, **kw)[0]
    assert _gh_sigmas(bad, logits[at]).max() > 10 * GH_TOL_SIGMA


def test_granite_cost_functions_at_the_published_sizes():
    with open(GH_CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    q = 18 / 32
    att = 2 * 4096 * 4096 + 2 * 4096 * 1024            # 41.94 M
    w_in, w_out, dt = 4096 * 16640, 8192 * 4096, 128 * 4096
    router, expert, shared = 72 * 4096, 3 * 4096 * 768, 3 * 4096 * 1536
    head = 100352 * 4096
    assert model.layer_kinds(cfg) == (2, 18)
    hit = 72 * (1 - (62 / 72) ** 16)
    assert model.experts_read(cfg, 16) == pytest.approx(hit) and 65.3 < hit < 65.5
    assert model.moe_bytes(cfg, rows=16) == pytest.approx(
        20 * (router + hit * expert + shared) * q)      # over all 20 layers
    assert 6.9e9 < 20 * hit * expert * q < 7.0e9        # the issue's 6.9 GB
    assert model.kv_bytes_per_token(cfg) == 8_192       # 2 layers x 4,096 B
    state = 128 * 128 * 64 * 4
    assert state == 4_194_304                           # 4.19 MB a mixer layer a row
    conv = 3 * 8448 * 2
    recent = 32 * ((8192 + 128) * 2 + 4 * 128)
    assert model.ssm_bytes(cfg, 16) == pytest.approx(
        18 * ((w_in + w_out) * q + 4 * dt + 16 * (state + conv + recent)))
    assert 2.4e9 < model.ssm_bytes(cfg, 16) < 2.5e9     # over the 18 mixer layers
    assert model.ssm_flops(cfg, 16) == pytest.approx(
        2.0 * 18 * 16 * (w_in + w_out + dt + 128 * (128 * 64 + 32 * 192)))
    assert model.ssm_bytes(cfg, 16) / 819e9 > 5 * model.ssm_flops(cfg, 16) / 197e12
    assert model.weight_bytes(cfg, rows=16) == pytest.approx(
        (2 * att + 18 * (w_in + w_out) + head) * q + 4 * 18 * dt
        + model.moe_bytes(cfg, rows=16))
    live = 16 * 416
    total = model.step_bytes(cfg, live, 1, 16)
    assert total == pytest.approx(
        model.weight_bytes(cfg, rows=16) + 18 * 16 * (state + conv + recent)
        + 8_192 * live)
    assert model.kv_read_bytes(cfg, 416, rows=16) == 8_192 * live
    # the least a step moves: experts two thirds, the mixer a quarter
    assert 9.4e9 < total < 10.0e9
    assert 0.70 < model.moe_bytes(cfg, rows=16) / total < 0.76
    assert 0.22 < model.ssm_bytes(cfg, 16) / total < 0.27
    assert model.step_flops(cfg, 16, live) == pytest.approx(
        2.0 * (16 * (2 * att + 20 * (router + 10 * expert + shared) + head)
               + 2 * 2 * 4096 * live) + model.ssm_flops(cfg, 16))


@pytest.mark.parametrize("ms", [5.0, None], ids=["change", "parent"])
def test_granite_reader_of_the_mixers_share(ms, monkeypatch):
    """``serve_ssm_share_pct``: the device time ``serve_ssm_ms_per_step`` sums,
    over every step of the window, over the table's busy time; a program
    without the parts (the parent) gives nothing and does not raise."""
    share = importlib.import_module("serve_ssm_share_pct")
    monkeypatch.setattr(share.serve_ssm_ms_per_step, "read", lambda ctx: ms)
    monkeypatch.setattr(share, "table", lambda ctx: {"steps": 4, "busy_s": 0.05})
    if ms is None:
        assert share.read({}) is None
        return
    assert share.read({}) == pytest.approx(100 * 0.020 / 0.05)
    monkeypatch.setattr(share, "table", lambda ctx: None)
    assert share.read({}) is None
