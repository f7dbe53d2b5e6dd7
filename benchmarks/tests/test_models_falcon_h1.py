"""``models/falcon_h1.py`` against the program, on the CPU at toy widths that
keep every ratio (five query heads a kv head at a head size that is not dim /
heads, a mixer of 4 heads of 16 in two groups, a state of 24 rows, 4 taps, three
blocks all alike, every multiplier off 1; these tests import JAX and
``dllama_tpu``).  Three independent forward passes on one seeded file the module
wrote, its ``ssm_a_log`` / ``ssm_dt_bias`` redrawn as ``tools/check_ssm.py``
draws them again (the seeded decay hides the state): the program (a chunked prefill
and decoding on the contiguous engine past a fold of its lagged state; the slot
programs on a paged pool with a ragged chunk), the module's own reference
(``last_logits`` / ``logits_at``: the attention form, no state, no ring, no
pages), and ``tests/reference_impl.py np_forward_falcon_h1`` on weights
dequantized by ``mformat.dequantize``; the configuration file against the
catalog's rules; the cost functions at the published sizes; the two new readers.
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

FH_TOY = dict(dim=64, hidden_dim=96, n_layers=3, n_heads=10, n_kv_heads=2,
              vocab_size=288, seq_len=512, norm_eps=1e-5, head_dim=16,
              ssm_heads=4, ssm_head_dim=16, ssm_state=24, ssm_groups=2,
              ssm_conv=4, mup_embedding=5.66, mup_head=0.25, mup_attn_in=0.9,
              mup_attn_out=0.6, mup_ssm_in=0.5, mup_ssm_out=0.8, mup_key=0.7,
              mup_gate=0.6, mup_down=0.45, mup_z=0.7, mup_x=1.5, mup_b=1.4,
              mup_c=1.3, mup_dt=0.7, rope_theta_f32=1e11)
FH_SEED, FH_PROMPT, FH_DECODE = 60, 150, 19
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# FH_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone (the engine sums over its
# state's 24 rows and its ring's 128 where the references sum over positions).
# Read when this test was written: the two references 1.5e-5 sigma apart (one is
# float32 on the device, one float64 inside its double sum), the engine 1.4e-5
# from either.  The same engine with bfloat16 activations reads 2e-2: the
# NEGATIVE CONTROL.
FH_TOL_SIGMA = 1e-4
FH_CONFIG = os.path.join(BENCH, "configs", "falcon-h1-34b.json")
FH_CELL = os.path.join(BENCH, "cells", "falcon-h1-34b.chat-wide.json")


def _fh_ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fh_redraw(model, path: str) -> None:
    """``tools/check_ssm.py``'s redraw at toy ranges: ``A`` in 0.5 .. 2, ``dt``
    near 0.01 .. 0.1, so the state hundreds of positions deep matters."""
    rng = np.random.default_rng(5)
    raw = np.memmap(path, np.uint8, "r+")
    by_name = {t[0]: t for t in model.plan(FH_TOY)}
    for i in range(FH_TOY["n_layers"]):
        for name, vals in (("ssm_a_log", np.log(rng.uniform(0.5, 2.0, 4))),
                           ("ssm_dt_bias", np.log(np.expm1(rng.uniform(0.01, 0.1, 4))))):
            _, _, _, off, nbytes = by_name[f"layers.{i}.{name}"]
            raw[off:off + nbytes].view(np.float32)[:] = vals.astype(np.float32)
    raw.flush()


@pytest.fixture(scope="module")
def fh_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote, its decay
    redrawn)``."""
    model = models.load("falcon_h1")
    path = str(tmp_path_factory.mktemp("falcon") / "fh-toy.m")
    mformat.synthesize(path, model, FH_TOY, FH_SEED, workers=2)
    _fh_redraw(model, path)
    return model, path


def _fh_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _fh_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks, read by the benchmark's own
    reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(FH_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(FH_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo", "ssm_in", "ssm_dt", "ssm_out", "w1",
                     "w2", "w3")}
    for key in ("ssm_conv_b", "ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm",
                "rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    out["ssm_conv_w"] = np.stack([tensor(f"layers.{i}.ssm_conv_w").reshape(-1, 4)
                                  for i in layers])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


def _fh_sigmas(got, want):
    return np.abs(got - want).max(-1) / want.std(-1)


@pytest.fixture(scope="module")
def fh_references(fh_toy):
    """``(tokens, the module's logits at every position, the numpy
    reference's)``."""
    model, path = fh_toy
    toks = np.random.default_rng(7).integers(3, FH_TOY["vocab_size"],
                                             FH_PROMPT + FH_DECODE).tolist()
    logits = model.logits_at(path, [toks], range(len(toks)))[0]
    full = _fh_ref_impl().np_forward_falcon_h1(_fh_dequantized(model, path),
                                               _fh_cfg(path), np.asarray(toks))
    return toks, logits, full


def _fh_engine_logits(path, toks, dtype):
    """The contiguous engine: the prompt in chunks of 32 and a bucketed tail,
    then token by token: logits ``(FH_DECODE + 1, vocab)``."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.runtime.engine import Engine

    with mfile.MFile(path) as mf:
        cfg, params = load_params(mf, dtype=dtype, keep_quantized=False)
    with jax.default_matmul_precision("highest"):
        eng = Engine(cfg.with_(quant_impl="xla"), params, batch=1)
        rows = [eng.prefill(toks[:FH_PROMPT])[0][0]]
        for tok in toks[FH_PROMPT:]:
            rows.append(eng.decode_one(int(tok))[0][0])
        assert eng._state_lo >= 64                   # a block was folded
    return np.stack(rows)


def test_falcon_h1_header_and_plan_are_what_the_program_parses(fh_toy):
    from dllama_tpu.io import mfile

    model, path = fh_toy
    mf = mfile.MFile(path)
    for key, want in dict(FH_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want, rel=1e-6), key
    assert mf.spec.arch == mfile.ARCH_FALCON_H1 == model.ARCH_FALCON_H1
    assert mf.spec.rope_theta == pytest.approx(1e11, rel=1e-6)   # not key 12's i32
    assert mf.spec.header_size == len(model.header(FH_TOY))
    assert tuple(k for k, _, _ in model.EXT_KEYS) \
        == mfile.ARCH_EXT_KEYS[mfile.ARCH_FALCON_H1]
    assert [n for _, n, _ in model.EXT_KEYS] == [
        n for k, n, _ in mfile.ALL_EXT_KEYS if k in
        mfile.ARCH_EXT_KEYS[mfile.ARCH_FALCON_H1]]
    assert model.read_header(path)["ssm_state"] == 24
    ours = model.plan(FH_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    shapes = dict((t[0], t[1]) for t in ours)
    assert shapes["layers.0.ssm_in"] == (224, 64) and shapes["layers.0.ssm_dt"] == (4, 64)
    assert shapes["layers.2.ssm_conv_w"] == (640,) and shapes["layers.0.wo"] == (64, 160)
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_falcon_h1_configuration_keeps_every_published_key_but_the_depth():
    """The catalog's rule: every number of the published config under the same
    key, but for the keys of ``reduced``, whose published values are kept
    beside them; depth only: no width, no head, no group, no vocabulary row."""
    with open(FH_CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 72}
    assert config["num_hidden_layers"] == 18          # a stage of four
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"Falcon-H1-34B-Instruct"' in l)
        assert config["source"] == row["source_url"]
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert set(config["assumed"]) == {
        "in_proj_split", "multipliers", "mamba_use_mlp", "mamba_expand", "rope",
        "state_precision", "dt_clamp", "D", "gated_norm", "mamba_chunk_size",
        "dt_rows_f32", "seeded_decay", "seeded_logits"}
    assert "four stages of 18 blocks" in config["deployment"]
    assert "10.47 GB on disk, 7.79 GB resident" in config["weights"]
    with open(FH_CELL) as f:
        cell = json.load(f)
    assert cell["argv"] == ["--workers", "tpu:1", "--batch-slots", "32",
                            "--kv-pages", "2080", "--kv-page-size", "16",
                            "--max-seq-len", "1024", "--max-pending", "64"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b", "chat-wide", 1)
    with open(os.path.join(BENCH, "traffic", "chat-wide.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["preroll_s"], mix["endpoint"]) == (40, 25, "completions")
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 960 <= 1024
    with open(os.path.join(BENCH, "traffic", "decode-heavy.json")) as f:
        assert mix["warmup"] == json.load(f)["warmup"]


def test_falcon_h1_shape_reads_the_published_keys_and_refuses_by_name(monkeypatch):
    with open(FH_CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["n_layers"], shp["n_heads"],
            shp["n_kv_heads"], shp["vocab_size"], shp["head_dim"], shp["ssm_heads"],
            shp["ssm_head_dim"], shp["ssm_state"], shp["ssm_groups"],
            shp["ssm_conv"]) == (5120, 21504, 18, 20, 4, 261120, 128, 32, 128,
                                 256, 2, 4)
    assert shp["mup_key"] == config["key_multiplier"] and shp["mup_dt"] == \
        config["ssm_multipliers"][4] and shp["mup_down"] == config["mlp_multipliers"][1]
    plan = model.plan(shp)
    assert 10.46e9 < plan[-1][3] + plan[-1][4] < 10.48e9     # 10.47 GB on disk
    q40 = sum(int(np.prod(t[1])) for t in plan
              if t[0].startswith("layers.0.") and t[2] == mformat.Q40)
    # ISSUE 60 counted W_in's 32 dt rows as Q40 (430.08 M): they are float32 here
    assert q40 == 429_916_160 and round(q40 * 18 / 32 / 1e6, 1) == 241.8
    for patch, says in (
            (dict(attention_bias=True), "attention_bias"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
            (dict(tie_word_embeddings=True), "tie_word_embeddings"),
            (dict(mamba_norm_before_gate=True), "mamba_norm_before_gate"),
            (dict(mamba_conv_bias=False), "convolution bias"),
            (dict(mamba_d_ssm=2048), "is not mamba_d_ssm"),
            (dict(num_key_value_heads=3), "not a multiple")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))
    # a checkout whose program lacks the arch id fails at once, by name
    monkeypatch.setattr(model, "_program_has_the_arch", lambda: False)
    with pytest.raises(SystemExit, match="no arch id 0xABCD0A .falcon_h1.: unknown arch id"):
        model.shape(config)


def test_falcon_h1_last_logits_and_logits_at_are_the_every_position_pass(
        fh_toy, fh_references):
    model, path = fh_toy
    toks, logits, _ = fh_references
    n = len(toks)
    last = model.last_logits(path, [toks[:FH_PROMPT]])[0]
    assert _fh_sigmas(last[None], logits[FH_PROMPT - 1][None])[0] <= FH_TOL_SIGMA
    some = model.logits_at(path, [toks], [0, 1, 5, FH_PROMPT, n - 1])[0]
    assert _fh_sigmas(some, logits[[0, 1, 5, FH_PROMPT, n - 1]]).max() <= FH_TOL_SIGMA


def test_falcon_h1_engine_and_two_references_agree_in_float32(fh_toy, fh_references):
    import jax.numpy as jnp

    toks, logits, full = fh_references
    between = _fh_sigmas(full, logits).max()
    assert between <= FH_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _fh_engine_logits(fh_toy[1], toks, jnp.float32)
    at = slice(FH_PROMPT - 1, FH_PROMPT + FH_DECODE)
    worst = max(_fh_sigmas(engine, logits[at]).max(),
                _fh_sigmas(engine, full[at]).max())
    assert worst <= FH_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_falcon_h1_slot_programs_on_a_paged_pool_agree_in_float32(fh_toy, fh_references):
    """A slot of a paged engine: chunks of 16 with a ragged last one, then
    decoded rows, beside a neighbour out of step; state, rings and pages."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import forward_slots, init_kv_pool

    toks, logits, _ = fh_references
    with mfile.MFile(fh_toy[1]) as mf:
        cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    cfg = cfg.with_(quant_impl="xla")
    table = jnp.asarray(1 + np.arange(2)[:, None] * 48 + np.arange(48)[None, :],
                        jnp.int32)
    step = jax.jit(lambda c, tk, pos, n: forward_slots(params, cfg, tk, c, pos, n,
                                                       table))
    with jax.default_matmul_precision("highest"):
        cache = init_kv_pool(cfg, 100, 4, slots=2, max_pages=48)
        pos, other, got = 0, 0, {}
        while pos < FH_PROMPT + 6:
            n = min(16, FH_PROMPT - pos) if pos < FH_PROMPT else 1
            m = min(7, 40 - other)             # the neighbour: shorter chunks
            tk = np.zeros((2, 16), np.int32)
            tk[1, :n] = toks[pos:pos + n]
            tk[0, :m] = toks[::-1][other:other + m]
            lg, cache = step(cache, jnp.asarray(tk), jnp.asarray([other, pos]),
                             jnp.asarray([m, n]))
            pos, other = pos + n, other + m
            got[pos - 1] = np.asarray(lg)[1]
    at = sorted(p for p in got if p >= FH_PROMPT - 1)
    worst = _fh_sigmas(np.stack([got[p] for p in at]), logits[at]).max()
    assert worst <= FH_TOL_SIGMA, f"slot programs: {worst:.2e} sigma"
    assert int(np.asarray(cache.rw).ravel()[1]) >= 64


def test_falcon_h1_tolerance_fails_bfloat16_activations(fh_toy, fh_references):
    """The nearest precision below float32 comes out NOT within the tolerance
    the float32 engine is held to, by a wide margin."""
    import jax.numpy as jnp

    toks, logits, _ = fh_references
    engine = _fh_engine_logits(fh_toy[1], toks, jnp.bfloat16)
    at = slice(FH_PROMPT - 1, FH_PROMPT + FH_DECODE)
    assert _fh_sigmas(engine, logits[at]).max() > 20 * FH_TOL_SIGMA
    lower = fh_toy[0].logits_at(fh_toy[1], [toks], range(FH_PROMPT - 1, len(toks)),
                                act_dtype=jnp.bfloat16)[0]
    assert _fh_sigmas(lower, logits[at]).max() > 20 * FH_TOL_SIGMA


def test_falcon_h1_the_redrawn_decay_shows_the_state(fh_toy, fh_references):
    """Why the file is redrawn: on it a dropped multiplier and a frozen decay are
    far out of tolerance at positions past a fold."""
    toks, _, full = fh_references
    ref = _fh_ref_impl()
    p, cfg = _fh_dequantized(*fh_toy), _fh_cfg(fh_toy[1])
    for wrong in ("no_decay", "no_dt", "no_b", "one_group"):
        bad = ref.np_forward_falcon_h1(p, cfg, np.asarray(toks), wrong=wrong)
        assert _fh_sigmas(bad[FH_PROMPT:], full[FH_PROMPT:]).max() > 20 * FH_TOL_SIGMA, wrong


def test_check_ssm_exposes_the_state_by_drawing_the_one_sided_nibbles_again(
        fh_toy, tmp_path):
    """``tools/check_ssm.py _expose``: values 23 and 31 of every Q40 block, which
    ``mformat`` draws out of -7 .. 0 (a row of any matrix then sums far off 0 and
    every prompt serves one token), come out with mean 0 like the other thirty;
    nothing else of a matrix moves; ``A`` and ``dt`` land in the published
    initialisation's ranges."""
    tool = _fh_check_ssm_tool()
    model, _ = fh_toy
    shape = dict(FH_TOY, dim=256, n_layers=1)
    src, dst = str(tmp_path / "seeded.m"), str(tmp_path / "exposed.m")
    mformat.synthesize(src, model, shape, FH_SEED, workers=2)
    tool._expose(model, shape, src, dst)
    by_name = {t[0]: t for t in model.plan(shape)}

    def blocks(path, name):
        _, shp, ft, off, nbytes = by_name[name]
        raw = np.memmap(path, np.uint8, "r")[off:off + nbytes]
        scale = raw.reshape(-1, mformat.Q40_BLOCK)[:, :2].copy().view(np.float16)
        with np.errstate(invalid="ignore"):
            return mformat.dequantize(np.asarray(raw), shp, ft).reshape(-1, 32) \
                / scale.astype(np.float32)

    for name in ("wcls", "layers.0.w1", "layers.0.ssm_in"):
        was, now = blocks(src, name), blocks(dst, name)
        live = np.isfinite(now).all(axis=1)          # the head's dead rows: scale 0
        was, now = was[live], now[live]
        one_sided = [23, 31]
        assert (was[:, one_sided].mean(0) < -3.0).all(), name   # the finding
        assert np.abs(now.mean(0)).max() < 0.5, name
        others = np.setdiff1d(np.arange(32), one_sided)
        np.testing.assert_array_equal(was[:, others], now[:, others])
    a_log, dt_bias = (np.memmap(dst, np.uint8, "r")[off:off + n].view(np.float32)
                      for _, _, _, off, n in (by_name["layers.0.ssm_a_log"],
                                              by_name["layers.0.ssm_dt_bias"]))
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    dt = np.log1p(np.exp(dt_bias))
    assert (dt >= 0.00099).all() and (dt <= 0.101).all()


def _fh_check_ssm_tool():
    spec = importlib.util.spec_from_file_location(
        "check_ssm_tool", os.path.join(BENCH, "tools", "check_ssm.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("control", [None, "bfloat16-state", "zero-state"])
def test_check_ssm_holds_the_slot_programs_state_planes_to_their_own_ring_rows(
        fh_toy, fh_references, monkeypatch, control):
    """``tools/check_ssm.py Watch`` on the slot programs: the state plane of a
    watched (layer, slot) is the rows the program itself wrote into its rings,
    folded in float64; under either control it is not, by far more than twice
    the tolerance."""
    import types

    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import forward_slots, init_kv_pool
    from dllama_tpu.ops import ssm

    tool = _fh_check_ssm_tool()
    toks = fh_references[0]
    with mfile.MFile(fh_toy[1]) as mf:
        cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    cfg = cfg.with_(quant_impl="xla")
    if control:
        monkeypatch.setattr(ssm, "fold", ssm.fold)     # put back after the test
        tool._control(control)
    table = jnp.asarray(1 + np.arange(2)[:, None] * 48 + np.arange(48)[None, :],
                        jnp.int32)
    step = jax.jit(lambda c, tk, pos, n: forward_slots(params, cfg, tk, c, pos, n,
                                                       table))
    engine = types.SimpleNamespace(
        params=params, cache=init_kv_pool(cfg, 100, 4, slots=2, max_pages=48))
    watches = [tool.Watch(engine, layer, 1) for layer in (0, cfg.n_layers - 1)]
    pos = 0
    while pos < FH_PROMPT + 6:
        n = min(16, FH_PROMPT - pos) if pos < FH_PROMPT else 1
        tk = np.zeros((2, 16), np.int32)
        tk[1, :n] = toks[pos:pos + n]
        _, engine.cache = step(engine.cache, jnp.asarray(tk), jnp.asarray([0, pos]),
                               jnp.asarray([0, n]))
        for w in watches:
            w.wrote(pos, n)
        pos += n
    assert all(w.checked >= 1 for w in watches)
    worst = max(w.worst for w in watches)
    if control:
        assert worst >= 2 * tool.PLANE_TOL, f"{control}: {worst:.2e}"
    else:
        assert worst <= tool.PLANE_TOL, f"{worst:.2e}"


def test_falcon_h1_cost_functions_at_the_published_sizes():
    with open(FH_CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    layers, q = cfg["num_hidden_layers"], 18 / 32
    att = 2 * 5120 * 2560 + 2 * 5120 * 512             # 31.46 M
    w_in, w_out, ffn = 5120 * 9216, 4096 * 5120, 3 * 5120 * 21504
    head, dt = 261120 * 5120, 32 * 5120
    assert att + w_in + w_out + ffn == 429_916_160      # 429.92 M a block
    assert model.weight_bytes(cfg) == pytest.approx(
        layers * ((att + w_in + w_out + ffn) * q + 4 * dt) + head * q)
    assert 5.10e9 < model.weight_bytes(cfg) < 5.13e9
    assert model.kv_bytes_per_token(cfg) == 36_864      # 2,048 B a layer
    state = 32 * 256 * 128 * 4
    assert state == 4_194_304                           # 4.19 MB a layer a row
    conv = 3 * 5120 * 2
    recent = 32 * ((4096 + 512) * 2 + 4 * 32)
    assert model.ssm_bytes(cfg, 32) == pytest.approx(
        layers * ((w_in + w_out) * q + 4 * dt + 32 * (state + conv + recent)))
    assert 3.2e9 < model.ssm_bytes(cfg, 32) < 3.5e9
    assert model.ssm_flops(cfg, 32) == pytest.approx(
        2.0 * layers * 32 * (w_in + w_out + dt + 32 * (256 * 128 + 32 * 384)))
    # the mixer is byte-bound by a factor of nine at 32 rows
    assert model.ssm_bytes(cfg, 32) / 819e9 > 9 * model.ssm_flops(cfg, 32) / 197e12
    live = 32 * 320
    total = model.step_bytes(cfg, live, 1, 32)
    assert total == pytest.approx(
        model.weight_bytes(cfg) + layers * 32 * (state + conv + recent)
        + 36_864 * live)
    assert model.kv_read_bytes(cfg, 320, rows=32) == 36_864 * live
    # the least a step moves: the mixer about two fifths, attention a twelfth
    assert 0.36 < model.ssm_bytes(cfg, 32) / total < 0.44
    assert 0.06 < (36_864 * live + layers * att * q) / total < 0.10
    assert model.step_flops(cfg, 32, live) == pytest.approx(
        2.0 * (32 * (layers * (att + ffn) + head) + layers * 2 * 2560 * live)
        + model.ssm_flops(cfg, 32))


def _fake_ssm_trace(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip: the mixer's parts and
    attention's own ops in a decode program and a mixed one."""
    meta = {1: {"tf_op": "jit(chunk)/while/body/qkv/qkv/ssm/q40_mm", "program_id": 7},
            2: {"tf_op": "jit(chunk)/while/body/kv_write/recent/dus", "program_id": 7},
            3: {"tf_op": "jit(chunk)/while/body/kv_write/fold/while/body/dot", "program_id": 7},
            4: {"tf_op": "jit(chunk)/while/body/attn/state/dot", "program_id": 7},
            5: {"tf_op": "jit(chunk)/while/body/attn/conv/fusion", "program_id": 7},
            6: {"tf_op": "jit(chunk)/while/body/attn/paged_attn_fused", "program_id": 7},
            7: {"tf_op": "jit(step)/wo/wo/ssm/q40_mm", "program_id": 9},
            8: {"tf_op": "jit(step)/qkv/q40_mm", "program_id": 9}}
    if not with_names:
        meta = {k: {"program_id": v["program_id"]} for k, v in meta.items()}
    ops = [(1, 0.0, 3e6), (2, 3e6, 0.5e6), (3, 4e6, 2e6), (4, 6e6, 10e6),
           (5, 16e6, 0.5e6), (6, 17e6, 9e6), (7, 30e6, 4e6), (8, 35e6, 6e6)]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": []}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_falcon_h1_readers_of_the_mixers_parts(with_names, tmp_path, monkeypatch):
    """``serve_ssm_ms_per_step`` reads the part ``ssm`` of ``qkv`` and ``wo`` and
    the parts ``conv``, ``state``, ``recent`` and ``fold`` under ``attn`` and
    ``kv_write``, per scheduler step, and leaves attention's own ops (the bare
    scopes) out; ``serve_ssm_roof_pct`` the floor of ``ssm_bytes`` /
    ``ssm_flops`` at the step's rows over that time, and says which floor
    binds; a program without the names (the parent) gives nothing and does not
    raise."""
    parts = importlib.import_module("_parts")
    scopes = importlib.import_module("_scopes")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(parts.xplane, "find_xplane", lambda out: str(pb))
    monkeypatch.setattr(parts.xmeta, "load", lambda path, keep_host:
                        _fake_ssm_trace(with_names))
    parts._SECONDS.clear()
    tab = {"steps": 4, "busy_s": 0.041, "scopes": {"attn": 0.0195}, "scoped": with_names}
    monkeypatch.setattr(scopes, "table", lambda ctx: tab)
    monkeypatch.setattr(parts, "table", lambda ctx: tab)
    monkeypatch.setattr(scopes, "scoped", lambda t: bool(t and t["scoped"]))
    monkeypatch.setattr(parts, "scoped", lambda t: bool(t and t["scoped"]))
    ms = importlib.reload(importlib.import_module("serve_ssm_ms_per_step"))
    roof = importlib.reload(importlib.import_module("serve_ssm_roof_pct"))
    monkeypatch.setattr(roof, "OUT", str(tmp_path))
    with open(FH_CONFIG) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "traced_window": (100.0, 105.0), "chips": 1,
           "window": (80.0, 125.0), "config": cfg,
           "samples": [(90.0, {"sched_slots_occupied": 32})], "records": [],
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    if not with_names:
        assert ms.read(ctx) is None and roof.read(ctx) is None
        return
    model = models.for_config(cfg)
    assert ms.read(ctx) == pytest.approx(20.0 / 4)      # 35 ms less attention's 15
    assert roof.read(ctx) == pytest.approx(
        100 * model.ssm_bytes(cfg, 32) / 819e9 / 5e-3)
    assert roof.read(ctx) < 100
    with open(tmp_path / "ssm-roof.json") as f:
        assert json.load(f)["floor"] == "bytes"
    # a configuration without a mixer has nothing to read
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        assert roof.read(dict(ctx, config=json.load(f))) is None
