"""``models/brumby.py`` against the program, on the CPU at toy widths that keep
every ratio (five query heads a kv head, a head of 16, four layers all alike,
degree 2; these tests import JAX and ``dllama_tpu``).  Three independent forward
passes on one seeded file the module wrote: the program (a chunked prefill and
decoding on the contiguous engine past a fold of its lagged state; the slot
programs with a ragged chunk), the module's own reference (``last_logits`` /
``logits_at``: the attention form, no state, no ring, no ``phi``), and
``tests/reference_impl.py np_forward_brumby`` on weights dequantized by
``mformat.dequantize``; the configuration file against the catalog's rules; the
cost functions at the published sizes; the three new readers.
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

BR_TOY = dict(dim=160, hidden_dim=224, n_layers=4, n_heads=10, n_kv_heads=2,
              vocab_size=288, seq_len=512, rope_theta=1000000.0, norm_eps=1e-6,
              retention_degree=2)
BR_SEED, BR_PROMPT, BR_DECODE = 51, 150, 19
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# BR_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone (the engine sums over its
# state's 144 products a head where the references sum over keys, and a
# quotient of two such sums doubles it).  Read when this test was written: the
# two references 5.1e-5 sigma apart (one is float32 on the device, one float64
# inside its scores), the engine 3.3e-5 from either.  The same engine with
# bfloat16 activations reads 8e-2: the NEGATIVE CONTROL.
BR_TOL_SIGMA = 1.5e-4
CONFIG = os.path.join(BENCH, "configs", "brumby-14b-base.json")
CELL = os.path.join(BENCH, "cells", "brumby-14b-base.long-decode.json")


def _ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def br_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("brumby")
    path = str(tmp_path_factory.mktemp("brumby") / "br-toy.m")
    mformat.synthesize(path, model, BR_TOY, BR_SEED, workers=2)
    return model, path


def _br_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _br_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks, read by the benchmark's own
    reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(BR_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(BR_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo", "wg", "w1", "w2", "w3")}
    for key in ("q_norm", "k_norm", "rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


def _br_sigmas(got, want):
    return np.abs(got - want).max(-1) / want.std(-1)


@pytest.fixture(scope="module")
def br_references(br_toy):
    """``(tokens, the module's logits at every position, the numpy
    reference's)``."""
    model, path = br_toy
    toks = np.random.default_rng(7).integers(3, BR_TOY["vocab_size"],
                                             BR_PROMPT + BR_DECODE).tolist()
    n = len(toks)
    logits = model.logits_at(path, [toks], range(n))[0]
    full = _ref_impl().np_forward_brumby(_br_dequantized(model, path),
                                         _br_cfg(path), np.asarray(toks))
    return toks, logits, full


def _br_engine_logits(path, toks, dtype, packed: bool, monkeypatch=None):
    """The contiguous engine: the prompt in chunks of 32 and a bucketed tail,
    then token by token: logits ``(BR_DECODE + 1, vocab)``."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.runtime.engine import Engine

    with mfile.MFile(path) as mf:
        cfg, params = load_params(mf, dtype=dtype, keep_quantized=packed)
    with jax.default_matmul_precision("highest"):
        eng = Engine(cfg.with_(quant_impl="xla"), params, batch=1)
        rows = [eng.prefill(toks[:BR_PROMPT])[0][0]]
        for tok in toks[BR_PROMPT:]:
            rows.append(eng.decode_one(int(tok))[0][0])
        assert eng._state_lo >= 64                     # a block was folded
    return np.stack(rows)


def test_brumby_header_and_plan_are_what_the_program_parses(br_toy):
    from dllama_tpu.io import mfile

    model, path = br_toy
    mf = mfile.MFile(path)
    for key, want in dict(BR_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want), key
    assert mf.spec.arch == mfile.ARCH_BRUMBY == model.ARCH_BRUMBY
    assert mf.spec.header_size == len(model.header(BR_TOY))
    assert tuple(k for k, _, _ in model.EXT_KEYS) \
        == mfile.ARCH_EXT_KEYS[mfile.ARCH_BRUMBY]
    assert model.read_header(path)["retention_degree"] == 2
    ours = model.plan(BR_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    shapes = dict((t[0], t[1]) for t in ours)
    assert shapes["layers.0.wg"] == (2, 160) and shapes["layers.3.q_norm"] == (16,)
    assert shapes["layers.0.wk"] == (32, 160) and shapes["layers.0.w1"] == (224, 160)
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_brumby_configuration_keeps_every_published_key_but_the_depth():
    """The catalog's rule: every number of the published config under the same
    key, but for the keys of ``reduced``, whose published values are kept
    beside them; depth only: no width, no head, no vocabulary row."""
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    assert config["num_hidden_layers"] == 20          # a stage of two; floor 4
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"Brumby-14B-Base"' in l)
        assert config["source"] == row["source_url"]
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert set(config["assumed"]) == {
        "retention_degree", "gate", "qk_norm", "quotient", "eps_and_scale",
        "state_precision", "switch_over", "seeded_gates"}
    assert "two stages of 20 layers" in config["deployment"]
    with open(CELL) as f:
        cell = json.load(f)
    assert cell["argv"] == ["--workers", "tpu:1", "--batch-slots", "8",
                            "--max-seq-len", "6144", "--max-pending", "64"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-base", "long-decode", 1)


def test_brumby_shape_reads_the_published_keys_and_refuses_by_name(monkeypatch):
    with open(CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["n_layers"], shp["n_heads"],
            shp["n_kv_heads"], shp["vocab_size"], shp["seq_len"],
            shp["retention_degree"]) == (5120, 17408, 20, 40, 8, 151936, 32768, 2)
    assert shp["norm_eps"] == 1e-6 and shp["rope_theta"] == 1000000
    last = model.plan(shp)[-1]
    assert 7.26e9 < last[3] + last[4] < 7.28e9          # 7.27 GB on disk
    for patch, says in (
            (dict(attention_bias=True), "attention_bias"),
            (dict(use_sliding_window=True), "use_sliding_window"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
            (dict(tie_word_embeddings=True), "tied"),
            (dict(head_dim=64), "is not hidden_size"),
            (dict(num_key_value_heads=7), "not a multiple")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))
    # a checkout whose program lacks the arch id fails at once, by name
    monkeypatch.setattr(model, "_program_has_the_arch", lambda: False)
    with pytest.raises(SystemExit, match="no arch id 0xABCD08 .brumby.: unknown arch id"):
        model.shape(config)


def test_brumby_last_logits_and_logits_at_are_the_every_position_pass(
        br_toy, br_references):
    model, path = br_toy
    toks, logits, _ = br_references
    n = len(toks)
    last = model.last_logits(path, [toks[:BR_PROMPT]])[0]
    assert _br_sigmas(last[None], logits[BR_PROMPT - 1][None])[0] <= BR_TOL_SIGMA
    some = model.logits_at(path, [toks], [0, 1, 5, BR_PROMPT, n - 1])[0]
    assert _br_sigmas(some, logits[[0, 1, 5, BR_PROMPT, n - 1]]).max() <= BR_TOL_SIGMA


def test_brumby_engine_and_two_references_agree_in_float32(br_toy, br_references):
    import jax.numpy as jnp

    toks, logits, full = br_references
    between = _br_sigmas(full, logits).max()
    assert between <= BR_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _br_engine_logits(br_toy[1], toks, jnp.float32, packed=False)
    at = slice(BR_PROMPT - 1, BR_PROMPT + BR_DECODE)
    worst = max(_br_sigmas(engine, logits[at]).max(),
                _br_sigmas(engine, full[at]).max())
    assert worst <= BR_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_brumby_slot_programs_agree_in_float32(br_toy, br_references):
    """The slot path the served cell runs: chunks of 16 with a ragged last one
    through ``forward_slots`` over the slot's own state and ring, then one
    token a step, a neighbour slot riding along."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import forward_slots, init_kv_cache

    toks, logits, _ = br_references
    with mfile.MFile(br_toy[1]) as mf:
        cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    step = jax.jit(lambda p, tk, c, pos, n: forward_slots(p, cfg, tk, c, pos, n))
    with jax.default_matmul_precision("highest"):
        cache = init_kv_cache(cfg, 2)
        pos, rows = 0, []
        while pos < BR_PROMPT:
            n = min(16, BR_PROMPT - pos)
            tk = np.zeros((2, 16), np.int32)
            tk[1, :n] = toks[pos:pos + n]
            lg, cache = step(params, jnp.asarray(tk), cache,
                             jnp.asarray([0, pos], jnp.int32),
                             jnp.asarray([0, n], jnp.int32))
            pos += n
        rows.append(np.asarray(lg)[1])
        one = jax.jit(lambda p, tk, c, pos, n: forward_slots(p, cfg, tk, c, pos, n))
        for tok in toks[BR_PROMPT:]:
            lg, cache = one(params, jnp.asarray([[0], [tok]], jnp.int32), cache,
                            jnp.asarray([0, pos], jnp.int32),
                            jnp.asarray([0, 1], jnp.int32))
            rows.append(np.asarray(lg)[1])
            pos += 1
    at = slice(BR_PROMPT - 1, BR_PROMPT + BR_DECODE)
    assert _br_sigmas(np.stack(rows), logits[at]).max() <= BR_TOL_SIGMA


def test_brumby_tolerance_fails_bfloat16_activations(br_toy, br_references):
    import jax.numpy as jnp

    toks, logits, _ = br_references
    engine = _br_engine_logits(br_toy[1], toks, jnp.bfloat16, packed=False)
    at = slice(BR_PROMPT - 1, BR_PROMPT + BR_DECODE)
    assert _br_sigmas(engine, logits[at]).max() > 100 * BR_TOL_SIGMA   # 8e-2


@pytest.mark.parametrize("state", ["zero", "bfloat16"])
def test_brumby_counter_readings_differ_from_the_reference(br_toy, br_references,
                                                          state):
    """``logits_at(state=)``: what a zeroed or a bfloat16 state would read
    (``tools/check_retention.py``'s two figures).  On this file's symmetric
    gates the tokens older than 32 positions have decayed below float32's
    resolution, so both readings are the reference's own here (which is why
    the tool redraws the gates); ``tests/test_brumby.py`` holds the program's
    own state to a zeroed and a rounded one on gates that show it."""
    model, path = br_toy
    toks, logits, _ = br_references
    at = [BR_PROMPT - 1, BR_PROMPT + BR_DECODE - 1]
    wrong = model.logits_at(path, [toks], at, state=state)[0]
    assert _br_sigmas(wrong, logits[at]).max() <= BR_TOL_SIGMA


def test_brumby_regate_draws_each_layers_gates_from_its_own_input(br_toy, tmp_path):
    """``regate`` (``tools/check_retention.py``'s redraw): a pass that hands
    each layer the mean of ITS normed input, the layers before it already
    regated, and uses what ``draw`` returns; gates drawn ``a m / |m|^2`` then
    read ``W_g u`` about ``a`` at every depth, and a second pass over the
    rewritten file meets the same means."""
    import shutil

    model, src = br_toy
    path = str(tmp_path / "regated.m")
    shutil.copyfile(src, path)
    by_name = {t[0]: t for t in model.plan(BR_TOY)}
    raw = np.memmap(path, np.uint8, "r+")
    toks = np.random.default_rng(3).integers(3, BR_TOY["vocab_size"], (1, 96)).tolist()
    means = []

    def draw(i, mean):
        _, shp, _, off, nbytes = by_name[f"layers.{i}.wg"]
        wg = np.full((shp[0], 1), 3.0, np.float32) * mean[None, :] / float(mean @ mean)
        raw[off:off + nbytes].view(np.float32).reshape(shp)[:] = wg
        means.append(mean)
        return wg

    model.regate(path, toks, draw)
    raw.flush()
    assert len(means) == BR_TOY["n_layers"]
    again = []

    def read(i, mean):
        _, shp, _, off, nbytes = by_name[f"layers.{i}.wg"]
        again.append(mean)
        return np.asarray(raw[off:off + nbytes]).view(np.float32).reshape(shp)

    model.regate(path, toks, read)
    for i, (first, second) in enumerate(zip(means, again)):
        assert np.allclose(first, second, rtol=1e-4, atol=1e-5), i
        _, shp, _, off, nbytes = by_name[f"layers.{i}.wg"]
        wg = np.asarray(raw[off:off + nbytes]).view(np.float32).reshape(shp)
        assert np.allclose(wg @ second, 3.0, rtol=1e-3), i
    # the layers are not alike in what they see: a gate fixed beforehand along
    # the first layer's mean would read far from 3 further down
    cos = [float(m @ means[0] / np.linalg.norm(m) / np.linalg.norm(means[0]))
           for m in means]
    assert min(cos) < 0.9


def test_brumby_cost_functions_at_the_published_sizes():
    with open(CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    layers, q = cfg["num_hidden_layers"], 18 / 32
    att = 2 * 5120 * 5120 + 2 * 5120 * 1024            # 62.9 M
    ffn, head, gate = 3 * 5120 * 17408, 151936 * 5120, 8 * 5120
    assert att + ffn == 330_301_440                     # 330.3 M a layer
    assert model.weight_bytes(cfg) == pytest.approx(
        layers * ((att + ffn) * q + 4 * gate) + head * q)
    assert 4.15e9 < model.weight_bytes(cfg) < 4.16e9
    assert model.kv_bytes_per_token(cfg) == 0.0
    # the least an exact implementation reads: the symmetric state once a row
    d = 128 * 129 // 2
    state = 8 * d * 129 * 4
    assert (d, state) == (8256, 34_080_768)             # 34.08 MB a layer a row
    recent = 32 * 8 * (2 * 128 * 2 + 4)
    assert model.retention_bytes(cfg, 8) == pytest.approx(
        layers * 8 * (state + recent))
    assert 5.4e9 < model.retention_bytes(cfg, 8) < 5.6e9
    assert model.retention_flops(cfg, 8) == pytest.approx(
        2.0 * layers * 8 * (40 * d * 129 + 40 * 32 * 256))
    # the read is byte-bound by a factor of ten at eight rows
    assert model.retention_bytes(cfg, 8) / 819e9 > 10 * model.retention_flops(cfg, 8) / 197e12
    assert model.step_bytes(cfg, 8 * 4000, 1, 8) == pytest.approx(
        model.weight_bytes(cfg) + model.retention_bytes(cfg, 8))
    assert model.step_bytes(cfg, 0, 1, 8) == model.step_bytes(cfg, 8 * 4000, 1, 8)
    assert model.step_flops(cfg, 8, 8 * 4000) == pytest.approx(
        2.0 * 8 * (layers * (att + gate + ffn) + head) + model.retention_flops(cfg, 8))
    # the state is 57 % of the step's bytes at eight rows
    share = model.retention_bytes(cfg, 8) / model.step_bytes(cfg, 0, 1, 8)
    assert 0.55 < share < 0.60


def _fake_retention_trace(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip: the operator's parts in a
    decode program and a mixed one."""
    meta = {1: {"tf_op": "jit(chunk)/while/body/qkv/qkv/retention/dot", "program_id": 7},
            2: {"tf_op": "jit(chunk)/while/body/kv_write/recent/dus", "program_id": 7},
            3: {"tf_op": "jit(chunk)/while/body/kv_write/fold/while/body/dot", "program_id": 7},
            4: {"tf_op": "jit(chunk)/while/body/attn/state/dot", "program_id": 7},
            5: {"tf_op": "jit(chunk)/while/body/attn/recent/dot", "program_id": 7},
            6: {"tf_op": "jit(chunk)/while/body/qkv/q40_mm", "program_id": 7},
            7: {"tf_op": "jit(step)/attn/state/dot", "program_id": 9}}
    if not with_names:
        meta = {k: {"program_id": v["program_id"]} for k, v in meta.items()}
    ops = [(1, 0.0, 1e6), (2, 1e6, 0.5e6), (3, 2e6, 2e6), (4, 4e6, 20e6),
           (5, 24e6, 4e6), (6, 28e6, 9e6), (7, 40e6, 12e6)]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": []}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_brumby_readers_of_the_retention_parts(with_names, tmp_path, monkeypatch):
    """``serve_retention_ms_per_step`` reads the parts ``retention``, ``state``,
    ``recent`` and ``fold`` under the scopes they sit in, per scheduler step;
    ``serve_retention_fold_ms_per_step`` the fold alone;
    ``serve_retention_roof_pct`` the floor of ``retention_bytes`` /
    ``retention_flops`` at the step's rows over ``state`` + ``recent`` under
    ``attn``, and says which floor binds; a program without the names (the
    parent) gives nothing and does not raise."""
    parts = importlib.import_module("_parts")
    scopes = importlib.import_module("_scopes")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(parts.xplane, "find_xplane", lambda out: str(pb))
    monkeypatch.setattr(parts.xmeta, "load", lambda path, keep_host:
                        _fake_retention_trace(with_names))
    parts._SECONDS.clear()
    tab = {"steps": 4, "busy_s": 0.049, "scopes": {"attn": 0.036}, "scoped": with_names}
    monkeypatch.setattr(scopes, "table", lambda ctx: tab)
    monkeypatch.setattr(parts, "table", lambda ctx: tab)
    monkeypatch.setattr(scopes, "scoped", lambda t: bool(t and t["scoped"]))
    monkeypatch.setattr(parts, "scoped", lambda t: bool(t and t["scoped"]))
    roof = importlib.reload(importlib.import_module("serve_retention_roof_pct"))
    monkeypatch.setattr(roof, "OUT", str(tmp_path))
    with open(CONFIG) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "traced_window": (100.0, 105.0), "chips": 1,
           "window": (80.0, 125.0), "config": cfg,
           "samples": [(90.0, {"sched_slots_occupied": 8})], "records": [],
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    read = {n: importlib.reload(importlib.import_module(n)).read(ctx) for n in (
        "serve_retention_ms_per_step", "serve_retention_fold_ms_per_step")}
    read["serve_retention_roof_pct"] = roof.read(ctx)
    if not with_names:
        assert set(read.values()) == {None}
        return
    model = models.for_config(cfg)
    assert read["serve_retention_ms_per_step"] == pytest.approx(39.5 / 4)
    assert read["serve_retention_fold_ms_per_step"] == pytest.approx(2.0 / 4)
    assert read["serve_retention_roof_pct"] == pytest.approx(
        100 * model.retention_bytes(cfg, 8) / 819e9 / 9e-3)
    assert read["serve_retention_roof_pct"] < 100
    with open(tmp_path / "retention-roof.json") as f:
        assert json.load(f)["floor"] == "bytes"
