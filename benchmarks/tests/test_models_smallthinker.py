"""``models/smallthinker.py`` against the program, on the CPU at toy widths
with the published 64 experts, 6 a token, periods of one full and three window
layers, 7 query heads a kv head and a head size that is not ``dim / n_heads``
(these tests import JAX and ``dllama_tpu``).  Three independent forward passes
on one seeded file the module wrote: the program's engine (a chunked prefill
that crosses the window, then decoding through a wrapped ring), the module's
own reference (``last_logits`` / ``logits_at`` / ``routing_margins``), and
``tests/reference_impl.py np_forward_smallthinker`` on weights dequantized by
``mformat.dequantize``; the readers' cost functions at the published sizes.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

ST_TOY = dict(dim=96, hidden_dim=32, n_layers=8, n_heads=28, n_kv_heads=4,
              vocab_size=288, seq_len=96, rope_theta=1500000.0, n_experts=64,
              n_active_experts=6, norm_eps=1e-6, head_dim=8, window=16,
              window_period=4)
ST_SEED, ST_PROMPT, ST_DECODE = 38, 41, 19
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# ST_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone (the engine's online softmax
# over ring blocks against one softmax over a masked row).  Measured when this
# test was written: 3e-6 sigma between the two references, 4e-6 between the
# engine and either.  The same engine with bfloat16 activations reads 1e-2 and
# more: the NEGATIVE CONTROL.  RoPE on a full layer, a window off by one, a
# router fed the normed input, SiLU for ReLU or a softmax over all 64 without
# renormalising read tenths of a sigma (tests/test_smallthinker.py).
ST_TOL_SIGMA = 2e-5
# ST_TOL_Q40_SIGMA, the packed path the cell serves: the Q40 matmuls round both
# operands to bfloat16 and accumulate in float32.  Compared on MARGIN-STEADY
# positions (models/smallthinker.py routing_margins: the gap between the 6th
# and 7th router logit over the spread of the row's router logits, above
# ``MARGIN_STEADY`` at all 8 layers); ``ST_MAX_LEFT_OUT`` bounds the share left
# out, so the test cannot pass by comparing nothing.
ST_TOL_Q40_SIGMA = 0.06
ST_MAX_LEFT_OUT = 0.75
# a prefill chunk of 16 rows: rings of 16 + 16 positions under sequences of 60
ST_SMALL_PRODUCT = 4 * 64 * 96 * 16


def _st_np_forward():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.np_forward_smallthinker


@pytest.fixture(scope="module")
def st_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("smallthinker")
    path = str(tmp_path_factory.mktemp("smallthinker") / "st-toy.m")
    mformat.synthesize(path, model, ST_TOY, ST_SEED, workers=2)
    return model, path


def _st_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _st_dequantized(model, path: str) -> dict:
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(ST_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(ST_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo")}
    out["router"] = np.stack([tensor(f"layers.{i}.moe_router").T for i in layers])
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack(
            [tensor(f"layers.{i}.experts.{e}.{key}").T
             for e in range(ST_TOY["n_experts"])]) for i in layers])
    for key in ("rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


@pytest.fixture(scope="module")
def st_references(st_toy):
    model, path = st_toy
    rng = np.random.RandomState(ST_SEED)
    toks = [int(t) for t in rng.randint(3, ST_TOY["vocab_size"],
                                        ST_PROMPT + ST_DECODE)]
    logits, margins = model.routing_margins(path, [toks])
    weights, cfg = _st_dequantized(model, path), _st_cfg(path)
    full = _st_np_forward()(weights, cfg, np.asarray(toks))
    return toks, logits[0], margins[0], full, weights, cfg


def _st_engine_logits(path: str, toks: list[int], steps: int, dtype,
                      packed: bool, monkeypatch) -> np.ndarray:
    """The program's logits after a chunked prefill of the prompt and after
    each of ``steps`` decoded tokens (seeded, not greedy)."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models import config as config_mod
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", ST_SMALL_PRODUCT)
    mf = mfile.MFile(path)
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                              dtype=dtype, keep_quantized=packed)
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 seq_len=ST_TOY["seq_len"])
    assert eng.cache.wk.shape[3] == 32 and eng.cache.k.shape[3] == 96
    logits, _ = eng.prefill(toks[:ST_PROMPT])   # 16 + 16 + a tail of 9
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[ST_PROMPT:ST_PROMPT + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return np.stack(got)


def _st_sigmas(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.abs(got - ref).max(-1) / ref.std(-1)


def test_smallthinker_header_and_plan_are_what_the_program_parses(st_toy):
    from dllama_tpu.io import mfile

    model, path = st_toy
    mf = mfile.MFile(path)
    for key, want in dict(ST_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_RELU).items():
        assert getattr(mf.spec, key) == pytest.approx(want), key
    assert mf.spec.arch == mfile.ARCH_SMALLTHINKER == model.ARCH_SMALLTHINKER
    assert mf.spec.header_size == len(model.header(ST_TOY))
    assert model.read_header(path)["window_period"] == 4
    ours = model.plan(ST_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    assert dict((t[0], t[1]) for t in ours)["layers.0.wq"] == (224, 96)
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_smallthinker_shape_reads_the_published_keys_and_refuses_by_name():
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["n_layers"], shp["n_heads"],
            shp["n_kv_heads"], shp["head_dim"], shp["window"], shp["window_period"],
            shp["n_experts"], shp["n_active_experts"], shp["vocab_size"],
            shp["seq_len"]) == (2560, 768, 52, 28, 4, 128, 4096, 4, 64, 6,
                                151936, 16384)
    assert config["reduced"] == [] and len(config["assumed"]) == 4
    size = model.plan(shp)[-1]
    assert 13.4e9 < size[3] + size[4] < 13.6e9      # the file: 13.5 GB
    for patch, says in (
            (dict(moe_primary_router_apply_softmax=False), "sigmoid router"),
            (dict(moe_num_secondary_experts=4), "secondary experts"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling is set"),
            (dict(rope_layout=[1] * 52), "rope_layout is not sliding_window_layout"),
            (dict(sliding_window_layout=[0, 1] * 26, rope_layout=[0, 1] * 26), None),
            (dict(sliding_window_layout=[0, 1, 1] * 17 + [1],
                  rope_layout=[0, 1, 1] * 17 + [1]), "not whole periods")):
        if says is None:
            assert model.shape(dict(config, **patch))["window_period"] == 2
            continue
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))


def test_smallthinker_last_logits_and_logits_at_are_the_every_position_pass(
        st_toy, st_references):
    model, path = st_toy
    toks, logits, _, _, _, _ = st_references
    n = ST_PROMPT + ST_DECODE
    last = model.last_logits(path, [toks[:ST_PROMPT]])[0]
    assert _st_sigmas(last[None], logits[ST_PROMPT - 1][None])[0] <= ST_TOL_SIGMA
    some = model.logits_at(path, [toks], [5, ST_PROMPT, n - 1])[0]
    assert _st_sigmas(some, logits[[5, ST_PROMPT, n - 1]]).max() <= ST_TOL_SIGMA


def test_smallthinker_engine_and_two_references_agree_in_float32(
        st_toy, st_references, monkeypatch):
    import jax.numpy as jnp

    toks, logits, _, full, _, _ = st_references
    between = _st_sigmas(full, logits).max()
    assert between <= ST_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _st_engine_logits(st_toy[1], toks, ST_DECODE, jnp.float32,
                               packed=False, monkeypatch=monkeypatch)
    at = slice(ST_PROMPT - 1, ST_PROMPT + ST_DECODE)
    worst = max(_st_sigmas(engine, logits[at]).max(),
                _st_sigmas(engine, full[at]).max())
    assert worst <= ST_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_smallthinker_tolerance_fails_bfloat16_activations(
        st_toy, st_references, monkeypatch):
    """NEGATIVE CONTROL for ``ST_TOL_SIGMA``: the program with bfloat16
    activations, the next precision below the float32 that run states."""
    import jax.numpy as jnp

    toks, logits, _, _, _, _ = st_references
    engine = _st_engine_logits(st_toy[1], toks, 0, jnp.bfloat16, packed=False,
                               monkeypatch=monkeypatch)
    assert _st_sigmas(engine, logits[ST_PROMPT - 1][None])[0] > 100 * ST_TOL_SIGMA


@pytest.mark.parametrize("wrong", ["rope_on_full", "window_plus_one",
                                   "router_after_norm", "silu", "softmax_all"])
def test_smallthinker_reference_with_one_fault_disagrees(st_references, wrong):
    toks, logits, _, _, weights, cfg = st_references
    other = _st_np_forward()(weights, cfg, np.asarray(toks), wrong=wrong)
    assert _st_sigmas(other, logits).max() > 1000 * ST_TOL_SIGMA


def test_smallthinker_packed_engine_agrees_on_margin_steady_positions(
        st_toy, st_references, monkeypatch):
    import jax.numpy as jnp

    model, path = st_toy
    toks, logits, margins, _, _, _ = st_references
    at = slice(ST_PROMPT - 1, ST_PROMPT + ST_DECODE)
    steady = margins[at].min(-1) > model.MARGIN_STEADY
    left_out = 1.0 - steady.mean()
    assert left_out <= ST_MAX_LEFT_OUT, (
        f"{left_out:.0%} of {steady.size} positions have a routing margin "
        f"under {model.MARGIN_STEADY}")
    engine = _st_engine_logits(path, toks, ST_DECODE, jnp.float32, packed=True,
                               monkeypatch=monkeypatch)
    worst = _st_sigmas(engine, logits[at])[steady].max()
    assert worst <= ST_TOL_Q40_SIGMA, (
        f"{worst:.4f} sigma over {int(steady.sum())} margin-steady positions")


def test_smallthinker_cost_functions_at_the_published_sizes():
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    q = 18 / 32
    att = 2 * 2560 * 3584 + 2 * 2560 * 512
    one = 3 * 2560 * 768
    assert model.layer_kinds(cfg) == (13, 39)
    assert model.moe_bytes(cfg) == 52 * (64 * 2560 + 6 * one) * q
    assert 1.03e9 < model.moe_bytes(cfg) < 1.05e9
    assert model.weight_bytes(cfg) == (52 * att + 151936 * 2560) * q + model.moe_bytes(cfg)
    assert model.kv_bytes_per_token(cfg) == 52 * 2048
    assert model.kv_read_bytes(cfg, 7000) == (13 * 7000 + 39 * 4096) * 2048
    assert model.kv_read_bytes(cfg, 100) == 52 * 100 * 2048
    assert model.step_bytes(cfg, 7000) == model.weight_bytes(cfg) + model.kv_read_bytes(cfg, 7000)
    assert model.step_flops(cfg, 1, 7000) == 2.0 * (
        52 * (att + 64 * 2560 + 6 * one) + 151936 * 2560
        + 2 * 28 * 128 * (13 * 7000 + 39 * 4096))
    # 16 rows hit 51 of 64 experts under uniform routing
    assert 50 < model.experts_read(cfg, 16) < 52


def _fake_trace(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip: a decode program (7: it
    samples) and a prefill program (9), and three chunk calls on the host."""
    meta = {1: {"tf_op": "jit(chunk)/while/body/attn/window/dot", "program_id": 7},
            2: {"tf_op": "jit(chunk)/while/body/sample/argmax", "program_id": 7},
            3: {"tf_op": "jit(chunk)/while/body/moe/experts/q40_mm", "program_id": 7},
            4: {"tf_op": "jit(step)/attn/window/dot", "program_id": 9},
            5: {"tf_op": "jit(step)/moe/experts/q40_mm_experts", "program_id": 9}}
    if not with_names:
        meta = {k: {"program_id": v["program_id"]} for k, v in meta.items()}
    ops = [(1, 0.0, 2e6), (2, 2e6, 1e6), (3, 3e6, 4e6), (4, 10e6, 50e6),
           (5, 60e6, 90e6), (1, 200e6, 2e6)]
    host = [("python", "engine.prefill_chunk", 10e6, 200e6, {"k": 512, "rows": 512, "pos": 0}),
            ("python", "engine.prefill_chunk", 220e6, 190e6, {"k": "512", "rows": 512}),
            ("python", "engine.prefill_chunk", 420e6, 60e6, {"k": 100, "rows": 128})]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": host if with_names else []}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_smallthinker_readers_of_the_decode_programs_and_the_chunk_spans(
        with_names, tmp_path, monkeypatch):
    """The by-program readers count the decode program alone (the prefill
    program's 50 ms under ``attn/window`` stay out) and the chunk spans give
    host ms per 1000 prompt tokens; a program without the names gives
    nothing and does not raise."""
    decode = importlib.import_module("_decode")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    kept = []
    monkeypatch.setattr(decode.xplane, "find_xplane", lambda out: str(pb))
    monkeypatch.setattr(decode.xmeta, "load", lambda path, keep_host: (
        kept.append([n for n in ("engine.prefill", "engine.prefill_chunk")
                     if keep_host(n)]), _fake_trace(with_names))[1])
    decode._CACHE.clear()
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "traced_window": (100.0, 105.0), "chips": 1,
           "records": [{"ok": True, "cut": False, "times": [100.5, 101.0, 104.0, 106.0]}],
           "config": cfg, "peaks": {"hbm_bytes_per_s": 819e9}}
    read = {n: importlib.import_module(n).read(ctx) for n in (
        "attn_window_ms_per_tok", "moe_select_ms_per_tok", "moe_select_roof_pct",
        "prefill_ms_per_ktok")}
    assert kept == [["engine.prefill_chunk"]]  # one parse for all of them
    if not with_names:
        assert set(read.values()) == {None}
        return
    assert read["attn_window_ms_per_tok"] == pytest.approx(4.0 / 3)
    assert read["moe_select_ms_per_tok"] == pytest.approx(4.0 / 3)
    floor_ms = models.for_config(cfg).moe_bytes(cfg, 1, 1) / 819e9 * 1e3
    assert read["moe_select_roof_pct"] == pytest.approx(100 * floor_ms / (4.0 / 3))
    assert read["prefill_ms_per_ktok"] == pytest.approx(450.0 / 1.124)
