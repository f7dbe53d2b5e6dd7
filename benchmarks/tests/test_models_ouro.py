"""``models/ouro.py`` against the program, on the CPU at toy widths that keep the
shape of the thing (three weight sets run four times, as many kv heads as query
heads, sandwich norms; these tests import JAX and ``dllama_tpu``).  Three
independent forward passes on one seeded file the module wrote: the program (a
prefill and decoding on the contiguous engine; the slot programs with a ragged
chunk over a paged pool), the module's own reference (``last_logits`` /
``logits_at``: every pass over the whole sequence, no cache) and
``tests/reference_impl.py np_forward_ouro`` on weights dequantized by
``mformat.dequantize``; the configuration file against the catalog's rules; the
cost functions at the published sizes against the program's ``CostModel``; the
two new readers.
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import mformat, models

OU_TOY = dict(dim=128, hidden_dim=192, n_layers=3, n_heads=4, n_kv_heads=4,
              vocab_size=288, seq_len=128, rope_theta=1000000.0, norm_eps=1e-6,
              loops=4)
OU_SEED, OU_PROMPT, OU_DECODE = 57, 37, 9
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# OU_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums over twelve block applications.  Read
# when this test was written: the two references 2.4e-6 sigma apart (one is
# float32 on the device, one float64 inside its norms), the engine 2.8e-6 from
# either; the tolerance is ten times that.  The same engine with bfloat16
# activations reads 3.7e-2 and the module's reference rounded to bfloat16
# 5.0e-2: the NEGATIVE CONTROLS, a thousand times over; the least of the wrong
# computations reads 3.5.
OU_TOL_SIGMA = 3e-5
CONFIG = os.path.join(BENCH, "configs", "ouro-2.6b.json")
CELL = os.path.join(BENCH, "cells", "ouro-2.6b.short-reason.json")


def _ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ou_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("ouro")
    path = str(tmp_path_factory.mktemp("ouro") / "ou-toy.m")
    mformat.synthesize(path, model, OU_TOY, OU_SEED, workers=2)
    return model, path


def _ou_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _ou_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks, read by the benchmark's own
    reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(OU_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(OU_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
    for key in ("rms_att", "rms_ffn", "rms_moe", "rms_ffn2"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


def _ou_sigmas(got, want):
    return np.abs(got - want).max(-1) / want.std(-1)


@pytest.fixture(scope="module")
def ou_references(ou_toy):
    """``(tokens, the module's logits at every position, the numpy
    reference's)``."""
    model, path = ou_toy
    toks = np.random.default_rng(7).integers(3, OU_TOY["vocab_size"],
                                             OU_PROMPT + OU_DECODE).tolist()
    logits = model.logits_at(path, [toks], range(len(toks)))[0]
    full = _ref_impl().np_forward_ouro(_ou_dequantized(model, path),
                                       _ou_cfg(path), np.asarray(toks))
    return toks, logits, full


def _ou_engine_logits(path, toks, dtype):
    """The contiguous engine: the prompt in one call, then token by token:
    logits ``(OU_DECODE + 1, vocab)``."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.runtime.engine import Engine

    with mfile.MFile(path) as mf:
        cfg, params = load_params(mf, dtype=dtype, keep_quantized=False)
    with jax.default_matmul_precision("highest"):
        eng = Engine(cfg.with_(quant_impl="xla"), params, batch=1)
        assert eng.cache.k.shape[0] == OU_TOY["n_layers"] * OU_TOY["loops"]
        rows = [eng.prefill(toks[:OU_PROMPT])[0][0]]
        for tok in toks[OU_PROMPT:]:
            rows.append(eng.decode_one(int(tok))[0][0])
    return np.stack(rows)


def test_ouro_header_and_plan_are_what_the_program_parses(ou_toy):
    from dllama_tpu.io import mfile

    model, path = ou_toy
    mf = mfile.MFile(path)
    for key, want in dict(OU_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want), key
    assert mf.spec.arch == mfile.ARCH_OURO == model.ARCH_OURO
    assert mf.spec.header_size == len(model.header(OU_TOY))
    assert tuple(k for k, _, _ in model.EXT_KEYS) \
        == mfile.ARCH_EXT_KEYS[mfile.ARCH_OURO]
    assert model.read_header(path)["loops"] == 4
    ours = model.plan(OU_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    assert [t[0] for t in ours[-6:-2]] == [
        "layers.2.rms_att", "layers.2.rms_ffn", "layers.2.rms_moe",
        "layers.2.rms_ffn2"]
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_ouro_configuration_keeps_every_published_key_and_cuts_nothing():
    """The catalog's rule: every number of the published config under the same
    key; ``reduced`` is empty: no width, layer, head or vocabulary row is cut."""
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == [] and "published" not in config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"Ouro-2.6B"' in l)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    assert (config["total_ut_steps"], config["early_exit_threshold"],
            config["num_hidden_layers"]) == (4, 1, 48)
    assert set(config["assumed"]) == {"sandwich_norms", "final_norm_in_the_loop",
                                      "no_bias", "rope", "cache_index"}
    assert set(config["left_out"]) == {"early_exit_gate", "cache_sharing"}
    assert "holds the model whole" in config["deployment"]
    with open(CELL) as f:
        cell = json.load(f)
    assert cell["argv"] == ["--workers", "tpu:1", "--batch-slots", "8",
                            "--kv-pages", "392", "--kv-page-size", "16",
                            "--max-seq-len", "768", "--max-pending", "64"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "short-reason", 1)
    with open(os.path.join(BENCH, "traffic", "short-reason.json")) as f:
        mix = json.load(f)
    # the longest request fits the context served, and the pool the worst case
    # ISSUE 57's mix, as it wrote it
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 80,
                                    "sigma": 0.5, "min": 32, "max": 176}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 400,
                                    "sigma": 0.3, "min": 256, "max": 576}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 752 <= 768
    assert 392 == 8 * 768 // 16 + 8 and mix["clients"] == 10


def test_ouro_shape_reads_the_published_keys_and_refuses_by_name(monkeypatch):
    with open(CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    assert (shp["dim"], shp["hidden_dim"], shp["n_layers"], shp["n_heads"],
            shp["n_kv_heads"], shp["vocab_size"], shp["seq_len"],
            shp["loops"]) == (2048, 5632, 48, 16, 16, 49152, 65536, 4)
    assert shp["norm_eps"] == 1e-6 and shp["rope_theta"] == 1000000
    last = model.plan(shp)[-1]
    assert 1.84e9 < last[3] + last[4] < 1.86e9          # 1.85 GB on disk
    for patch, says in (
            (dict(use_sliding_window=True), "sliding window"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
            (dict(tie_word_embeddings=True), "tied"),
            (dict(layer_types=["sliding_attention"] * 48), "full attention"),
            (dict(early_exit_threshold=0.5), "exit gate is left out"),
            (dict(head_dim=64), "is not hidden_size"),
            (dict(num_key_value_heads=7), "not a multiple")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))


def test_ouro_last_logits_and_logits_at_are_the_every_position_pass(
        ou_toy, ou_references):
    model, path = ou_toy
    toks, logits, _ = ou_references
    n = len(toks)
    last = model.last_logits(path, [toks[:OU_PROMPT]])[0]
    assert _ou_sigmas(last[None], logits[OU_PROMPT - 1][None])[0] <= OU_TOL_SIGMA
    some = model.logits_at(path, [toks], [0, 1, 5, OU_PROMPT, n - 1])[0]
    assert _ou_sigmas(some, logits[[0, 1, 5, OU_PROMPT, n - 1]]).max() <= OU_TOL_SIGMA


def test_ouro_engine_and_two_references_agree_in_float32(ou_toy, ou_references):
    import jax.numpy as jnp

    toks, logits, full = ou_references
    between = _ou_sigmas(full, logits).max()
    assert between <= OU_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _ou_engine_logits(ou_toy[1], toks, jnp.float32)
    at = slice(OU_PROMPT - 1, OU_PROMPT + OU_DECODE)
    worst = max(_ou_sigmas(engine, logits[at]).max(),
                _ou_sigmas(engine, full[at]).max())
    assert worst <= OU_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_ouro_slot_programs_agree_in_float32(ou_toy, ou_references):
    """The slot path the served cell runs: chunks of 16 with a ragged last one
    through ``forward_slots`` over a paged pool of twelve planes behind a
    permuted page table, then one token a step, a neighbour slot riding along."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import forward_slots, init_kv_pool

    toks, logits, _ = ou_references
    with mfile.MFile(ou_toy[1]) as mf:
        cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    table = jnp.asarray(1 + np.random.default_rng(3).permutation(16).reshape(2, 8),
                        jnp.int32)
    step = jax.jit(lambda p, tk, c, pos, n: forward_slots(p, cfg, tk, c, pos, n, table))
    with jax.default_matmul_precision("highest"):
        cache = init_kv_pool(cfg, 17, 8)
        assert cache.k.shape[0] == 12
        pos, rows = 0, []
        while pos < OU_PROMPT:
            n = min(16, OU_PROMPT - pos)
            tk = np.zeros((2, 16), np.int32)
            tk[1, :n] = toks[pos:pos + n]
            lg, cache = step(params, jnp.asarray(tk), cache,
                             jnp.asarray([0, pos], jnp.int32),
                             jnp.asarray([0, n], jnp.int32))
            pos += n
        rows.append(np.asarray(lg)[1])
        for tok in toks[OU_PROMPT:]:
            lg, cache = step(params, jnp.asarray([[0], [tok]], jnp.int32), cache,
                             jnp.asarray([0, pos], jnp.int32),
                             jnp.asarray([0, 1], jnp.int32))
            rows.append(np.asarray(lg)[1])
            pos += 1
    at = slice(OU_PROMPT - 1, OU_PROMPT + OU_DECODE)
    assert _ou_sigmas(np.stack(rows), logits[at]).max() <= OU_TOL_SIGMA


def test_ouro_tolerance_fails_bfloat16_activations(ou_toy, ou_references):
    """The negative control, both ways: the engine in bfloat16, and the
    reference itself with its activations rounded to bfloat16 (``act_dtype``:
    the reading "the reference in the precision below")."""
    import jax.numpy as jnp

    model, path = ou_toy
    toks, logits, _ = ou_references
    at = slice(OU_PROMPT - 1, OU_PROMPT + OU_DECODE)
    engine = _ou_engine_logits(path, toks, jnp.bfloat16)
    assert _ou_sigmas(engine, logits[at]).max() > 100 * OU_TOL_SIGMA
    low = model.logits_at(path, [toks], range(len(toks)), act_dtype=jnp.bfloat16)[0]
    assert _ou_sigmas(low, logits).max() > 100 * OU_TOL_SIGMA


@pytest.mark.parametrize("wrong", ["read_pass0", "write_next", "no_loop_norm",
                                   "no_post_norm", "one_pass_short"])
def test_ouro_counter_readings_differ_from_the_reference(ou_toy, ou_references,
                                                         wrong):
    """What the tolerance is for: each wrong computation of the loop moves the
    module's own logits by thousands of tolerances."""
    model, path = ou_toy
    toks, logits, _ = ou_references
    kw = dict(passes=3) if wrong == "one_pass_short" else dict(wrong=wrong)
    bad = _ref_impl().np_forward_ouro(_ou_dequantized(model, path), _ou_cfg(path),
                                      np.asarray(toks), **kw)
    assert _ou_sigmas(bad, logits).max() > 1000 * OU_TOL_SIGMA


def test_ouro_cost_functions_at_the_published_sizes_agree_with_the_programs():
    with open(CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    q = 18 / 32
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    head = 49152 * 2048
    assert layer == 51_380_224                          # 51.38 M a layer
    assert model.loop_weight_bytes(cfg) == pytest.approx((4 * 48 * layer + head) * q)
    assert 5.60e9 < model.loop_weight_bytes(cfg) < 5.61e9
    assert model.weight_bytes(cfg, 1, 8) == model.loop_weight_bytes(cfg)
    assert model.kv_bytes_per_token(cfg) == 1_572_864   # 192 planes, bfloat16
    assert model.kv_read_bytes(cfg, 290, rows=8) == 8 * 290 * 1_572_864
    assert model.step_bytes(cfg, 8 * 290, 1, 8) == pytest.approx(
        model.loop_weight_bytes(cfg) + 8 * 290 * 1_572_864)
    assert model.step_flops(cfg, 8, 8 * 290) == pytest.approx(
        2.0 * (8 * (192 * layer + head) + 8 * 290 * 192 * 2 * 2048))
    # 8 rows are 0.16 TFLOP a step: memory-bound by a factor of eight
    assert 0.15e12 < model.step_flops(cfg, 8, 8 * 290) < 0.17e12
    assert model.step_bytes(cfg, 8 * 290, 1, 8) / 819e9 \
        > 8 * model.step_flops(cfg, 8, 8 * 290) / 197e12
    # the program's own arithmetic (obs/cost.py) on the same shape
    from dllama_tpu.obs.cost import CostModel
    cm = CostModel(dim=2048, hidden_dim=5632, n_layers=48, n_heads=16,
                   n_kv_heads=16, vocab_size=49152, weight_codec="q40",
                   kv_codec="kv_bfloat16", kv_el_bytes=2, n_loops=4)
    assert cm.params_per_token == 4 * 48 * layer
    assert cm.weight_read_bytes() == model.loop_weight_bytes(cfg)
    assert cm.kv_write_bytes(1) == model.kv_bytes_per_token(cfg)
    assert cm.kv_read_bytes(289, 1, True) == model.kv_read_bytes(cfg, 290)


def _fake_trace(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip: a step's matmuls and norms."""
    meta = {1: {"tf_op": "jit(step)/while/body/while/body/norm/mul", "program_id": 7},
            2: {"tf_op": "jit(step)/while/body/while/body/norm/post/mul", "program_id": 7},
            3: {"tf_op": "jit(step)/while/body/while/body/qkv/q40_mm", "program_id": 7},
            4: {"tf_op": "jit(step)/while/body/while/body/w13/q40_mm", "program_id": 7},
            5: {"tf_op": "jit(step)/while/body/while/body/attn/paged", "program_id": 7},
            6: {"tf_op": "jit(step)/head/q40_mm", "program_id": 7}}
    if not with_names:
        meta = {k: {"program_id": v["program_id"]} for k, v in meta.items()}
    for k, v in meta.items():
        v.update(display=f"fusion.{k}", name=f"fusion.{k}")
    ops = [(1, 0.0, 2e6), (2, 2e6, 2e6), (3, 4e6, 10e6), (4, 14e6, 20e6),
           (5, 34e6, 12e6), (6, 46e6, 2e6)]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": [(0, "sched.enqueue", 1e6 + 12e6 * k, 1e6, {}) for k in range(4)]}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_ouro_readers_of_the_loop(with_names, tmp_path, monkeypatch):
    """``serve_loop_weight_roof_pct`` divides ``loop_weight_bytes`` over the peak
    bandwidth by the time under the matmul scopes a scheduler step, and
    ``serve_norm_ms_per_step`` reads scope ``norm`` whole (the ``post`` part
    included); a program without the names (the parent, or a stale compile
    cache) gives nothing and does not raise, and so does a configuration whose
    module has no ``loop_weight_bytes``."""
    scopes = importlib.import_module("_scopes")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(scopes.xplane, "find_xplane", lambda out: str(pb))
    monkeypatch.setattr(scopes.xmeta, "load", lambda path, keep_host:
                        _fake_trace(with_names))
    monkeypatch.setattr(scopes, "OUT", str(tmp_path))
    scopes._TABLES.clear()
    with open(CONFIG) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "chips": 1, "config": cfg, "before": {},
           "after": {}, "cell": {"config": "ouro-2.6b", "traffic": "short-reason"},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    roof = importlib.reload(importlib.import_module("serve_loop_weight_roof_pct"))
    norm = importlib.reload(importlib.import_module("serve_norm_ms_per_step"))
    if not with_names:
        assert roof.read(ctx) is None and norm.read(ctx) is None
        return
    assert norm.read(ctx) == pytest.approx(4.0 / 4)
    model = models.for_config(cfg)
    want = 100 * model.loop_weight_bytes(cfg) / 819e9 / (32e-3 / 4)
    assert roof.read(ctx) == pytest.approx(want) and want < 100
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        assert roof.read(dict(ctx, config=json.load(f))) is None


@pytest.mark.parametrize("control, passes", [(None, True), ("float8", False),
                                             ("a-pass-fewer", False)])
def test_check_loops_passes_the_reference_and_sees_each_control(ou_toy, control,
                                                                 passes):
    """``tools/check_loops.py``'s two comparisons at toy widths, with no engine:
    the reference in the program's place passes both, and each ``--control``
    (the reference in float8; a pass fewer) fails both."""
    import sys
    from harness import correct
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    try:
        tool = importlib.import_module("check_loops")
    finally:
        sys.path.remove(os.path.join(BENCH, "tools"))
    model, path = ou_toy
    vocab = OU_TOY["vocab_size"]
    wrong = tool.wrong_computation(model, path, control) if control else {}
    seqs = [list(map(int, s)) for s in correct.check_prompts(
        tool.SEED, tool.N_PROMPTS, tool.PROMPT_LEN + tool.STEPS, vocab)]
    at = range(tool.PROMPT_LEN - 1, tool.PROMPT_LEN + tool.STEPS)
    got = model.logits_at(path, seqs, at, **wrong)
    assert tool.judge_logits(model, path, seqs, got)["ok"] is passes
    fed, chosen = tool.control_tokens(model, path, vocab, wrong, True)
    verdict = tool.judge_tokens(model, path, fed, chosen)
    assert verdict["ok"] is passes and verdict["compared"] == "greedy tokens"
