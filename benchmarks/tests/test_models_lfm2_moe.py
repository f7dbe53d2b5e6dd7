"""``models/lfm2_moe.py`` against the program, on the CPU at toy widths that
keep every ratio (periods ``conv, conv, attention, conv``, two leading dense
layers, 3 taps, 16 experts of which 4 a token, 4 query heads a kv head, a head
size stated in the header; these tests import JAX and ``dllama_tpu``).  Three
independent forward passes on one seeded file the module wrote: the program (a
chunked prefill and decoding on the contiguous engine, its convolution state a
ring beside the cache; the slot programs with a ragged chunk), the module's own
reference (``last_logits`` / ``logits_at`` / ``routing_margins``: the
convolution as shifted copies of ``z``, no state), and
``tests/reference_impl.py np_forward_lfm2_moe`` on weights dequantized by
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

LF_TOY = dict(dim=64, hidden_dim=96, n_layers=8, n_heads=8, n_kv_heads=2,
              vocab_size=288, seq_len=96, rope_theta=1000000.0, n_experts=16,
              n_active_experts=4, moe_hidden_dim=32, n_dense_layers=2,
              routed_scale=1.0, norm_eps=1e-5, head_dim=8, window_period=4,
              window_full_at=2, conv_taps=3)
LF_SEED, LF_PROMPT, LF_DECODE = 47, 41, 19
# Logits are compared in sigmas: the reference's own spread over the vocabulary
# at that position, as harness/correct.py does on the chip.
#
# LF_TOL_SIGMA, float32 end to end: the engine loads the file dequantized, so
# all three sides read the same 4-bit weights exactly and compute in float32;
# they differ by the order of float32 sums alone (a few 1e-6 sigma when this
# test was written).  The same engine with bfloat16 activations reads 1e-2 and
# more: the NEGATIVE CONTROL.  Each wrong computation below reads hundredths of
# a sigma or more.
LF_TOL_SIGMA = 2e-5
# LF_TOL_Q40_SIGMA, the packed path the cells serve, on MARGIN-STEADY positions
# (as SmallThinker's test): the packed path rounds each matmul's activation to
# bfloat16, and a conv layer multiplies three of those products.  Read when
# this test was written: 0.063 sigma at worst over the positions whose margin
# exceeds the module's MARGIN_STEADY (0.24 where a position under it flips an
# expert); bfloat16 activations throughout read 0.33 on the same positions.
# LF_MAX_LEFT_OUT bounds the share left out.
LF_TOL_Q40_SIGMA = 0.09
LF_MAX_LEFT_OUT = 0.75
# a prefill chunk of 16 rows: the prompt of 41 is 16 + 16 + a tail of 9
LF_SMALL_PRODUCT = 4 * 16 * 64 * 16
CONFIG = os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")


def _ref_impl():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lf_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("lfm2_moe")
    path = str(tmp_path_factory.mktemp("lfm2") / "lf-toy.m")
    mformat.synthesize(path, model, LF_TOY, LF_SEED, workers=2)
    return model, path


def _lf_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _lf_dequantized(model, path: str) -> dict:
    """The file's tensors in the program's stacks (by layer kind, by FFN
    segment), read by the benchmark's own reader."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(LF_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(LF_TOY["n_layers"])
    att = [i for i in layers if i % 4 == 2]
    conv = [i for i in layers if i % 4 != 2]
    dense = range(LF_TOY["n_dense_layers"])
    moe = range(LF_TOY["n_dense_layers"], LF_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in att])
           for k in ("wq", "wk", "wv", "wo")}
    for key in ("q_norm", "k_norm"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in att])
    for key in ("conv_in", "conv_out"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}").T for i in conv])
    out["conv_taps"] = np.stack([tensor(f"layers.{i}.conv_taps").reshape(
        LF_TOY["dim"], LF_TOY["conv_taps"]) for i in conv])
    for key in ("rms_att", "rms_ffn"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    for key in ("w1", "w2", "w3"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}").T for i in dense])
    out["router"] = np.stack([tensor(f"layers.{i}.moe_router").T for i in moe])
    out["router_bias"] = np.stack([tensor(f"layers.{i}.moe_router_bias") for i in moe])
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack(
            [tensor(f"layers.{i}.experts.{e}.{key}").T
             for e in range(LF_TOY["n_experts"])]) for i in moe])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


@pytest.fixture(scope="module")
def lf_references(lf_toy):
    model, path = lf_toy
    rng = np.random.RandomState(LF_SEED)
    toks = [int(t) for t in rng.randint(3, LF_TOY["vocab_size"],
                                        LF_PROMPT + LF_DECODE)]
    logits, margins = model.routing_margins(path, [toks])
    weights, cfg = _lf_dequantized(model, path), _lf_cfg(path)
    full = _ref_impl().np_forward_lfm2_moe(weights, cfg, np.asarray(toks))
    return toks, logits[0], margins[0], full, weights, cfg


def _lf_load(path: str, dtype, packed: bool):
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params

    mf = mfile.MFile(path)
    return load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                       dtype=dtype, keep_quantized=packed)


def _lf_engine_logits(path: str, toks: list[int], steps: int, dtype,
                      packed: bool, monkeypatch) -> np.ndarray:
    """The program's logits after a chunked prefill of the prompt and after
    each of ``steps`` decoded tokens (seeded, not greedy)."""
    import jax

    from dllama_tpu.models import config as config_mod
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", LF_SMALL_PRODUCT)
    cfg, params = _lf_load(path, dtype, packed)
    assert cfg.prefill_chunk() == 16
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 seq_len=LF_TOY["seq_len"])
    assert eng.cache.cz.shape[:3] == (6, 1, 1) and eng.cache.k.shape[0] == 2
    logits, _ = eng.prefill(toks[:LF_PROMPT])   # 16 + 16 + a tail of 9
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[LF_PROMPT:LF_PROMPT + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return np.stack(got)


def _lf_sigmas(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.abs(got - ref).max(-1) / ref.std(-1)


def test_lfm2_header_and_plan_are_what_the_program_parses(lf_toy):
    from dllama_tpu.io import mfile

    model, path = lf_toy
    mf = mfile.MFile(path)
    for key, want in dict(LF_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == pytest.approx(want), key
    assert mf.spec.arch == mfile.ARCH_LFM2_MOE == model.ARCH_LFM2_MOE
    assert mf.spec.header_size == len(model.header(LF_TOY))
    assert tuple(k for k, _, _ in model.EXT_KEYS) \
        == mfile.ARCH_EXT_KEYS[mfile.ARCH_LFM2_MOE]
    hd = model.read_header(path)
    assert (hd["conv_taps"], hd["window_period"], hd["window_full_at"]) == (3, 4, 2)
    ours = model.plan(LF_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    shapes = dict((t[0], t[1]) for t in ours)
    assert shapes["layers.0.conv_in"] == (192, 64) and shapes["layers.0.w1"] == (96, 64)
    assert shapes["layers.0.conv_taps"] == (192,) and "layers.0.wq" not in shapes
    assert shapes["layers.2.wq"] == (64, 64) and shapes["layers.2.wk"] == (16, 64)
    assert "layers.2.conv_in" not in shapes and "layers.1.moe_router" not in shapes
    assert shapes["layers.2.moe_router"] == (16, 64)
    assert "layers.2.experts.15.up" in shapes and "layers.2.shared_w1" not in shapes
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_lfm2_configuration_keeps_every_published_key_but_the_reduced():
    """The catalog's rule: every number of the published config under the same
    key, but for the keys of ``reduced``, whose published values are kept
    beside them; depth only: no width, no expert, no vocabulary row."""
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"]["num_hidden_layers"] == 40
    assert len(config["published"]["layer_types"]) == 40
    depth = config["num_hidden_layers"]
    assert depth % 4 == 0 and depth >= 8        # whole periods; the floors
    assert config["layer_types"] == config["published"]["layer_types"][:depth]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"LFM2-24B-A2B"' in l)
        assert config["source"] == row["source_url"]
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert set(config["assumed"]) == {"conv_operator", "qk_norm", "router", "head",
                                      "residuals", "head_dim"}
    assert "pipeline of two stages" in config["deployment"]
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "num_experts", "num_experts_per_tok", "num_attention_heads",
                  "vocab_size", "conv_L_cache"):
        assert width not in config["reduced"]


def test_lfm2_shape_reads_the_published_keys_and_refuses_by_name(monkeypatch):
    with open(CONFIG) as f:
        config = json.load(f)
    model = models.for_config(config)
    shp = model.shape(config)
    layers = config["num_hidden_layers"]
    assert (shp["dim"], shp["hidden_dim"], shp["moe_hidden_dim"], shp["n_layers"],
            shp["n_heads"], shp["n_kv_heads"], shp["head_dim"], shp["window_period"],
            shp["window_full_at"], shp["conv_taps"], shp["n_experts"],
            shp["n_active_experts"], shp["n_dense_layers"], shp["vocab_size"],
            shp["seq_len"]) == (2048, 11776, 1536, layers, 32, 8, 64, 4, 2, 3, 64,
                                4, 2, 65536, 128000)
    assert shp["routed_scale"] == 1.0 and shp["norm_eps"] == 1e-5
    size = model.plan(shp)[-1]
    per_layer = (size[3] + size[4] - 0.62e9) / layers   # less embedding and head
    assert 0.32e9 < per_layer < 0.35e9
    for patch, says in (
            (dict(conv_bias=True), "conv_bias is true"),
            (dict(norm_topk_prob=False), "norm_topk_prob is false"),
            (dict(use_expert_bias=False), "use_expert_bias is false"),
            (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
             "rope_type is not default"),
            (dict(layer_types=["conv"] * layers), "does not cover the layers"),
            (dict(layer_types=["conv", "full_attention"] * (layers // 2 - 1)
                  + ["full_attention", "conv"]), "not whole periods"),
            (dict(num_experts_per_tok=65), "not in 1..num_experts"),
            (dict(num_dense_layers=layers), "leaves no expert layer"),
            (dict(conv_L_cache=1), "keeps no state")):
        with pytest.raises(SystemExit, match=says):
            model.shape(dict(config, **patch))
    # a checkout whose program lacks the arch id fails at once, by name
    monkeypatch.setattr(model, "_program_has_the_arch", lambda: False)
    with pytest.raises(SystemExit, match="no arch id 0xABCD07"):
        model.shape(config)


def test_lfm2_last_logits_and_logits_at_are_the_every_position_pass(
        lf_toy, lf_references):
    model, path = lf_toy
    toks, logits, margins, _, _, _ = lf_references
    n = LF_PROMPT + LF_DECODE
    assert margins.shape == (n, 6)                  # the expert layers'
    last = model.last_logits(path, [toks[:LF_PROMPT]])[0]
    assert _lf_sigmas(last[None], logits[LF_PROMPT - 1][None])[0] <= LF_TOL_SIGMA
    some = model.logits_at(path, [toks], [0, 1, 5, LF_PROMPT, n - 1])[0]
    assert _lf_sigmas(some, logits[[0, 1, 5, LF_PROMPT, n - 1]]).max() <= LF_TOL_SIGMA


def test_lfm2_engine_and_two_references_agree_in_float32(
        lf_toy, lf_references, monkeypatch):
    import jax.numpy as jnp

    toks, logits, _, full, _, _ = lf_references
    between = _lf_sigmas(full, logits).max()
    assert between <= LF_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _lf_engine_logits(lf_toy[1], toks, LF_DECODE, jnp.float32,
                               packed=False, monkeypatch=monkeypatch)
    at = slice(LF_PROMPT - 1, LF_PROMPT + LF_DECODE)
    worst = max(_lf_sigmas(engine, logits[at]).max(),
                _lf_sigmas(engine, full[at]).max())
    assert worst <= LF_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_lfm2_slot_programs_with_the_state_beside_the_pool_agree_in_float32(
        lf_toy, lf_references):
    """The paged path the served cell runs: chunks of 16 with a ragged last one
    through ``forward_slots`` over the attention layers' pool and the slot's
    own state ring, then one token a step."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.transformer import forward_slots, init_kv_pool

    toks, logits, _, _, _, _ = lf_references
    cfg, params = _lf_load(lf_toy[1], jnp.float32, packed=False)
    cache = init_kv_pool(cfg, 30, 4, slots=2, max_pages=24)
    assert cache.k.shape[0] == 2 and cache.cz.shape[:3] == (6, 2, 1)
    table = jnp.asarray(np.stack([np.zeros(24, np.int32), np.random.RandomState(
        2).permutation(np.arange(1, 25)).astype(np.int32)]))
    errs, pos = [], 0
    with jax.default_matmul_precision("highest"):
        for t, n in [(16, 16), (16, 16), (16, 9)] + [(1, 1)] * LF_DECODE:
            tk = np.zeros((2, t), np.int32)
            tk[1, :n] = toks[pos:pos + n]
            lg, cache = forward_slots(
                params, cfg, jnp.asarray(tk), cache,
                jnp.asarray([0, pos], jnp.int32), jnp.asarray([0, n], jnp.int32),
                table)
            pos += n
            errs.append(_lf_sigmas(np.asarray(lg)[1:], logits[pos - 1][None])[0])
    assert pos == LF_PROMPT + LF_DECODE and max(errs) <= LF_TOL_SIGMA, errs


def test_lfm2_tolerance_fails_bfloat16_activations(lf_toy, lf_references,
                                                   monkeypatch):
    """NEGATIVE CONTROL for ``LF_TOL_SIGMA``: the program with bfloat16
    activations, the next precision below the float32 that run states."""
    import jax.numpy as jnp

    toks, logits, _, _, _, _ = lf_references
    engine = _lf_engine_logits(lf_toy[1], toks, 0, jnp.bfloat16, packed=False,
                               monkeypatch=monkeypatch)
    assert _lf_sigmas(engine, logits[LF_PROMPT - 1][None])[0] > 100 * LF_TOL_SIGMA


@pytest.mark.parametrize("wrong", ["split_order", "gate_after", "taps_reversed",
                                   "no_rope", "no_head_norm", "attention_first",
                                   "bias_in_weights", "softmax_router"])
def test_lfm2_reference_with_one_fault_disagrees(lf_references, wrong):
    toks, logits, _, _, weights, cfg = lf_references
    other = _ref_impl().np_forward_lfm2_moe(weights, cfg, np.asarray(toks),
                                            wrong=wrong)
    assert _lf_sigmas(other, logits).max() > 100 * LF_TOL_SIGMA


def test_lfm2_packed_engine_agrees_on_margin_steady_positions(
        lf_toy, lf_references, monkeypatch):
    import jax.numpy as jnp

    model, path = lf_toy
    toks, logits, margins, _, _, _ = lf_references
    at = slice(LF_PROMPT - 1, LF_PROMPT + LF_DECODE)
    steady = margins[at].min(-1) > model.MARGIN_STEADY
    left_out = 1.0 - steady.mean()
    assert left_out <= LF_MAX_LEFT_OUT, (
        f"{left_out:.0%} of {steady.size} positions have a routing margin "
        f"under {model.MARGIN_STEADY}")
    engine = _lf_engine_logits(path, toks, LF_DECODE, jnp.float32, packed=True,
                               monkeypatch=monkeypatch)
    worst = _lf_sigmas(engine, logits[at])[steady].max()
    assert worst <= LF_TOL_Q40_SIGMA, (
        f"{worst:.4f} sigma over {int(steady.sum())} margin-steady positions")


def test_lfm2_cost_functions_at_the_published_sizes():
    with open(CONFIG) as f:
        cfg = json.load(f)
    model = models.for_config(cfg)
    layers = cfg["num_hidden_layers"]
    n_att, n_conv, n_moe = layers // 4, layers - layers // 4, layers - 2
    q = 18 / 32
    att = 2 * 2048 * 2048 + 2 * 2048 * 512           # 10.49 M
    conv = 4 * 2048 * 2048                           # 16.78 M
    one = 3 * 2048 * 1536                            # 9.437 M
    dense, head = 3 * 2048 * 11776, 65536 * 2048
    assert (att, conv, one) == (10_485_760, 16_777_216, 9_437_184)
    assert model.layer_kinds(cfg) == (n_att, n_conv)
    # 16 rows of 4 of 64 hit 41 of the 64 under uniform routing
    assert 41.0 < model.experts_read(cfg, 16) < 41.5
    assert model.experts_read(cfg, 1) == pytest.approx(4.0)
    moe = n_moe * (64 * 2048 + model.experts_read(cfg, 16) * one) * q
    assert model.moe_bytes(cfg, 1, 16) == pytest.approx(moe)
    assert model.moe_bytes(cfg) == pytest.approx(n_moe * (64 * 2048 + 4 * one) * q)
    assert model.weight_bytes(cfg, 1, 16) == pytest.approx(
        (n_att * att + n_conv * conv + 2 * dense + head) * q + moe)
    # a cached token is the attention layers' alone: 16,384 B at 8 of them
    assert model.kv_bytes_per_token(cfg) == n_att * 2 * 8 * 64 * 2
    assert model.kv_read_bytes(cfg, 300, rows=16) == 16 * 300 * n_att * 2048
    # a conv layer: both matrices packed, 6144 f32 taps, 2 rows read + 1 written
    state = 3 * 2048 * 2
    assert model.conv_bytes(cfg) == pytest.approx(
        n_conv * (conv * q + 4 * 6144 + state))
    assert model.conv_bytes(cfg, 1, 16) == pytest.approx(
        n_conv * (conv * q + 4 * 6144 + 16 * state))
    assert 9.4e6 < model.conv_bytes(cfg) / n_conv < 9.5e6
    assert model.conv_flops(cfg, 16) == 2.0 * n_conv * 16 * (conv + 5 * 2048)
    assert model.step_bytes(cfg, 16 * 300, 1, 16) == pytest.approx(
        model.weight_bytes(cfg, 1, 16) + n_conv * 16 * state
        + 16 * model.kv_read_bytes(cfg, 300))
    assert model.step_flops(cfg, 1, 300) == pytest.approx(2.0 * (
        n_att * att + n_conv * conv + 2 * dense + head
        + n_moe * (64 * 2048 + 4 * one) + 2 * 32 * 64 * n_att * 300))


def _fake_conv_trace(with_names: bool) -> dict:
    """What ``xmeta.load`` returns for one chip: a decode program (7: it
    samples) and a prefill program (9), the operator's four parts in each."""
    meta = {1: {"tf_op": "jit(chunk)/while/body/qkv/conv/q40_mm", "program_id": 7},
            2: {"tf_op": "jit(chunk)/while/body/kv_write/conv/dus", "program_id": 7},
            3: {"tf_op": "jit(chunk)/while/body/attn/conv/fusion", "program_id": 7},
            4: {"tf_op": "jit(chunk)/while/body/wo/conv/q40_mm", "program_id": 7},
            5: {"tf_op": "jit(chunk)/while/body/sample/argmax", "program_id": 7},
            6: {"tf_op": "jit(chunk)/while/body/qkv/q40_mm", "program_id": 7},
            7: {"tf_op": "jit(step)/qkv/conv/q40_mm", "program_id": 9}}
    if not with_names:
        meta = {k: {"program_id": v["program_id"]} for k, v in meta.items()}
    ops = [(1, 0.0, 3e6), (2, 3e6, 0.5e6), (3, 4e6, 1e6), (4, 5e6, 1.5e6),
           (5, 7e6, 1e6), (6, 8e6, 2e6), (7, 20e6, 40e6)]
    return {"devices": {"/device:TPU:0": {"meta": meta, "ops": ops, "modules": []}},
            "host": []}


@pytest.mark.parametrize("with_names", [True, False], ids=["change", "parent"])
def test_lfm2_readers_of_the_conv_part(with_names, tmp_path, monkeypatch):
    """``conv_ms_per_tok`` / ``conv_roof_pct`` read the part ``conv`` under the
    four scopes in the decode programs alone (the prefill program's 40 ms stay
    out, and so does the attention layers' ``qkv``); ``serve_conv_ms_per_step``
    / ``serve_conv_roof_pct`` the same per scheduler step over every program,
    against ``conv_bytes`` at the step's rows; a program without the names (the
    parent) gives nothing and does not raise."""
    decode = importlib.import_module("_decode")
    parts = importlib.import_module("_parts")
    scopes = importlib.import_module("_scopes")
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    for mod in (decode, parts):
        monkeypatch.setattr(mod.xplane, "find_xplane", lambda out: str(pb))
        monkeypatch.setattr(mod.xmeta, "load", lambda path, keep_host:
                            _fake_conv_trace(with_names))
    decode._CACHE.clear()
    parts._SECONDS.clear()
    tab = {"steps": 4, "busy_s": 0.049, "scopes": {"qkv": 0.045}, "scoped": with_names}
    monkeypatch.setattr(scopes, "table", lambda ctx: tab)
    monkeypatch.setattr(parts, "table", lambda ctx: tab)
    monkeypatch.setattr(scopes, "scoped", lambda t: bool(t and t["scoped"]))
    monkeypatch.setattr(parts, "scoped", lambda t: bool(t and t["scoped"]))
    with open(CONFIG) as f:
        cfg = json.load(f)
    ctx = {"trace": {"chips": 1}, "traced_window": (100.0, 105.0), "chips": 1,
           "window": (80.0, 125.0), "config": cfg,
           "samples": [(90.0, {"sched_slots_occupied": 16})],
           "records": [{"ok": True, "cut": False, "n_prompt": 100,
                        "times": [100.5, 101.0, 104.0, 106.0]}],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read = {n: importlib.reload(importlib.import_module(n)).read(ctx) for n in (
        "conv_ms_per_tok", "conv_roof_pct", "serve_conv_ms_per_step",
        "serve_conv_roof_pct")}
    if not with_names:
        assert set(read.values()) == {None}
        return
    model = models.for_config(cfg)
    assert read["conv_ms_per_tok"] == pytest.approx(6.0 / 3)     # 3 tokens traced
    assert read["conv_roof_pct"] == pytest.approx(
        100 * model.conv_bytes(cfg, 1, 1) / 819e9 / 2e-3)
    assert read["serve_conv_ms_per_step"] == pytest.approx(46.0 / 4)
    assert read["serve_conv_roof_pct"] == pytest.approx(
        100 * model.conv_bytes(cfg, 1, 16) / 819e9 / 11.5e-3)
