"""``ARCH_OLMOE`` (0xABCD03): the format, the converter against HuggingFace's own
``OlmoeForCausalLM``, the loader, the flags the arch id sets, the q/k norm on a
tp mesh, and the proof that the three older architectures did not move.

The block: q/k RMSNorm over the whole projection before the head split and
RoPE (rotate-half, rows not permuted), top-k router probabilities used
unnormalised.  ``benchmarks/tests/test_models_olmoe.py`` holds the engine and
the two plain references to each other at 64 experts top-8; here the oracle is
the published implementation itself, at toy widths.
"""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "converter"))

from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models.config import ModelConfig, tiny_config
from dllama_tpu.models.params import (init_params, load_params, param_shapes,
                                      quantize_matmuls)
from dllama_tpu.models.transformer import forward, init_kv_cache
from dllama_tpu.ops import q40
from dllama_tpu.parallel import sharding
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine

TOKENS = [[3, 17, 42, 99, 7, 64, 5, 23, 81]]


def _spec(arch, n_experts=4, ftype=quants.F32):
    return mfile.ModelSpec(
        arch=arch, dim=64, hidden_dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        n_experts=n_experts, n_active_experts=2 if n_experts else 0,
        vocab_size=96, seq_len=32, weights_ftype=ftype, header_size=120)


def test_arch_id_and_the_flags_derived_from_it():
    assert mfile.ARCH_OLMOE == 0xABCD03
    assert mfile.ARCH_NAMES[mfile.ARCH_OLMOE] == "olmoe"
    flags = {name: (tiny_config(arch=arch, n_experts=4, n_active_experts=2).qk_norm,
                    tiny_config(arch=arch, n_experts=4, n_active_experts=2).norm_topk_prob,
                    tiny_config(arch=arch).rope_interleaved)
             for arch, name in mfile.ARCH_NAMES.items()}
    assert flags == {"llama": (False, True, True), "grok1": (False, True, False),
                     "mixtral": (False, True, False), "olmoe": (True, False, False),
                     "deepseek2": (False, False, True),
                     "smallthinker": (False, True, False),
                     "exaone_moe": (False, True, False),
                     "lfm2_moe": (False, True, False),
                     "brumby": (False, True, False),
                     "ouro": (False, True, False),
                     "falcon_h1": (False, True, False),
                     "granitemoehybrid": (False, True, False)}
    shapes = param_shapes(tiny_config(arch=mfile.ARCH_OLMOE, n_experts=4,
                                      n_active_experts=2))
    assert (shapes["q_norm"], shapes["k_norm"]) == ((2, 64), (2, 32))
    assert "q_norm" not in param_shapes(tiny_config(arch=mfile.ARCH_MIXTRAL,
                                                    n_experts=4, n_active_experts=2))


def test_plan_puts_the_two_norms_between_wo_and_the_router():
    names = [t.name for t in mfile.tensor_plan(_spec(mfile.ARCH_OLMOE))]
    at = names.index("layers.1.wo")
    assert names[at + 1:at + 5] == ["layers.1.q_norm", "layers.1.k_norm",
                                    "layers.1.moe_router", "layers.1.experts.0.up"]
    by = {t.name: t for t in mfile.tensor_plan(_spec(mfile.ARCH_OLMOE))}
    assert (by["layers.0.q_norm"].shape, by["layers.0.k_norm"].shape) == ((64,), (32,))
    assert by["layers.0.q_norm"].ftype == by["layers.0.k_norm"].ftype == quants.F32


# sha256 of repr([(name, shape, ftype, offset, nbytes)]) of the plan of _spec(arch)
# on the parent of the PR that added ARCH_OLMOE: their files are laid out as before
PARENT_PLANS = {
    mfile.ARCH_LLAMA: "8435b5c67b1074b0355715c34fe91c710b4b672f64e6db7bfe476ce37bcd331e",
    mfile.ARCH_GROK1: "6800a7184e83bf3901b1d64ba91bba350cac33b86d5ed3f15f33e347ef39303e",
    mfile.ARCH_MIXTRAL: "dca00f7d8e58179adb059ed066f21712b6975c3116bd5276a6674a3be516cce2",
}


@pytest.mark.parametrize("arch", sorted(PARENT_PLANS), ids=lambda a: mfile.ARCH_NAMES[a])
def test_older_architectures_files_are_laid_out_as_before(arch):
    plan = mfile.tensor_plan(_spec(arch, n_experts=0 if arch == mfile.ARCH_LLAMA else 4))
    text = repr([(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in plan])
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PLANS[arch]


def test_validate_spec_knows_the_arch_and_wants_its_experts(tmp_path):
    assert mfile.validate_spec(_spec(mfile.ARCH_OLMOE), "x.m").arch == mfile.ARCH_OLMOE
    with pytest.raises(ArtifactError, match="n_active_experts"):
        mfile.validate_spec(_spec(mfile.ARCH_OLMOE, n_experts=0), "x.m")
    bad = _spec(mfile.ARCH_OLMOE)
    bad.arch = max(mfile.ARCH_NAMES) + 1   # the first id no arch has
    with pytest.raises(ArtifactError, match="unknown architecture"):
        mfile.validate_spec(bad, "x.m")


# ---- the converter against the published implementation ------------------

HF_CONFIG = dict(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=4, vocab_size=128,
                 max_position_embeddings=64, num_experts=16, num_experts_per_tok=4,
                 rope_theta=10000.0, rms_norm_eps=1e-5, tie_word_embeddings=False)


@pytest.fixture(scope="module")
def hf_olmoe_dir(tmp_path_factory):
    import torch
    from transformers import OlmoeConfig, OlmoeForCausalLM
    torch.manual_seed(0)
    model = OlmoeForCausalLM(OlmoeConfig(**HF_CONFIG)).eval()
    with torch.no_grad():  # norm weights of 1 would not tell a missing q/k norm weight
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.copy_(1.0 + 0.2 * torch.randn_like(p))
    d = tmp_path_factory.mktemp("hf_olmoe")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_convert_hf_olmoe_logits_match_torch(hf_olmoe_dir, tmp_path):
    """HF safetensors -> .m -> the loader -> forward, against torch's
    ``OlmoeForCausalLM``: tensor names and order, unpermuted q/k rows with
    rotate-half RoPE, the whole-projection q/k norms and their weights, the
    softmax over all experts with the top-k used unnormalised."""
    import convert_hf
    import torch

    folder, torch_model = hf_olmoe_dir
    out = str(tmp_path / "olmoe.m")
    convert_hf.convert(folder, quants.F32, out)
    mf = mfile.MFile(out)
    assert (mf.spec.arch, mf.spec.n_experts, mf.spec.n_active_experts) == \
        (mfile.ARCH_OLMOE, 16, 4)
    cfg, params = load_params(mf)
    cfg = cfg.with_(dtype=jnp.float32)
    assert params["q_norm"].shape == (2, 64) and params["k_norm"].shape == (2, 64)
    with torch.no_grad():
        want = torch_model(torch.tensor(TOKENS)).logits.numpy()[0]
    logits, _ = forward(params, cfg, jnp.asarray(TOKENS), init_kv_cache(cfg, 1),
                        jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=2e-4, rtol=1e-3)
    # decode through the cache (the few-row strategy of moe_ffn) agrees too
    cache, steps = init_kv_cache(cfg, 1), []
    for i, t in enumerate(TOKENS[0]):
        lg, cache = forward(params, cfg, jnp.asarray([[t]]), cache, jnp.int32(i))
        steps.append(np.asarray(lg)[0, 0])
    np.testing.assert_allclose(np.stack(steps), want, atol=2e-4, rtol=1e-3)


def test_convert_hf_olmoe_q40_loads_packed_and_runs(hf_olmoe_dir, tmp_path):
    import convert_hf

    folder, _ = hf_olmoe_dir
    f32, packed = str(tmp_path / "f32.m"), str(tmp_path / "q40.m")
    convert_hf.convert(folder, quants.F32, f32)
    convert_hf.convert(folder, quants.Q40, packed)
    mf = mfile.MFile(packed)
    np.testing.assert_array_equal(mf.tensor("layers.1.q_norm"),
                                  mfile.MFile(f32).tensor("layers.1.q_norm"))
    cfg, params = load_params(mf, keep_quantized=True)
    assert isinstance(params["up"], q40.QTensor) and params["q_norm"].dtype == np.float32
    dense_cfg, dense = load_params(mfile.MFile(f32))
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    ref = Engine(dense_cfg, dense, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    got, _ = eng.prefill(TOKENS[0])
    want, _ = ref.prefill(TOKENS[0])
    # 4-bit weights of a 64-wide toy: coarse, but a wrong tensor order or a
    # missing norm is whole sigmas
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 0.5 * np.asarray(want).std()


@pytest.mark.parametrize("key,value,says", [
    ("norm_topk_prob", True, "norm_topk_prob"),
    ("clip_qkv", 8.0, "clip_qkv"),
    ("attention_bias", True, "attention_bias"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
])
def test_convert_hf_refuses_olmoe_variants_it_would_get_wrong(tmp_path, key, value, says):
    import convert_hf

    config = dict(HF_CONFIG, model_type="olmoe", **{key: value})
    (tmp_path / "config.json").write_text(json.dumps(config))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.Q40)


def test_convert_hf_reads_both_expert_count_keys(tmp_path):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(HF_CONFIG, model_type="olmoe")))
    spec = convert_hf.load_spec(str(tmp_path), quants.Q40)
    assert (spec.arch, spec.n_experts, spec.n_active_experts) == (mfile.ARCH_OLMOE, 16, 4)
    mixtral = {k: v for k, v in HF_CONFIG.items() if k != "num_experts"}
    (tmp_path / "config.json").write_text(json.dumps(dict(
        mixtral, model_type="mixtral", num_local_experts=8, num_experts_per_tok=2)))
    spec = convert_hf.load_spec(str(tmp_path), quants.Q40)
    assert (spec.arch, spec.n_experts, spec.n_active_experts) == (mfile.ARCH_MIXTRAL, 8, 2)
    assert convert_hf.hf_source_name("layers.1.wq", spec) == \
        ("model.layers.1.self_attn.q_proj.weight", True)
    assert convert_hf.hf_source_name("layers.1.experts.3.up", spec) == \
        ("model.layers.1.block_sparse_moe.experts.3.w3.weight", False)


# ---- the q/k norm on a tp mesh -------------------------------------------

def test_qk_norm_on_a_tp_mesh_is_the_whole_projections():
    """On a tp mesh q and k come out of their matmuls sharded along the axis
    the norm's mean runs over.  The mean must be the whole projection's (an
    all-reduce), never a shard's: tp=4 against tp=1, dense and packed."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = tiny_config(arch=mfile.ARCH_OLMOE, n_experts=8, n_active_experts=2,
                      dim=256, hidden_dim=128, n_layers=2, n_heads=8,
                      n_kv_heads=8, vocab_size=128, seq_len=32)
    params = init_params(cfg, seed=3)
    rng = np.random.RandomState(5)
    # heads of very different size, so that a per-shard mean would show
    scale = np.repeat(np.exp(rng.randn(8)), 32).astype(np.float32)
    for key in ("wq", "wk"):
        params[key] = params[key] * scale
    params["q_norm"] = jnp.asarray(1.0 + 0.2 * rng.randn(2, 256), jnp.float32)
    params["k_norm"] = jnp.asarray(1.0 + 0.2 * rng.randn(2, 256), jnp.float32)
    assert sharding.param_specs(cfg)["q_norm"] == sharding.REPL
    prompt = [1, 2, 3, 4, 5]
    one = make_mesh(tp=1, devices=jax.devices()[:1])
    four = make_mesh(tp=4, devices=jax.devices()[:4])
    for p, c in ((params, cfg),
                 (quantize_matmuls(params, cfg), cfg.with_(quant_impl="xla"))):
        l1, _ = Engine(c, p, mesh=one).prefill(prompt)
        l4, _ = Engine(c, p, mesh=four).prefill(prompt)
        np.testing.assert_allclose(l1, l4, rtol=0, atol=1e-3 + 1e-3 * np.abs(l1).max())


# ---- the older architectures did not move ---------------------------------

# sha256 of str(jax.make_jaxpr(forward)) for the configurations below on the
# parent of the PR that added ARCH_OLMOE (named scopes are not part of a
# jaxpr's text): the same jaxpr is the same program, so Mixtral's and Grok-1's
# logits are what they were, to the bit, whatever machine runs this.
PARENT_JAXPRS = {
    ("mixtral", False, 1): "ba1abd0d70cd7d4d", ("mixtral", False, 7): "d5df8100c334cd89",
    ("mixtral", True, 1): "e9904aac89f8410f", ("mixtral", True, 7): "1a018d945bce5470",
    ("grok1", False, 1): "caef8a1515f54acd", ("grok1", False, 7): "00f5d7b5efa060ad",
    ("grok1", True, 1): "25b6d53deaf67f4e", ("grok1", True, 7): "ef69892429b06a04",
    ("mixtral16", False, 1): "38d14217d4ac779e", ("mixtral16", False, 7): "6f7f2f83f8895c5d",
    ("mixtral16", True, 1): "1c6eb74559582b9a", ("mixtral16", True, 7): "cabe4fab9f91b632",
}
OLDER = {
    "mixtral": dict(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2),
    "grok1": dict(arch=mfile.ARCH_GROK1, n_experts=4, n_active_experts=2,
                  hidden_act=mfile.ACT_GELU),
    "mixtral16": dict(arch=mfile.ARCH_MIXTRAL, n_experts=16, n_active_experts=4),
}


@pytest.mark.parametrize("name,packed,t", sorted(PARENT_JAXPRS),
                         ids=lambda v: str(v))
def test_mixtral_and_grok1_programs_are_the_parents(name, packed, t):
    """``moe_ffn``'s strategies (select at one row; dense, unrolled at 4
    experts and scan at 16 for seven) trace to the jaxpr they had."""
    cfg = tiny_config(**OLDER[name]).with_(quant_impl="xla")
    params = init_params(cfg, seed=3)
    if packed:
        params = quantize_matmuls(params, cfg)
    jaxpr = jax.make_jaxpr(
        lambda p, tk, cache: forward(p, cfg, tk, cache, jnp.int32(0)))(
            params, jnp.zeros((1, t), jnp.int32), init_kv_cache(cfg, 1))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        PARENT_JAXPRS[(name, packed, t)]


def test_olmoe_program_differs_from_mixtrals_by_its_two_bits():
    """The same widths under the two arch ids: OLMoE's program has the two
    norms and lacks the renormalising division, and its logits differ."""
    base = dict(n_experts=4, n_active_experts=2)
    mix = tiny_config(arch=mfile.ARCH_MIXTRAL, **base)
    olm = tiny_config(arch=mfile.ARCH_OLMOE, **base)
    params = init_params(olm, seed=3)
    shared = {k: v for k, v in params.items() if k not in ("q_norm", "k_norm")}
    toks = jnp.asarray(TOKENS)
    a, _ = forward(shared, mix, toks, init_kv_cache(mix, 1), jnp.int32(0))
    b, _ = forward(params, olm, toks, init_kv_cache(olm, 1), jnp.int32(0))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 0.1 * np.asarray(a).std()
    assert isinstance(ModelConfig.qk_norm, property)  # derived, not a field


# ---- tracing: the parts of scope `moe`, and the strategy in the ledger ----

_OP_NAME = re.compile(r"op_name=\"([^\"]+)\"")


@pytest.mark.parametrize("rows,experts,packed,path", [
    (1, 16, True, "select"), (6, 16, True, "scan"), (6, 4, True, "unrolled"),
    (6, 16, False, "dense"), (1, 16, False, "select"),
    (6, 16, True, "all-experts"), (1, 16, True, "select-chosen")])
def test_moe_parts_are_named_and_the_ledger_records_the_strategy(rows, experts,
                                                                 packed, path):
    """Every strategy of ``moe_ffn`` names its work ``moe/router``,
    ``moe/experts`` and ``moe/combine`` in the compiled text's ``op_name``
    (a reader that knows only ``SCOPES`` files all three under ``moe``; the
    q/k norm lies under ``qkv``), and records one ``moe/<strategy>`` site."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops.scopes import PARTS, SCOPES, part

    # the kernel path (interpret mode here) takes every expert, or a row's
    # chosen ones, in one launch; the XLA path keeps the loops over experts
    impl = "pallas_interpret" if path in ("all-experts", "select-chosen") else "xla"
    cfg = tiny_config(arch=mfile.ARCH_OLMOE, n_experts=experts,
                      n_active_experts=2, n_layers=1).with_(quant_impl=impl)
    params = init_params(cfg, seed=3)
    if packed:
        params = quantize_matmuls(params, cfg)
    before = obs_dispatch.dispatches()
    text = jax.jit(lambda p, tk, c: forward(p, cfg, tk, c, jnp.int32(0))).lower(
        params, jnp.zeros((1, rows), jnp.int32), init_kv_cache(cfg, 1)
    ).compile().as_text()
    after = obs_dispatch.dispatches()
    assert {k for k in after if k.startswith("moe/")
            and after[k] > before.get(k, 0)} == {"moe/" + path}
    names = _OP_NAME.findall(text)
    under_moe = [n for n in names if "/moe/" in n]
    seen = {c for n in under_moe for c in n.split("/moe/", 1)[1].split("/")
            if c in PARTS["moe"]}
    # a fusion carries its root's name: the dense strategies' small combine
    # may fuse into an op of the experts.  OLMoE has no shared expert: its
    # `moe` has the three parts it had, and no other
    olmoe_parts = {"router", "experts", "combine"}
    assert seen == olmoe_parts or (not packed and seen == {"router", "experts"}), \
        sorted(seen)
    for n in under_moe:  # the last component that is a scope is still `moe`
        assert [c for c in n.split("/") if c in SCOPES][-1] == "moe", n
    assert any("/qkv/" in n and "rsqrt" in n for n in names), "q/k norm not under qkv"
    with pytest.raises(ValueError):
        part("ffn")
