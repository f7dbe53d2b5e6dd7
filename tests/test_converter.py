"""Converter tests.

The strongest check in the suite: a real HuggingFace ``LlamaForCausalLM``
is saved to safetensors, converted to `.m` by converter/convert_hf.py, and
the resulting model's logits are compared against the torch forward pass —
cross-implementation parity covering the q/k RoPE permutation, tensor
order, and every transform in between."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "converter"))

from dllama_tpu import quants
from dllama_tpu.io import mfile, tfile
from dllama_tpu.models.params import load_params


@pytest.fixture(scope="module")
def hf_model_dir(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    config = LlamaConfig(
        hidden_size=64, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=128,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False,
        # the .m format carries no norm-eps field: the reference runtime
        # hardcodes 1e-5 (funcs.cpp:120), so converted HF models always run
        # with 1e-5 regardless of config.json — align the fixture
        rms_norm_eps=1e-5)
    model = LlamaForCausalLM(config).eval()
    d = tmp_path_factory.mktemp("hf_llama")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_convert_hf_logits_match_torch(hf_model_dir, tmp_path):
    import torch
    import jax.numpy as jnp
    folder, torch_model = hf_model_dir
    out = str(tmp_path / "conv.m")

    import convert_hf
    convert_hf.convert(folder, quants.F32, out)

    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_LLAMA
    assert mf.spec.n_kv_heads == 2
    cfg, params = load_params(mf)
    cfg = cfg.with_(dtype=jnp.float32)

    tokens = [[3, 17, 42, 99, 7]]
    with torch.no_grad():
        want = torch_model(torch.tensor(tokens)).logits.numpy()[0]

    from dllama_tpu.models.transformer import forward, init_kv_cache
    logits, _ = forward(params, cfg, jnp.asarray(tokens),
                        init_kv_cache(cfg, 1), jnp.int32(0))
    got = np.asarray(logits)[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)


def test_convert_hf_q40_close_to_f32(hf_model_dir, tmp_path):
    import convert_hf
    folder, _ = hf_model_dir
    f32_path = str(tmp_path / "f32.m")
    q40_path = str(tmp_path / "q40.m")
    convert_hf.convert(folder, quants.F32, f32_path)
    convert_hf.convert(folder, quants.Q40, q40_path)
    a = mfile.MFile(f32_path).tensor("layers.0.wq")
    b = mfile.MFile(q40_path).tensor("layers.0.wq")
    assert np.abs(a - b).max() < np.abs(a).max() / 7  # 4-bit step bound


def test_convert_hf_q80_loads_packed(hf_model_dir, tmp_path):
    """HF → Q80 `.m` → the packed Q8 loader path (reference ftype-dispatch
    parity end-to-end through the converter)."""
    import convert_hf
    import jax.numpy as jnp

    from dllama_tpu.ops import q8

    folder, _ = hf_model_dir
    q80_path = str(tmp_path / "q80.m")
    convert_hf.convert(folder, quants.Q80, q80_path)
    mf = mfile.MFile(q80_path)
    assert mf.spec.weights_ftype == quants.Q80
    # 8-bit codec: much tighter than the Q40 bound
    a = mf.tensor("layers.0.wq")
    cfg, params = load_params(mf, keep_quantized=True)
    assert isinstance(params["wqkv"], q8.Q8Tensor)
    # layer-stacked fused (L, n, q|k|v): layer 0's q slice must equal the
    # file tensor's dequant exactly (same codec, pure byte transpose)
    w = np.asarray(q8.dequantize(params["wqkv"], jnp.float32))[0]
    np.testing.assert_allclose(w[:, :cfg.dim], a.reshape(cfg.dim, cfg.dim).T,
                               rtol=0, atol=1e-6)


def test_convert_llama_meta_checkpoint(tmp_path):
    import torch
    import convert_llama
    dim, n_layers, n_heads, vocab = 64, 2, 4, 96
    # Meta sizing rule: hidden = multiple_of * ceil((2*4*dim/3)/multiple_of)
    folder = tmp_path / "meta"
    folder.mkdir()
    (folder / "params.json").write_text(json.dumps({
        "dim": dim, "n_layers": n_layers, "n_heads": n_heads,
        "multiple_of": 32, "norm_eps": 1e-5, "vocab_size": vocab}))
    rng = np.random.RandomState(0)
    hidden_dim = 32 * ((int(2 * 4 * dim / 3) + 31) // 32)

    def t(*shape):
        return torch.tensor(rng.randn(*shape).astype(np.float32) * 0.05)

    # two shards, split like Meta does (attention/ffn on axis 0/1)
    sd0, sd1 = {}, {}
    def split(key, full, axis):
        halves = np.split(full.numpy(), 2, axis=axis)
        sd0[key] = torch.tensor(halves[0])
        sd1[key] = torch.tensor(halves[1])

    emb = t(vocab, dim); split("tok_embeddings.weight", emb, 1)
    for l in range(n_layers):
        for k, ax in [("attention.wq.weight", 0), ("attention.wk.weight", 0),
                      ("attention.wv.weight", 0), ("attention.wo.weight", 1)]:
            split(f"layers.{l}.{k}", t(dim, dim), ax)
        split(f"layers.{l}.feed_forward.w1.weight", t(hidden_dim, dim), 0)
        split(f"layers.{l}.feed_forward.w2.weight", t(dim, hidden_dim), 1)
        split(f"layers.{l}.feed_forward.w3.weight", t(hidden_dim, dim), 0)
        sd0[f"layers.{l}.attention_norm.weight"] = torch.ones(dim)
        sd1[f"layers.{l}.attention_norm.weight"] = torch.ones(dim)
        sd0[f"layers.{l}.ffn_norm.weight"] = torch.ones(dim)
        sd1[f"layers.{l}.ffn_norm.weight"] = torch.ones(dim)
    sd0["norm.weight"] = torch.ones(dim); sd1["norm.weight"] = torch.ones(dim)
    split("output.weight", t(vocab, dim), 0)
    torch.save(sd0, folder / "consolidated.00.pth")
    torch.save(sd1, folder / "consolidated.01.pth")

    out = str(tmp_path / "meta.m")
    convert_llama.convert(str(folder), quants.F32, out)
    mf = mfile.MFile(out)
    assert mf.spec.hidden_dim == hidden_dim
    # wq reconstructed = concat of both shards on axis 0
    wq = mf.tensor("layers.0.wq")
    assert wq.shape == (dim, dim)
    np.testing.assert_allclose(wq[:dim // 2], sd0["layers.0.attention.wq.weight"].numpy())


def test_convert_tokenizer_hf_fast(tmp_path):
    import convert_tokenizer_hf
    d = tmp_path / "tok"
    d.mkdir()
    vocab = {"a": 0, "b": 1, "ab": 2}
    (d / "tokenizer.json").write_text(json.dumps({
        "model": {"type": "BPE", "vocab": vocab, "merges": ["a b"]},
        "added_tokens": [
            {"id": 3, "content": "<s>"}, {"id": 4, "content": "</s>"}],
    }))
    (d / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<s>", "eos_token": "</s>",
        "chat_template": "{% for m in messages %}<|im_start|>...{% endfor %}"}))
    out = convert_tokenizer_hf.convert(str(d), "test", "<|stop|>",
                                       out_path=str(tmp_path / "t.t"))
    r = tfile.read_tfile(out)
    assert r.vocab == [b"a", b"b", b"ab", b"<s>", b"</s>"]
    assert r.bos_id == 3 and r.eos_id == 4 and r.chat_eos_id == 4
    assert "<|im_start|>" in r.chat_template
    assert r.chat_stop == "<|stop|>"


def test_convert_tokenizer_llama3(tmp_path):
    import base64
    import convert_tokenizer_llama3 as c3
    lines = [f"{base64.b64encode(bytes([65 + i])).decode()} {i}" for i in range(10)]
    src = tmp_path / "tokenizer.model"
    src.write_text("\n".join(lines) + "\n")
    out = c3.convert(str(src), out_path=str(tmp_path / "l3.t"))
    r = tfile.read_tfile(out)
    assert r.vocab[0] == b"A"
    assert len(r.vocab) == 10 + 256
    assert r.vocab[10 + 9] == b"<|eot_id|>"
    assert r.bos_id == 128000 and r.chat_eos_id == 128009
    assert "<|start_header_id|>" in r.chat_template


def test_launch_lists_reference_zoo():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import launch
    assert set(launch.MODELS) == {"tinyllama_1_1b_3t_q40", "llama3_8b_q40",
                                  "llama3_8b_instruct_q40"}


# ---- deepseek_v2 -------------------------------------------------------------

DS2_HF = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    vocab_size=128, max_position_embeddings=64, q_lora_rank=64, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, head_dim=8,
    n_routed_experts=32, num_experts_per_tok=6, n_shared_experts=2, n_group=8,
    topk_group=3, topk_method="group_limited_greedy", first_k_dense_replace=1,
    routed_scaling_factor=16.0, norm_topk_prob=False, rope_theta=10000.0,
    tie_word_embeddings=False, rms_norm_eps=1e-6)


@pytest.fixture(scope="module")
def hf_deepseek2_dir(tmp_path_factory):
    import torch
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM
    torch.manual_seed(0)
    model = DeepseekV2ForCausalLM(DeepseekV2Config(**DS2_HF)).eval()
    with torch.no_grad():  # the gate's weight is allocated, not initialised
        for name, p in model.named_parameters():
            if name.endswith("mlp.gate.weight"):
                p.normal_(0, 0.5)
    d = tmp_path_factory.mktemp("hf_deepseek2")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_convert_deepseek_v2_logits_match_huggingfaces_own(hf_deepseek2_dir, tmp_path):
    """``model_type: deepseek_v2`` through the converter, the loader and
    ``forward`` against transformers' ``DeepseekV2ForCausalLM``: the names,
    ``kv_b_proj`` kept whole (a head's rows k_nope then v), RoPE on adjacent
    pairs with no permutation, the grouped choice, the x16 and the shared
    experts.  (transformers' port applies no ``mscale`` to the softmax scale,
    so the fixture has no ``rope_scaling``; YaRN is held to the published
    formula in ``tests/test_deepseek_v2.py``.)  float32 on both sides: 2e-5."""
    import jax
    import jax.numpy as jnp
    import torch

    import convert_hf
    from dllama_tpu.models.transformer import forward, init_kv_cache

    folder, torch_model = hf_deepseek2_dir
    out = str(tmp_path / "ds2.m")
    convert_hf.convert(folder, quants.F32, out)
    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_DEEPSEEK2
    assert (mf.spec.n_groups, mf.spec.topk_groups, mf.spec.n_dense_layers,
            mf.spec.n_shared_experts, mf.spec.moe_hidden_dim) == (8, 3, 1, 2, 32)
    assert mf.spec.rope_factor == 1.0 and mf.spec.norm_eps == np.float32(1e-6)
    assert mf.info("layers.1.wkv_b").shape == (4 * 32, 32)
    cfg, params = load_params(mf)
    cfg = cfg.with_(dtype=jnp.float32)
    tokens = [[3, 17, 42, 99, 7, 64, 5, 23, 81, 11]]
    with torch.no_grad():
        want = torch_model(torch.tensor(tokens)).logits.numpy()[0]
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(tokens),
                            init_kv_cache(cfg, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("key,value,says", [
    ("scoring_func", "sigmoid", "scoring_func is 'sigmoid'"),
    ("topk_method", "noaux_tc", "topk_method is 'noaux_tc'"),
    ("norm_topk_prob", True, "norm_topk_prob is true"),
    ("moe_layer_freq", 2, "moe_layer_freq is 2"),
    ("attention_bias", True, "attention_bias is true"),
    ("rope_scaling", {"type": "linear", "factor": 4.0}, "rope_scaling type is 'linear'"),
    ("q_lora_rank", None, "q_lora_rank is null"),
])
def test_convert_hf_refuses_deepseek_v2_variants_by_name(tmp_path, key, value, says):
    import convert_hf

    config = dict(DS2_HF, model_type="deepseek_v2", hidden_act="silu",
                  scoring_func="softmax", moe_layer_freq=1, attention_bias=False)
    config[key] = value
    (tmp_path / "config.json").write_text(json.dumps(config))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)


def test_convert_hf_reads_yarn_and_greedy_into_the_header(tmp_path):
    import convert_hf

    config = dict(DS2_HF, model_type="deepseek_v2", topk_method="greedy",
                  rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                                "beta_slow": 1, "mscale": 0.707,
                                "mscale_all_dim": 0.707,
                                "original_max_position_embeddings": 4096})
    (tmp_path / "config.json").write_text(json.dumps(config))
    spec = convert_hf.load_spec(str(tmp_path), quants.Q40)
    assert (spec.n_groups, spec.topk_groups) == (1, 1)  # greedy: one group
    assert (spec.rope_factor, spec.rope_orig_seq_len, spec.rope_mscale,
            spec.rope_mscale_all_dim) == (40.0, 4096, 0.707, 0.707)
    mfile.validate_spec(spec, "x.m")


def test_convert_hf_refuses_a_checkpoint_with_a_correction_bias(tmp_path):
    """V3's router adds ``e_score_correction_bias`` to the scores; the runtime
    has none, and a config.json alone does not say the checkpoint has one."""
    from safetensors.numpy import save_file

    import convert_hf

    config = dict(DS2_HF, model_type="deepseek_v2")
    (tmp_path / "config.json").write_text(json.dumps(config))
    save_file({"model.layers.1.mlp.gate.e_score_correction_bias":
               np.zeros(32, np.float32)}, str(tmp_path / "model.safetensors"))
    with pytest.raises(SystemExit, match="e_score_correction_bias"):
        convert_hf.convert(str(tmp_path), quants.F32, str(tmp_path / "x.m"))


# ---- smallthinker --------------------------------------------------------------

ST_HF = dict(
    model_type="smallthinker", hidden_size=96, moe_ffn_hidden_size=32,
    num_hidden_layers=8, num_attention_heads=28, num_key_value_heads=4,
    head_dim=8, vocab_size=128, max_position_embeddings=96,
    moe_num_primary_experts=64, moe_num_active_primary_experts=6,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1500000, rope_scaling=None,
    rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=16, tie_word_embeddings=False)


def test_convert_smallthinker_names_and_logits(tmp_path):
    """A toy checkpoint under SmallThinker's published tensor names
    (``block_sparse_moe.primary_router``, ``experts.N.up|gate|down``) through
    the converter, the loader and ``forward`` against the plain numpy
    reference on the same weights: rows are not permuted (the runtime rotates
    halves as the checkpoint does), the header carries the head size, the
    window and the period, ReLU is activation 2."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    import convert_hf
    import reference_impl as ref
    from dllama_tpu.models.config import tiny_smallthinker
    from dllama_tpu.models.params import init_params
    from dllama_tpu.models.transformer import forward, init_kv_cache

    cfg = tiny_smallthinker()
    p = {k: np.asarray(v, np.float32)
         for k, v in init_params(cfg, seed=9, scale=0.08).items()}
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.norm.weight": p["rms_final"], "lm_head.weight": p["wcls"].T}
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                             ("wo", "o_proj")):
            hf[f"{base}self_attn.{theirs}.weight"] = p[ours][i].T
        hf[base + "input_layernorm.weight"] = p["rms_att"][i]
        hf[base + "post_attention_layernorm.weight"] = p["rms_ffn"][i]
        hf[base + "block_sparse_moe.primary_router.weight"] = p["router"][i].T
        for e in range(cfg.n_experts):
            for leaf in ("up", "gate", "down"):
                hf[f"{base}block_sparse_moe.experts.{e}.{leaf}.weight"] = p[leaf][i, e].T
    (tmp_path / "config.json").write_text(json.dumps(ST_HF))
    save_file({k: np.ascontiguousarray(v) for k, v in hf.items()},
              str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "st.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_SMALLTHINKER
    assert (mf.spec.head_dim, mf.spec.window, mf.spec.window_period,
            mf.spec.hidden_act, mf.spec.hidden_dim) == (8, 16, 4, mfile.ACT_RELU, 32)
    got_cfg, params = load_params(mf)
    got_cfg = got_cfg.with_(dtype=jnp.float32)
    toks = np.random.RandomState(4).randint(3, 128, (40,)).astype(np.int32)
    want = ref.np_forward_smallthinker(p, cfg, toks)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, got_cfg, jnp.asarray(toks)[None],
                            init_kv_cache(got_cfg, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("key,value,says", [
    ("moe_primary_router_apply_softmax", False, "a sigmoid router"),
    ("norm_topk_prob", False, "norm_topk_prob is false"),
    ("moe_num_secondary_experts", 8, "secondary experts are configured"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling is"),
    ("rope_layout", [1] * 8, "rope_layout differs from sliding_window_layout"),
    ("sliding_window_layout", [0, 1, 1, 1, 1, 0, 1, 1], "is not whole periods"),
    ("tie_word_embeddings", True, "tie_word_embeddings is true"),
])
def test_convert_hf_refuses_smallthinker_variants_by_name(tmp_path, key, value, says):
    import convert_hf

    config = dict(ST_HF)
    config[key] = value
    if key == "sliding_window_layout":
        config["rope_layout"] = value
    (tmp_path / "config.json").write_text(json.dumps(config))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)


# ---- granitemoehybrid (Granite-4.0-H) ------------------------------------------

GRANITE_HF = dict(
    model_type="granitemoehybrid", hidden_size=64, intermediate_size=32,
    shared_intermediate_size=64, num_hidden_layers=10, num_attention_heads=8,
    num_key_value_heads=2, num_local_experts=12, num_experts_per_tok=3,
    vocab_size=128, max_position_embeddings=512, hidden_act="silu",
    normalization_function="rmsnorm", rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None, position_embedding_type="nope", attention_bias=False,
    mamba_proj_bias=False, mamba_conv_bias=True, mamba_n_heads=8, mamba_d_head=8,
    mamba_d_state=12, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=1,
    mamba_chunk_size=256, tie_word_embeddings=True,
    layer_types=(["mamba"] * 2 + ["attention"] + ["mamba"] * 2) * 2,
    embedding_multiplier=3.0, logits_scaling=4.0, residual_multiplier=0.5,
    attention_multiplier=0.3)


def test_convert_granitemoehybrid_names_stacked_experts_and_logits(tmp_path):
    """A toy checkpoint under the names the converter ASSUMES (nobody fetched
    the published files): the mixer's as Falcon-H1's, the experts as ONE stacked
    ``input_linear`` (E, 2 F, D) of ``gate | up`` rows and ``output_linear`` (E,
    D, F), the shared MLP's ``input_linear`` likewise, a tied head (no
    ``lm_head``), through the converter, the loader and ``forward`` against the
    plain numpy reference on the same weights.  The header carries the period,
    the four scalars (``attention_multiplier`` as the key's multiplier,
    ``residual_multiplier`` on each of the three branch outputs) and the shared
    MLP's width in experts."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    import convert_hf
    import reference_impl as ref
    from dllama_tpu.models.config import tiny_granite_hybrid
    from dllama_tpu.models.params import init_params
    from dllama_tpu.models.transformer import forward, init_kv_cache

    cfg = tiny_granite_hybrid()
    p = {k: np.asarray(v, np.float32)
         for k, v in init_params(cfg, seed=9, scale=0.08).items()}
    p["wcls"] = p["embedding"].T.copy()                      # the head is tied
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.norm.weight": p["rms_final"]}
    n_att = n_mix = 0
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        if i % cfg.window_period == cfg.window_full_at:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                hf[f"{base}self_attn.{theirs}.weight"] = p[ours][n_att].T
            n_att += 1
        else:
            hf[base + "mamba.in_proj.weight"] = np.concatenate(
                [p["ssm_in"][n_mix].T, p["ssm_dt"][n_mix].T])      # z | xBC | dt
            hf[base + "mamba.conv1d.weight"] = p["ssm_conv_w"][n_mix][:, None, :]
            hf[base + "mamba.out_proj.weight"] = p["ssm_out"][n_mix].T
            for ours, theirs in (("ssm_conv_b", "conv1d.bias"), ("ssm_a_log", "A_log"),
                                 ("ssm_dt_bias", "dt_bias"), ("ssm_d", "D"),
                                 ("ssm_norm", "norm.weight")):
                hf[f"{base}mamba.{theirs}"] = p[ours][n_mix]
            n_mix += 1
        hf[base + "input_layernorm.weight"] = p["rms_att"][i]
        hf[base + "post_attention_layernorm.weight"] = p["rms_ffn"][i]
        moe = base + "block_sparse_moe."
        hf[moe + "router.layer.weight"] = p["router"][i].T
        hf[moe + "input_linear.weight"] = np.concatenate(
            [p["gate"][i].transpose(0, 2, 1), p["up"][i].transpose(0, 2, 1)], axis=1)
        hf[moe + "output_linear.weight"] = p["down"][i].transpose(0, 2, 1)
        hf[base + "shared_mlp.input_linear.weight"] = np.concatenate(
            [p["shared_w1"][i].T, p["shared_w3"][i].T])
        hf[base + "shared_mlp.output_linear.weight"] = p["shared_w2"][i].T
    (tmp_path / "config.json").write_text(json.dumps(GRANITE_HF))
    save_file({k: np.ascontiguousarray(v, np.float32) for k, v in hf.items()},
              str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "granite.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_GRANITE_HYBRID
    assert (mf.spec.window_period, mf.spec.window_full_at, mf.spec.hidden_dim,
            mf.spec.moe_hidden_dim, mf.spec.n_shared_experts) == (5, 2, 64, 32, 2)
    assert (mf.spec.mup_embedding, mf.spec.mup_head, mf.spec.mup_attn_out,
            mf.spec.mup_ssm_out, mf.spec.mup_down) == (3.0, 0.25, 0.5, 0.5, 0.5)
    assert abs(mf.spec.mup_key - 0.3 * 8 ** 0.5) < 1e-6
    got_cfg, params = load_params(mf)
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32), p[k], err_msg=k)
    got_cfg = got_cfg.with_(dtype=jnp.float32)
    toks = np.random.RandomState(4).randint(3, 128, (32,)).astype(np.int32)
    want = ref.np_forward_granite_hybrid(p, cfg, toks)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, got_cfg, jnp.asarray(toks)[None],
                            init_kv_cache(got_cfg, 1, 64), jnp.int32(0))
    # the header carries the multipliers as float32
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("key,value,says", [
    ("position_embedding_type", "rope", "position_embedding_type is 'rope'"),
    ("mamba_n_groups", 2, "a norm a group"),
    ("attention_bias", True, "attention_bias is True"),
    ("mamba_proj_bias", True, "mamba_proj_bias is True"),
    ("shared_intermediate_size", 48, "not a whole number of experts"),
    ("layer_types", ["mamba"] * 9 + ["attention"], "x"),
])
def test_convert_hf_refuses_granite_variants_by_name(tmp_path, key, value, says):
    import convert_hf

    config = dict(GRANITE_HF, **{key: value})
    (tmp_path / "config.json").write_text(json.dumps(config))
    if says == "x":  # ten layers, the attention layer last: one whole period
        spec = convert_hf.load_spec(str(tmp_path), quants.F32)
        assert (spec.window_period, spec.window_full_at) == (10, 9)
        return
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)
