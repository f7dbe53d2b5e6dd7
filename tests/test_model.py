"""Model forward-pass tests: jax vs independent numpy oracle, prefill ≡
decode consistency, and all three arch families.

This is the port of the reference's integration strategy
(llama2-tasks-test.cpp / grok1-tasks-test.cpp): deterministic fixture
weights → run the real execution path → compare against a golden oracle."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dllama_tpu.io import mfile
from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params, param_shapes
from dllama_tpu.models.transformer import forward, forward_last, init_kv_cache
from reference_impl import np_forward


def np_params(params):
    return {k: np.asarray(v) for k, v in params.items()}


def run_jax_full(cfg, params, tokens):
    cache = init_kv_cache(cfg, batch=1)
    logits, _ = forward(params, cfg, jnp.asarray([tokens]), cache, jnp.int32(0))
    return np.asarray(logits)[0]


CFGS = {
    "llama": tiny_config(),
    "llama_gqa8": tiny_config(n_heads=8, n_kv_heads=8, dim=64),
    "mixtral": tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2),
    "grok1": tiny_config(arch=mfile.ARCH_GROK1, n_experts=4, n_active_experts=2,
                         hidden_act=mfile.ACT_GELU),
    "olmoe": tiny_config(arch=mfile.ARCH_OLMOE, n_experts=8, n_active_experts=3),
}


@pytest.mark.parametrize("name", list(CFGS))
def test_forward_matches_numpy_oracle(name):
    cfg = CFGS[name]
    params = init_params(cfg, seed=3)
    tokens = list(np.random.RandomState(0).randint(0, cfg.vocab_size, 7))
    got = run_jax_full(cfg, params, tokens)
    want = np_forward(np_params(params), cfg, tokens)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("name", ["llama", "mixtral", "grok1", "olmoe"])
def test_decode_matches_prefill(name):
    """Token-at-a-time decode through the KV cache must reproduce the
    full-sequence forward — the autoregression-correctness property."""
    cfg = CFGS[name]
    params = init_params(cfg, seed=11)
    tokens = list(np.random.RandomState(1).randint(0, cfg.vocab_size, 6))

    full = run_jax_full(cfg, params, tokens)

    cache = init_kv_cache(cfg, batch=1)
    step_logits = []
    for i, t in enumerate(tokens):
        logits, cache = forward(params, cfg, jnp.asarray([[t]]), cache, jnp.int32(i))
        step_logits.append(np.asarray(logits)[0, 0])
    np.testing.assert_allclose(np.stack(step_logits), full, atol=2e-4, rtol=1e-3)


def test_prefill_then_decode_continues():
    """Prefill T tokens then decode more — mixed-mode consistency."""
    cfg = CFGS["llama"]
    params = init_params(cfg, seed=5)
    tokens = list(np.random.RandomState(2).randint(0, cfg.vocab_size, 8))

    full = run_jax_full(cfg, params, tokens)

    cache = init_kv_cache(cfg, batch=1)
    _, cache = forward(params, cfg, jnp.asarray([tokens[:5]]), cache, jnp.int32(0))
    outs = []
    for i in range(5, 8):
        logits, cache = forward(params, cfg, jnp.asarray([[tokens[i]]]), cache, jnp.int32(i))
        outs.append(np.asarray(logits)[0, 0])
    np.testing.assert_allclose(np.stack(outs), full[5:8], atol=2e-4, rtol=1e-3)


def test_forward_last_matches_forward():
    cfg = CFGS["llama"]
    params = init_params(cfg, seed=7)
    tokens = np.random.RandomState(3).randint(0, cfg.vocab_size, (1, 6))
    cache = init_kv_cache(cfg, batch=1)
    full, _ = forward(params, cfg, jnp.asarray(tokens), cache, jnp.int32(0))
    cache2 = init_kv_cache(cfg, batch=1)
    last, _ = forward_last(params, cfg, jnp.asarray(tokens), cache2, jnp.int32(0), jnp.int32(3))
    np.testing.assert_allclose(np.asarray(last)[0], np.asarray(full)[0, 3], atol=1e-5)


def test_padded_prefill_ignores_padding():
    """Right-padding must not affect logits at the real last index (the
    engine pads prompts up to a bucket)."""
    cfg = CFGS["llama"]
    params = init_params(cfg, seed=9)
    tokens = [5, 17, 40]
    cache = init_kv_cache(cfg, batch=1)
    exact, _ = forward_last(params, cfg, jnp.asarray([tokens]), cache, jnp.int32(0), jnp.int32(2))
    padded = tokens + [0] * 5
    cache2 = init_kv_cache(cfg, batch=1)
    got, _ = forward_last(params, cfg, jnp.asarray([padded]), cache2, jnp.int32(0), jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact), atol=1e-5)


def test_grok_scales_applied():
    """Grok-1 embedding ×78.38… and logit ×0.577… (grok1-tasks.cpp:13,:272)."""
    cfg = CFGS["grok1"]
    assert cfg.embedding_scale == pytest.approx(78.38367176906169)
    assert cfg.logit_scale == pytest.approx(0.5773502691896257)
    assert not cfg.rope_interleaved  # falcon/neox rope (transformer.cpp:227-231)
    assert CFGS["llama"].rope_interleaved


def test_param_shapes_cover_all_archs():
    for name, cfg in CFGS.items():
        shapes = param_shapes(cfg)
        p = init_params(cfg, seed=0)
        assert set(p) == set(shapes)
        for k, v in p.items():
            assert tuple(v.shape) == shapes[k], k
