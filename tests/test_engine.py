"""Engine tests: generation loop semantics, prefill modes, stats, limits."""

import numpy as np
import pytest
import jax

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine, _next_bucket
from dllama_tpu.sampling import Sampler


CFG = tiny_config(seq_len=32)


def make_engine(cfg=CFG, seed=4):
    return Engine(cfg, init_params(cfg, seed=seed),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]))


def test_step_stats_split_inference_and_transfer():
    """I ends at block_until_ready, T is the host fetch after it: the two
    always add up to G, for a prefill step and for decode chunks."""
    eng = make_engine()
    _, st = eng.prefill([5, 9, 2])
    assert abs(st.inference_ms + st.transfer_ms - st.generation_ms) < 1e-6
    chunk_stats = [s for _, s in eng.generate_stream([7], 10, chunk=4)
                   if s.generation_ms > 0]
    assert chunk_stats
    for s in chunk_stats:
        assert abs(s.inference_ms + s.transfer_ms - s.generation_ms) < 1e-6


def test_next_bucket():
    assert _next_bucket(1) == 16
    assert _next_bucket(16) == 16
    assert _next_bucket(17) == 32
    assert _next_bucket(100) == 128


def test_generate_greedy_deterministic():
    prompt = [5, 9, 2]
    a = [t for t, _ in make_engine().generate(prompt, 12, Sampler(CFG.vocab_size, 0.0, 0.9, 7))]
    b = [t for t, _ in make_engine().generate(prompt, 12, Sampler(CFG.vocab_size, 0.0, 0.9, 99))]
    assert a == b  # greedy ignores seed
    assert a[:3] == prompt
    assert len(a) == 12


def test_batched_prefill_equals_single_token_prefill():
    """True prefill must produce the same continuation as the reference's
    token-at-a-time prompt feed (dllama.cpp:53-58)."""
    prompt = [5, 9, 2, 17, 30]
    s = lambda: Sampler(CFG.vocab_size, 0.0, 0.9, 1)
    fast = [t for t, _ in make_engine().generate(prompt, 15, s())]
    slow = [t for t, _ in make_engine().generate(prompt, 15, s(), prefill_single_token=True)]
    assert fast == slow


def test_eos_stops_generation():
    e = make_engine()
    toks = [t for t, _ in e.generate([5, 9], 30, Sampler(CFG.vocab_size, 0.0, 0.9, 1))]
    eos = toks[4]  # pretend the 5th token is EOS; regenerate with it as a stop
    e2 = make_engine()
    toks2 = [t for t, _ in e2.generate([5, 9], 30, Sampler(CFG.vocab_size, 0.0, 0.9, 1), eos_ids=(eos,))]
    assert toks2[-1] == eos
    assert len(toks2) <= len(toks)


def test_steps_clamped_to_seq_len():
    e = make_engine()
    toks = [t for t, _ in e.generate([1, 2], 10_000, Sampler(CFG.vocab_size, 0.0, 0.9, 1))]
    assert len(toks) == CFG.seq_len  # clamp (app.cpp:118-120 parity)
    assert e.pos <= CFG.seq_len


def test_decode_beyond_seq_len_raises():
    e = make_engine()
    e.pos = e.seq_len
    with pytest.raises(ValueError, match="seq_len"):
        e.decode_one(1)


def test_stats_populated():
    e = make_engine()
    logits, st = e.prefill([1, 2, 3])
    assert logits.shape == (1, CFG.vocab_size)
    assert st.generation_ms > 0
    assert st.inference_ms > 0
    assert st.generation_ms + 1e-6 >= st.inference_ms


def test_reset_restarts_sequence():
    e = make_engine()
    l1, _ = e.prefill([4, 7, 1])
    e.reset()
    assert e.pos == 0
    l2, _ = e.prefill([4, 7, 1])
    np.testing.assert_allclose(l1, l2, atol=1e-5)


def test_prefill_bucket_never_overflows_cache():
    """Regression: a padded prefill bucket near the end of context must not
    exceed the cache — dynamic_update_slice clamps out-of-range starts
    backwards, silently overwriting valid KV history."""
    e = make_engine()
    e.prefill(list(range(1, 21)))  # pos=20 of seq_len=32
    l_cont, _ = e.prefill([21, 22, 23, 24, 25])  # bucket must cap at 12, not 16
    e2 = make_engine()
    l_full, _ = e2.prefill(list(range(1, 26)))
    np.testing.assert_allclose(l_cont, l_full, atol=1e-4, rtol=1e-3)


def test_multi_turn_kv_continuity():
    """Chat-style incremental prefill: a second prefill continues the same
    KV sequence (dllama.cpp:111-203 chat mode keeps pos across turns)."""
    e = make_engine()
    e.prefill([4, 7, 1])
    l_cont, _ = e.prefill([9, 3])
    e2 = make_engine()
    l_full, _ = e2.prefill([4, 7, 1, 9, 3])
    np.testing.assert_allclose(l_cont, l_full, atol=1e-4, rtol=1e-3)
