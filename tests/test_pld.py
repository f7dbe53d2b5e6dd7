"""Prompt-lookup speculative decoding (beyond reference — the reference
has no speculation).  The whole contract is EXACTNESS: every emitted token
is a true-greedy argmax, so `generate_pld` must reproduce the vanilla
greedy stream token for token no matter how many proposals get accepted
or rejected."""

import jax
import numpy as np
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine

CFG = tiny_config(seq_len=96)


def make_engine(batch=1):
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=batch)


PROMPTS = [
    [5, 9, 2],
    [7, 3, 11, 4, 6, 1, 8],
    [2, 3, 4, 2, 3, 4, 2, 3, 4, 2, 3],  # repetitive → real acceptances
]


@pytest.mark.parametrize("k,ngram", [(5, 2), (3, 3), (7, 1)])
def test_pld_exactly_matches_vanilla_greedy(k, ngram):
    for prompt in PROMPTS:
        ref = [t for t, _ in make_engine().generate_stream(
            prompt, 40, temperature=0.0, chunk=8)]
        pld = make_engine().generate_pld(prompt, 40, ngram=ngram, k=k)
        assert pld == ref, (prompt, k, ngram)


def test_pld_echoes_whole_prompt_when_steps_small():
    """generate_stream echoes the full prompt before the steps check; so
    must generate_pld."""
    prompt = [5, 9, 2, 7, 1]
    ref = [t for t, _ in make_engine().generate_stream(prompt, 3,
                                                       temperature=0.0)]
    assert make_engine().generate_pld(prompt, 3) == ref == prompt


def test_pld_eos_truncates_like_vanilla():
    ref = [t for t, _ in make_engine().generate_stream(
        [5, 9, 2], 40, temperature=0.0, chunk=8)]
    eos = ref[10]
    want = [t for t, _ in make_engine().generate_stream(
        [5, 9, 2], 40, temperature=0.0, chunk=8, eos_ids=(eos,))]
    got = make_engine().generate_pld([5, 9, 2], 40, ngram=2, k=5,
                                     eos_ids=(eos,))
    assert got == want
    assert got[-1] == eos


def test_pld_continues_usable_after_run():
    """The dead cache rows a rejected window wrote must never poison a
    later decode: pos-accounting keeps them beyond the live prefix."""
    e = make_engine()
    first = e.generate_pld([5, 9, 2], 24, ngram=2, k=5)
    # same engine, fresh conversation
    e.reset()
    again = e.generate_pld([5, 9, 2], 24, ngram=2, k=5)
    assert first == again


def test_pld_rejects_batch_and_sp():
    with pytest.raises(ValueError, match="single-stream"):
        make_engine(batch=2).generate_pld([1, 2], 8)
    if len(jax.devices()) >= 2:
        cfg = tiny_config(seq_len=64)
        sp_engine = Engine(cfg, init_params(cfg, seed=4),
                           mesh=make_mesh(tp=1, sp=2,
                                          devices=jax.devices()[:2]))
        with pytest.raises(ValueError, match="sp"):
            sp_engine.generate_pld([1, 2], 8)


# ---- on the fused Q40 kernel a row's logits follow the block's rows only above
# ---- SLICED_MAX_ROWS rows (PRs 50, 62) --------------------------------------

def _q40_engine(exact_scales=False):
    from dllama_tpu.models.params import quantize_matmuls
    from fixtures import bf16_exact_scales
    cfg = tiny_config(dim=256, hidden_dim=512, n_heads=8, n_kv_heads=4,
                      seq_len=96).with_(quant_impl="pallas_interpret")
    params = quantize_matmuls(init_params(cfg, seed=4), cfg)
    if exact_scales:
        params = bf16_exact_scales(params)
    return Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))


@pytest.mark.parametrize("k", [1, 5])
def test_pld_on_the_fused_q40_kernel_matches_vanilla_greedy(k):
    """A decode step's one row is contracted against weights no one rounded
    (``q40_body/grouped-*``), and since PR 62 so are a verify window's ``k + 1``
    rows (``q40_body/sliced-words``, no ``dot`` site): the verify's logits are
    the step's to the order of their float32 sums, so the two streams are one,
    accepted drafts included."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    prompt = PROMPTS[2]
    obs_dispatch.reset()
    ref = [t for t, _ in _q40_engine().generate_stream(
        prompt, 40, temperature=0.0, chunk=8)]
    assert sum(v for k, v in obs_dispatch.dispatches().items()
               if k.startswith("q40_body/grouped-")) > 0
    before = obs_dispatch.dispatches().get("q40_body/sliced-words", 0)
    eng = _q40_engine()
    assert eng.generate_pld(prompt, 40, ngram=2, k=k) == ref
    assert obs_dispatch.dispatches()["q40_body/sliced-words"] > before
    assert "q40_body/dot" not in obs_dispatch.dispatches()
    obs_dispatch.reset()


@pytest.mark.parametrize("rows", [2, 17], ids=["two-rows", "one-past-the-sliced-body"])
@pytest.mark.parametrize("exact_scales", [False, True],
                         ids=["f16-scales", "bf16-exact-scales"])
def test_a_rows_logits_depend_on_the_blocks_rows_by_the_weights_rounding_alone(
        exact_scales, rows):
    """The same token at the same position, decoded alone and as the first of a
    block.  Beside one other row (any block of 2 to SLICED_MAX_ROWS: every
    verify window, every served decode step) no weight is rounded either, and
    the logits agree to the order of their float32 sums (PR 62: ROADMAP D17's
    first way out).  In a block of one row more than that the dot body rounds
    each weight to bf16: the logits differ at the 1e-3 level, never by more
    than 1e-2 of the largest, with the same argmax; and the whole of the
    difference is that rounding: on weights that are exact in bf16 they agree
    to the order of their sums again."""
    from dllama_tpu.models.transformer import forward, init_kv_cache
    from dllama_tpu.ops import q40
    assert rows == 2 or rows == q40.SLICED_MAX_ROWS + 1
    eng = _q40_engine(exact_scales)
    cfg, params = eng.cfg, eng.params
    prompt = jax.numpy.asarray([PROMPTS[1]], jax.numpy.int32)
    _, cache = forward(params, cfg, prompt, init_kv_cache(cfg, 1), jax.numpy.int32(0))
    pos = jax.numpy.int32(prompt.shape[1])
    alone, _ = forward(params, cfg, jax.numpy.asarray([[9]]), cache, pos)
    block = [9] + [3 + i % 5 for i in range(rows - 1)]
    beside, _ = forward(params, cfg, jax.numpy.asarray([block]), cache, pos)
    alone, beside = np.asarray(alone)[0, 0], np.asarray(beside)[0, 0]
    diff = np.abs(alone - beside).max() / np.abs(alone).max()
    assert alone.argmax() == beside.argmax()
    if exact_scales or rows == 2:
        assert diff < 2e-5, diff
    else:
        assert 1e-4 < diff < 1e-2, diff
