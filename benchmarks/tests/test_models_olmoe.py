"""``models/olmoe.py`` against the program, on the CPU at toy widths with the
published 64 experts and 8 a token (these tests import JAX and ``dllama_tpu``).
What ``test_models_program.py`` does for the dense and the Mixtral block, with
what this block adds: the q/k RMSNorm over the whole projection, router
probabilities used unnormalised, and a top-8 of 64 whose near-ties rounding
can flip.  Three independent forward passes on one seeded file the module
wrote: the program's engine (prefill, then decode through its cache; then the
paged slot path), the module's own reference (``last_logits`` /
``routing_margins``), and ``tests/reference_impl.py np_forward`` on weights
dequantized by ``mformat.dequantize``.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

from conftest import ROOT
from harness import mformat, models

OLMOE_TOY = dict(dim=128, hidden_dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
                 vocab_size=288, seq_len=64, rope_theta=10000.0,
                 n_experts=64, n_active_experts=8)
OLMOE_SEED, OLMOE_PROMPT, OLMOE_DECODE = 31, 16, 24

# Logits are compared in sigmas: the reference's own spread over the
# vocabulary at that position, as harness/correct.py does on the chip.
#
# OLMOE_TOL_SIGMA, float32 end to end.  The engine loads the file dequantized,
# so all three sides read the same 4-bit weights exactly and compute in
# float32; they differ by the order of float32 sums alone, and a near-tie of
# the router cannot flip (a flip needs an error of the size of the margin, and
# the smallest margin of these positions is 1e-4 of a router logit's spread
# against sums that agree to 1e-6).  Measured when this test was written,
# seeds 31-33, prefill and 8 decode steps: 2.6e-6 sigma between the two
# references, 2.2e-6 between the engine and either.  The same engine with
# bfloat16 activations reads 1.9e-2 to 5.8e-2: the NEGATIVE CONTROL.  A
# renormalised top-k, a dropped q/k norm, a per-head q/k norm or interleaved
# RoPE read tenths of a sigma to whole sigmas (the two live-bit tests below).
OLMOE_TOL_SIGMA = 1e-5
# OLMOE_TOL_Q40_SIGMA, the packed path a cell serves: the Q40 matmuls round
# both operands to bfloat16 and accumulate in float32, which reads 0.015-0.030
# sigma at these widths (the dense file's 0.045, for the same rounding).
# Rounding can also choose another expert than float32 where the router's 8th
# and 9th probabilities nearly tie, so the comparison is made on MARGIN-STEADY
# positions: those whose routing margin (models/olmoe.py routing_margins: the
# gap between the 8th and 9th router logit over the spread of the row's router
# logits) exceeds ``MARGIN_STEADY`` at every layer.  The packed engine's
# activations are off by about 2e-3 of their size here, so 0.015 is several
# times what could flip a choice.  At these widths 64 router logits spread
# over 0.37 of a unit, the mean margin is 0.076 spreads, and about a third of
# the positions have a smaller margin than the threshold at one of two layers:
# ``OLMOE_MAX_LEFT_OUT`` bounds the share left out, so the test cannot pass by
# comparing nothing.  (At toy widths a flip moves logits by less than the
# rounding does, because the flipped pair carries weights near 1/64; at
# published widths tools/check_routing.py measures both, PERF.md.)
OLMOE_TOL_Q40_SIGMA = 0.045
OLMOE_MAX_LEFT_OUT = 0.6


def _olmoe_np_forward():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.np_forward


@pytest.fixture(scope="module")
def olmoe_toy(tmp_path_factory):
    """``(module, path of a seeded .m file the module wrote)``."""
    model = models.load("olmoe")
    path = str(tmp_path_factory.mktemp("olmoe") / "olmoe-toy.m")
    mformat.synthesize(path, model, OLMOE_TOY, OLMOE_SEED, workers=2)
    return model, path


def _olmoe_cfg(path: str):
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)


def _olmoe_dequantized(model, path: str) -> dict:
    """The file's weights as float32 in the runtime layout ``np_forward``
    takes, through the benchmark's reader and plain numpy."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(OLMOE_TOY)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(OLMOE_TOY["n_layers"])
    out = {k: np.stack([tensor(f"layers.{i}.{k}").T for i in layers])
           for k in ("wq", "wk", "wv", "wo")}
    out["router"] = np.stack([tensor(f"layers.{i}.moe_router").T for i in layers])
    for key in ("up", "gate", "down"):
        out[key] = np.stack([np.stack(
            [tensor(f"layers.{i}.experts.{e}.{key}").T
             for e in range(OLMOE_TOY["n_experts"])]) for i in layers])
    for key in ("rms_att", "rms_ffn", "q_norm", "k_norm"):
        out[key] = np.stack([tensor(f"layers.{i}.{key}") for i in layers])
    out.update(embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
               wcls=tensor("wcls").T)
    return out


@pytest.fixture(scope="module")
def olmoe_references(olmoe_toy):
    """The tokens; the module's logits at every position and its routing
    margins (one pass); ``np_forward``'s logits at every position; the
    dequantized weights and the config, for the live-bit tests."""
    model, path = olmoe_toy
    rng = np.random.RandomState(OLMOE_SEED)
    toks = [int(t) for t in rng.randint(3, OLMOE_TOY["vocab_size"],
                                        OLMOE_PROMPT + OLMOE_DECODE)]
    logits, margins = model.routing_margins(path, [toks])
    weights, cfg = _olmoe_dequantized(model, path), _olmoe_cfg(path)
    full = _olmoe_np_forward()(weights, cfg, np.asarray(toks))
    return toks, logits[0], margins[0], full, weights, cfg


def _olmoe_engine_logits(path: str, toks: list[int], steps: int, dtype,
                         packed: bool) -> np.ndarray:
    """The program's logits after the prompt and after each of ``steps``
    decoded tokens (seeded, not greedy), ``(steps + 1, vocab)``."""
    import jax

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    mf = mfile.MFile(path)
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                              dtype=dtype, keep_quantized=packed)
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 seq_len=OLMOE_TOY["seq_len"])
    logits, _ = eng.prefill(toks[:OLMOE_PROMPT])
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[OLMOE_PROMPT:OLMOE_PROMPT + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return np.stack(got)


def _olmoe_sigmas(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Largest ``|got - ref|`` of each position, in that position's sigmas."""
    return np.abs(got - ref).max(-1) / ref.std(-1)


def test_olmoe_header_and_plan_are_what_the_program_parses(olmoe_toy):
    from dllama_tpu.io import mfile

    model, path = olmoe_toy
    mf = mfile.MFile(path)
    for key, want in dict(OLMOE_TOY, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == want, key
    assert mf.spec.arch == mfile.ARCH_OLMOE == model.ARCH_OLMOE
    assert mf.spec.header_size == len(model.header(OLMOE_TOY))
    ours = model.plan(OLMOE_TOY)
    theirs = mfile.tensor_plan(mf.spec)
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    names = [t[0] for t in ours]
    at = names.index("layers.0.wo")
    assert names[at + 1:at + 4] == ["layers.0.q_norm", "layers.0.k_norm",
                                    "layers.0.moe_router"]
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def test_olmoe_last_logits_is_the_every_position_pass(olmoe_toy, olmoe_references):
    model, path = olmoe_toy
    toks, logits, _, _, _, _ = olmoe_references
    for n in (OLMOE_PROMPT, OLMOE_PROMPT + OLMOE_DECODE):
        last = model.last_logits(path, [toks[:n]])[0]
        assert _olmoe_sigmas(last[None], logits[n - 1][None])[0] <= OLMOE_TOL_SIGMA


def test_olmoe_engine_and_two_references_agree_in_float32(olmoe_toy, olmoe_references):
    import jax.numpy as jnp

    toks, logits, _, full, _, _ = olmoe_references
    between = _olmoe_sigmas(full, logits).max()
    assert between <= OLMOE_TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _olmoe_engine_logits(olmoe_toy[1], toks, OLMOE_DECODE, jnp.float32,
                                  packed=False)
    at = slice(OLMOE_PROMPT - 1, OLMOE_PROMPT + OLMOE_DECODE)
    worst = max(_olmoe_sigmas(engine, logits[at]).max(),
                _olmoe_sigmas(engine, full[at]).max())
    assert worst <= OLMOE_TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_olmoe_tolerance_fails_bfloat16_activations(olmoe_toy, olmoe_references):
    """NEGATIVE CONTROL for ``OLMOE_TOL_SIGMA``: the program with bfloat16
    activations, the next precision below the float32 that run states."""
    import jax.numpy as jnp

    toks, logits, _, _, _, _ = olmoe_references
    engine = _olmoe_engine_logits(olmoe_toy[1], toks, 0, jnp.bfloat16, packed=False)
    assert _olmoe_sigmas(engine, logits[OLMOE_PROMPT - 1][None])[0] > 100 * OLMOE_TOL_SIGMA


@pytest.mark.parametrize("bit", ("norm_topk_prob", "qk_norm"))
def test_olmoe_reference_with_a_flipped_bit_disagrees(olmoe_references, bit):
    """The two flags ``ARCH_OLMOE`` sets are live: ``np_forward`` with either
    one flipped (a renormalised top-k; no q/k norm) is no longer the block."""
    from dllama_tpu.models.config import ModelConfig

    toks, logits, _, _, weights, cfg = olmoe_references
    flipped_cls = type("Flipped", (ModelConfig,), {bit: not getattr(cfg, bit)})
    flipped = flipped_cls(**{f.name: getattr(cfg, f.name)
                             for f in dataclasses.fields(cfg)})
    assert getattr(flipped, bit) != getattr(cfg, bit)
    other = _olmoe_np_forward()(weights, flipped, np.asarray(toks))
    # from the second position on: the first attends to itself alone, and
    # its output is its v whatever q and k are
    assert _olmoe_sigmas(other, logits)[1:].min() > 1000 * OLMOE_TOL_SIGMA


def test_olmoe_packed_engine_agrees_on_margin_steady_positions(olmoe_toy, olmoe_references):
    import jax.numpy as jnp

    model, path = olmoe_toy
    toks, logits, margins, _, _, _ = olmoe_references
    at = slice(OLMOE_PROMPT - 1, OLMOE_PROMPT + OLMOE_DECODE)
    steady = margins[at].min(-1) > model.MARGIN_STEADY
    left_out = 1.0 - steady.mean()
    assert left_out <= OLMOE_MAX_LEFT_OUT, (
        f"{left_out:.0%} of {steady.size} positions have a routing margin "
        f"under {model.MARGIN_STEADY}")
    engine = _olmoe_engine_logits(path, toks, OLMOE_DECODE, jnp.float32, packed=True)
    worst = _olmoe_sigmas(engine, logits[at])[steady].max()
    assert worst <= OLMOE_TOL_Q40_SIGMA, (
        f"{worst:.4f} sigma over {int(steady.sum())} margin-steady positions")


def test_olmoe_paged_slots_match_the_contiguous_engine(olmoe_toy):
    """Six greedy requests through the paged slot path (``forward_slots``: six
    rows a step, so ``moe_ffn`` takes its scan over the 64 packed experts)
    against the same prompts alone on the contiguous engine (the scan for the
    prompt, the select path for each decoded token): equal tokens."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    mf = mfile.MFile(olmoe_toy[1])
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=jnp.float32),
                              dtype=jnp.float32, keep_quantized=True)
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    rng = np.random.RandomState(OLMOE_SEED + 1)
    prompts = [[int(t) for t in rng.randint(3, OLMOE_TOY["vocab_size"], n)]
               for n in (5, 9, 6, 12, 7, 10)]
    new = 10
    solo = Engine(cfg, params, mesh=mesh, seq_len=OLMOE_TOY["seq_len"])
    want = []
    for p in prompts:
        solo.reset()
        want.append([t for t, _ in solo.generate_stream(
            p, len(p) + new, temperature=0.0, chunk=4)][len(p):])
    before = obs_dispatch.dispatches()
    page = 4
    paged = Engine(cfg, params, mesh=mesh, seq_len=OLMOE_TOY["seq_len"],
                   batch=len(prompts), kv_page_size=page,
                   kv_pages=len(prompts) * (OLMOE_TOY["seq_len"] // page) + 1)
    sched = SlotScheduler(paged, prefill_chunk=4, decode_burst=4)
    try:
        tickets = [sched.submit(p, new, temperature=0.0) for p in prompts]
        got = [list(t.tokens()) for t in tickets]
    finally:
        sched.close()
    assert got == want
    after = obs_dispatch.dispatches()
    assert after.get("moe/scan", 0) > before.get("moe/scan", 0)
