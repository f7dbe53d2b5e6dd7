"""The architecture modules against the program, on the CPU at toy widths
(these tests import JAX and ``dllama_tpu``; the rest of this directory does
not).  Two things: a module's ``header`` + ``plan`` are the file the program's
``io/mfile.py`` parses and plans; and three independent forward passes agree
on a file the module wrote: the program's engine (prefill, then decode through
its cache), the module's own ``last_logits``, and ``tests/reference_impl.py
np_forward`` on weights dequantized by ``mformat.dequantize``.
"""

import importlib.util
import os

import numpy as np
import pytest

from conftest import ROOT
from harness import mformat, models

TOY = dict(dim=128, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
           vocab_size=288, seq_len=64, rope_theta=10000.0)
# the experts cover both branches of MOE_PREFILL_UNROLL_MAX (8): a static
# unroll at 8, a scan at 16; one decode row takes the <= 4-row branch
CASES = {"dense": ("dense", {}),
         "moe-8-top-2": ("moe", dict(n_experts=8, n_active_experts=2)),
         "moe-16-top-4": ("moe", dict(n_experts=16, n_active_experts=4))}
SEED, PROMPT_LEN, DECODE_STEPS = 30, 24, 8

# Logits are compared in units of the reference's own spread over the
# vocabulary (sigma), as harness/correct.py does on the chip.
#
# TOL_SIGMA, float32 end to end.  The engine loads the file dequantized
# (``keep_quantized=False``), so all three sides read the same 4-bit weights
# exactly and compute in float32: they differ by the order of float32 sums
# alone.  Largest difference over the prefill and 8 decode steps, three cases,
# seeds 30-32 (measured when this test was written): 1.8e-6 sigma between the
# two references, 1.9e-6 between the engine and either.  The same engine with
# bfloat16 activations reads 1.5e-2 to 2.5e-2 sigma on the prefill alone: the
# NEGATIVE CONTROL below.  1e-4 admits the first with room for another BLAS
# and fails the second by two orders of magnitude; a wrong RoPE pairing, a
# router that is not renormalised or a swapped gate and up read tenths of a
# sigma.
TOL_SIGMA = 1e-4
# TOL_Q40_SIGMA, the packed path a cell serves.  The Q40 matmuls round both
# operands to bfloat16 and accumulate in float32 whatever the engine's dtype
# (ops/q40.py), so this run is there to take the packed branches of
# ``moe_ffn`` (static unroll at 8 experts, scan at 16, the <= 4-row select)
# and the packed dense block, not to tell precisions apart: at these widths it
# reads 0.017-0.025 sigma and an 8-bit activation control (np_forward with
# Q80-rounded norms) 0.026-0.043, too close for any limit (tests/
# test_host_loader.py has the same 0.045 for the same rounding at wider
# shapes).  With experts, rounding can also flip a near-tie of the router's
# top-k, and the logits of that position then move by tenths of a sigma: with
# SEED 31 and 32 the 16-expert case reads 0.56 and 0.29 at one position and
# 0.02 elsewhere, on the packed path and with bfloat16 activations alike.
# That is the model under rounding, not a fault; SEED 30 has no such tie, and
# PERF.md section 7 lists it for the PR that brings an MoE cell.
TOL_Q40_SIGMA = 0.045


def _np_forward():
    spec = importlib.util.spec_from_file_location(
        "tests_reference_impl", os.path.join(ROOT, "tests", "reference_impl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.np_forward


@pytest.fixture(scope="module", params=sorted(CASES))
def toy(request, tmp_path_factory):
    """``(module, shape, path of a seeded .m file the module wrote)``."""
    name, experts = CASES[request.param]
    model = models.load(name)
    shape = dict(TOY, **experts)
    path = str(tmp_path_factory.mktemp("models") / (request.param + ".m"))
    mformat.synthesize(path, model, shape, SEED, workers=2)
    return model, shape, path


@pytest.fixture(scope="module")
def references(toy):
    """The tokens, and the two references' logits after the prompt and after
    each decoded token: the module's ``last_logits`` (one pass a length) and
    ``np_forward`` (one pass) on the benchmark's dequantized weights."""
    import jax.numpy as jnp

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig

    model, shape, path = toy
    rng = np.random.RandomState(SEED)
    toks = [int(t) for t in rng.randint(3, shape["vocab_size"],
                                        PROMPT_LEN + DECODE_STEPS)]
    lengths = range(PROMPT_LEN, PROMPT_LEN + DECODE_STEPS + 1)
    ours = [model.last_logits(path, [toks[:n]])[0] for n in lengths]
    cfg = ModelConfig.from_spec(mfile.MFile(path).spec, dtype=jnp.float32)
    full = _np_forward()(_dequantized(model, shape, path), cfg, np.asarray(toks))
    return toks, ours, [full[n - 1] for n in lengths]


def test_header_and_plan_are_what_the_program_parses(toy):
    from dllama_tpu.io import mfile

    model, shape, path = toy
    mf = mfile.MFile(path)
    for key, want in dict(shape, weights_ftype=mformat.Q40,
                          hidden_act=mfile.ACT_SILU).items():
        assert getattr(mf.spec, key) == want, key
    assert mf.spec.arch == (mfile.ARCH_MIXTRAL if shape.get("n_experts")
                            else mfile.ARCH_LLAMA)
    assert (mf.spec.n_experts, mf.spec.n_active_experts) == (
        shape.get("n_experts", 0), shape.get("n_active_experts", 0))
    assert mf.spec.header_size == len(model.header(shape))
    ours = model.plan(shape)
    theirs = mfile.tensor_plan(mf.spec)
    assert [t[0] for t in ours] == [t.name for t in theirs]
    assert ours == [(t.name, t.shape, t.ftype, t.offset, t.nbytes) for t in theirs]
    assert ours[-1][3] + ours[-1][4] == os.path.getsize(path)


def _engine_logits(path: str, toks: list[int], steps: int, dtype,
                   packed: bool) -> list[np.ndarray]:
    """The program's logits after the prompt and after each of ``steps``
    decoded tokens (seeded, not greedy: tools/check_logits.py says why)."""
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
                 seq_len=TOY["seq_len"])
    logits, _ = eng.prefill(toks[:PROMPT_LEN])
    got = [np.asarray(logits, np.float32)[0]]
    for tok in toks[PROMPT_LEN:PROMPT_LEN + steps]:
        logits, _ = eng.decode_one(tok)
        got.append(np.asarray(logits, np.float32)[0])
    return got


def _dequantized(model, shape: dict, path: str) -> dict:
    """The file's weights as float32 in the runtime layout ``np_forward``
    takes (input dimension first, stacked over layers and experts), through
    the benchmark's reader and plain numpy."""
    raw = np.memmap(path, np.uint8, "r")
    by_name = {t[0]: t for t in model.plan(shape)}

    def tensor(name):
        _, shp, ft, off, nbytes = by_name[name]
        return mformat.dequantize(np.asarray(raw[off:off + nbytes]), shp, ft)

    layers = range(shape["n_layers"])

    def stack(key):
        return np.stack([tensor(f"layers.{i}.{key}").T for i in layers])

    out = {k: stack(k) for k in ("wq", "wk", "wv", "wo")}
    if shape.get("n_experts"):
        out["router"] = stack("moe_router")
        for key in ("up", "gate", "down"):
            out[key] = np.stack([np.stack(
                [tensor(f"layers.{i}.experts.{e}.{key}").T
                 for e in range(shape["n_experts"])]) for i in layers])
    else:
        out.update({k: stack(k) for k in ("w1", "w2", "w3")})
    out.update(
        rms_att=np.stack([tensor(f"layers.{i}.rms_att") for i in layers]),
        rms_ffn=np.stack([tensor(f"layers.{i}.rms_ffn") for i in layers]),
        embedding=tensor("token_embedding"), rms_final=tensor("rms_final"),
        wcls=tensor("wcls").T)
    return out


def _worst_sigma(got: list[np.ndarray], ref: list[np.ndarray]) -> float:
    return max(float(np.abs(g - r).max() / r.std()) for g, r in zip(got, ref))


def test_engine_and_two_references_agree(toy, references):
    import jax.numpy as jnp

    toks, ours, numpy_ref = references
    between = _worst_sigma(ours, numpy_ref)
    assert between <= TOL_SIGMA, f"the two references disagree: {between:.2e} sigma"
    engine = _engine_logits(toy[2], toks, DECODE_STEPS, jnp.float32, packed=False)
    worst = max(_worst_sigma(engine, ours), _worst_sigma(engine, numpy_ref))
    assert worst <= TOL_SIGMA, f"the engine against the references: {worst:.2e} sigma"


def test_tolerance_fails_bfloat16_activations(toy, references):
    """NEGATIVE CONTROL for ``TOL_SIGMA``: the program with bfloat16
    activations, the next precision below the float32 that run states."""
    import jax.numpy as jnp

    toks, ours, _ = references
    engine = _engine_logits(toy[2], toks, 0, jnp.bfloat16, packed=False)
    assert _worst_sigma(engine, ours) > 100 * TOL_SIGMA


def test_packed_engine_agrees_to_its_rounding(toy, references):
    import jax.numpy as jnp

    toks, ours, _ = references
    engine = _engine_logits(toy[2], toks, DECODE_STEPS, jnp.float32, packed=True)
    worst = _worst_sigma(engine, ours)
    assert worst <= TOL_Q40_SIGMA, f"{worst:.4f} sigma"
