"""The loader keeps weights on the host until ``place_params`` shards them,
and a model of Yi-34B's proportions agrees with the plain reference at tp=4
and tp=1 when it is loaded that way.

The toy model keeps what makes Yi-34B awkward to shard: 7 query heads per KV
head, and head counts and widths that are 7 x a power of two, so that no
per-shard size at tp=4 is a power of two (7 heads, 224 of 896 columns, 448 of
1792 FFN columns, 224 of 896 vocabulary rows a device).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import reference_impl
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.models.params import load_params
from dllama_tpu.obs import metrics as obs_metrics
from dllama_tpu.ops import q40, q8
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.parallel.sharding import param_specs
from dllama_tpu.runtime import engine as engine_mod
from dllama_tpu.runtime.engine import Engine

YI_TOY = dict(dim=896, hidden_dim=1792, n_layers=2, n_heads=28, n_kv_heads=4,
              vocab_size=896, seq_len=64)
PROMPT_LEN, DECODE_STEPS = 32, 8

# Logits are compared in units of the reference's own spread over the
# vocabulary (sigma), as benchmarks/harness/correct.py does on the chip.  The
# engine runs the Q40 kernel (interpreted here) on bfloat16 activations (8
# significant bits, relative to each element) and accumulates in float32; on
# these weights that moves a logit by at most 0.036 sigma over the prefill and
# 8 decode steps (0.036 at tp=1, 0.031 at tp=4; the XLA path reads 0.034;
# measured when this test was written; the chip reads 0.01-0.03 at full size).
# Rounding only the two normed activations of each block to Q80 (8 bits
# relative to the largest of every 32 values), half of what the reference
# engine's 8-bit activation path rounds, already moves the prefill's last
# logits by 0.051 sigma (0.069 at the worst position): the NEGATIVE CONTROL
# below.  0.045 admits the first and fails the second; a dropped layer or a
# wrong shard boundary is off by whole sigmas.
TOL_SIGMA = 0.045


def _spec(ftype=quants.Q40) -> mfile.ModelSpec:
    return mfile.ModelSpec(
        arch=mfile.ARCH_LLAMA, n_experts=0, n_active_experts=0,
        hidden_act=mfile.ACT_SILU, rope_theta=10000.0, weights_ftype=ftype,
        **YI_TOY)


@pytest.fixture(scope="module")
def yi_toy(tmp_path_factory):
    """A seeded Q40 ``.m`` file, and the same weights dequantized into the
    runtime layout (input dim first, layer-stacked) for ``np_forward``,
    read tensor by tensor through the file reader, not through the loader."""
    path = str(tmp_path_factory.mktemp("yi_toy") / "yi-toy.m")
    rng = np.random.RandomState(26)
    with mfile.MFileWriter(path, _spec()) as w:
        for t in w.plan:
            x = rng.randn(*t.shape).astype(np.float32)
            w.write_tensor(t.name, 1.0 + 0.02 * x if x.ndim == 1
                           else x / np.sqrt(t.shape[-1]))
    mf = mfile.MFile(path)
    L = YI_TOY["n_layers"]

    def stack(name):
        return np.stack([mf.tensor(f"layers.{i}.{name}").T for i in range(L)])

    dense = {k: stack(k) for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
    dense.update(
        rms_att=np.stack([mf.tensor(f"layers.{i}.rms_att") for i in range(L)]),
        rms_ffn=np.stack([mf.tensor(f"layers.{i}.rms_ffn") for i in range(L)]),
        embedding=mf.tensor("token_embedding"), rms_final=mf.tensor("rms_final"),
        wcls=mf.tensor("wcls").T)
    return path, dense


def _load(path: str, tp: int):
    mf = mfile.MFile(path)
    cfg = ModelConfig.from_spec(mf.spec, dtype=jnp.float32).with_(
        quant_impl="pallas_interpret")
    return load_params(mf, cfg, dtype=jnp.float32, keep_quantized=True,
                       fuse=tp == 1)


def _mesh(tp: int):
    return make_mesh(tp=tp, devices=jax.devices()[:tp])


def _sigmas(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(got - ref).max() / ref.std())


@pytest.mark.parametrize("tp", [1, 4])
def test_prefill_and_decode_logits_match_the_reference(yi_toy, tp):
    path, dense = yi_toy
    cfg, params = _load(path, tp)
    eng = Engine(cfg, params, mesh=_mesh(tp), seq_len=YI_TOY["seq_len"])
    rng = np.random.RandomState(7)
    toks = [int(t) for t in rng.randint(3, cfg.vocab_size, PROMPT_LEN)]
    logits, _ = eng.prefill(toks)
    got = [np.asarray(logits, np.float32)[0]]
    for _ in range(DECODE_STEPS):  # greedy, through the cache
        toks.append(int(got[-1].argmax()))
        logits, _ = eng.decode_one(toks[-1])
        got.append(np.asarray(logits, np.float32)[0])
    ref = reference_impl.np_forward(dense, cfg, np.asarray(toks))
    worst = max(_sigmas(g, ref[PROMPT_LEN - 1 + i]) for i, g in enumerate(got))
    assert worst <= TOL_SIGMA, f"tp={tp}: {worst:.4f} sigma"


def test_tolerance_fails_an_8bit_activation_path(yi_toy, monkeypatch):
    """NEGATIVE CONTROL for ``TOL_SIGMA``: the reference itself, with the
    output of every RMSNorm rounded to Q80, is outside the tolerance."""
    _, dense = yi_toy
    cfg = ModelConfig.from_spec(_spec(), dtype=jnp.float32)
    toks = np.random.RandomState(7).randint(3, cfg.vocab_size, PROMPT_LEN)
    ref = reference_impl.np_forward(dense, cfg, toks)
    plain = reference_impl.rmsnorm

    def q80_norm(x, w):
        y = plain(x, w)
        raw = quants.quantize_q80(y.astype(np.float32).reshape(-1))
        return quants.dequantize_q80(raw, y.size).reshape(y.shape)

    monkeypatch.setattr(reference_impl, "rmsnorm", q80_norm)
    rounded = reference_impl.np_forward(dense, cfg, toks)
    assert _sigmas(rounded[-1], ref[-1]) > TOL_SIGMA


def test_load_params_commits_nothing_to_a_device(yi_toy):
    path, _ = yi_toy
    for tp in (1, 4):
        _, params = _load(path, tp)
        leaves = jax.tree.leaves(params)
        assert leaves and all(type(x) is np.ndarray for x in leaves), \
            {k: type(v) for k, v in params.items()}


@pytest.mark.parametrize("ftype,codec", [(quants.Q40, q40), (quants.Q80, q8)])
def test_unfuse_of_host_leaves_is_views(tmp_path, ftype, codec):
    path = str(tmp_path / "m.m")
    rng = np.random.RandomState(0)
    with mfile.MFileWriter(path, _spec(ftype)) as w:
        for t in w.plan:
            w.write_tensor(t.name, (rng.randn(*t.shape) * 0.05).astype(np.float32))
    mf = mfile.MFile(path)
    cfg, fused = load_params(mf, keep_quantized=True, fuse=True)
    assert isinstance(fused["wqkv"], codec.Tensor)
    split = engine_mod._unfuse(fused, cfg)
    for whole, parts in (("wqkv", ("wq", "wk", "wv")), ("w13", ("w1", "w3"))):
        for part in parts:
            for leaf, base in zip(jax.tree.leaves(split[part]),
                                  jax.tree.leaves(fused[whole])):
                assert type(leaf) is np.ndarray and leaf.base is not None
                assert np.shares_memory(leaf, base)
    _, unfused = load_params(mf, keep_quantized=True, fuse=False)
    for k in ("wq", "wk", "wv", "w1", "w3"):
        for a, b in zip(jax.tree.leaves(split[k]), jax.tree.leaves(unfused[k])):
            np.testing.assert_array_equal(a, b)


def test_every_device_holds_its_share_and_no_more(yi_toy):
    path, _ = yi_toy
    tp = 4
    cfg, params = _load(path, tp)
    specs = param_specs(cfg)
    whole = {x.shape for k, v in params.items() if specs[k] != P()
             for x in jax.tree.leaves(v)}
    before = {id(a) for a in jax.live_arrays()}
    eng = Engine(cfg, params, mesh=_mesh(tp), seq_len=YI_TOY["seq_len"])
    # no sharded weight that the engine made sits whole on one device
    for a in jax.live_arrays():
        if id(a) not in before and len(a.sharding.device_set) == 1:
            assert a.shape not in whole, (a.shape, a.dtype)
    replicated = 0
    for name, value in eng.params.items():
        sharded = specs[name] != P()
        for leaf in jax.tree.leaves(value):
            sizes = {s.device.id: s.data.nbytes for s in leaf.addressable_shards}
            assert len(sizes) == tp
            want = leaf.nbytes // tp if sharded else leaf.nbytes
            assert set(sizes.values()) == {want}, (name, sizes)
            replicated += 0 if sharded else leaf.nbytes
    gauge = {d: obs_metrics.PARAM_BYTES_RESIDENT.get(str(dev.id))
             for d, dev in enumerate(jax.devices()[:tp])}
    assert len(set(gauge.values())) == 1, gauge
    total = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    assert gauge[0] == (total - replicated) // tp + replicated


def test_reduce_rule_is_static_and_takes_the_ring_at_yi_width(monkeypatch):
    """The reduce is chosen from static facts: on a TPU, halves that are
    lane-aligned take the ring (Llama-2-7B's 4096 and Yi-34B's 7168 alike),
    and ``DLLAMA_TP_REDUCE=psum`` is a requested path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DLLAMA_TP_REDUCE", raising=False)
    assert q40._fused_reduce_ok(4096, 4, False)
    assert q40._fused_reduce_ok(7168, 4, False)
    assert not q40._fused_reduce_ok(7168 + 128, 4, False)  # lane alignment
    assert not q40._fused_reduce_ok(7168, 4, True)         # interpreted
    monkeypatch.setenv("DLLAMA_TP_REDUCE", "psum")
    assert not q40._fused_reduce_ok(7168, 4, False)
