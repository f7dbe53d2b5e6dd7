"""Falcon-H1 (``ARCH_FALCON_H1``): grouped-query attention and a Mamba-2
state-space mixer side by side in every block, on the normal path of both
engines against the float32 ATTENTION-form reference (``reference_impl.
np_forward_falcon_h1``: no state, no ring, no convolution cache, no pages),
seeded random weights at ``tiny_falcon_h1()``: five query heads a kv head, two
groups, a state of 24 rows beside heads of 16, an odd ``W_in`` width (228), every
multiplier off 1.

``init_params`` draws ``A`` in 0.5..2 and ``dt`` near 0.01..0.1, so a position
decays by ``e^-0.005`` to ``e^-0.2`` and what was folded into the state hundreds
of positions ago still moves every logit (``test_the_state_matters``).
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models import config as config_mod
from dllama_tpu.models import packing
from dllama_tpu.models.config import tiny_falcon_h1
from dllama_tpu.models.params import init_params, load_params
from dllama_tpu.models.transformer import (forward_slots, forward_slots_all,
                                           init_kv_cache, init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import retention, ssm
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine, StateRewindTooDeep
from dllama_tpu.runtime.scheduler import SlotScheduler

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "converter"))

CFG = tiny_falcon_h1()
A, R, C = retention.FOLD, retention.REWIND, retention.RING
N = 3 * C + 60                        # a context past several folds
TOKS = np.random.RandomState(0).randint(3, 128, (N + 40,)).astype(np.int32)
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums (the ring's 128 products and the state's 24 a head,
# three layers) against the reference's float64 double sum: a few 1e-7 of
# logits whose spread is 0.03.  A state rounded to bfloat16 moves a logit by
# 1e-4, the smallest multiplier's absence (``mup_dt``) by 3e-5, a dropped branch
# by 1e-1 (``test_each_wrong_computation_is_seen``, ``test_a_bfloat16_state...``).
TOL = 3e-6
MUPS = [n for _, n, _ in mfile.SSM_KEYS[5:-1]]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=5, scale=0.08)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_falcon_h1(p, CFG, TOKS[:N]), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _logits(p, toks, cfg=CFG):
    return ref.np_forward_falcon_h1(p, cfg, np.asarray(toks, np.int32))


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=0, n_active_experts=0, vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, hidden_act=cfg.hidden_act,
        rope_theta=cfg.rope_theta, weights_ftype=ftype,
        norm_eps=cfg.norm_eps, head_dim=cfg.head_dim,
        **{n: getattr(cfg, n) for _, n, _ in mfile.SSM_KEYS[:-1]})
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, p, cfg=CFG, ftype=quants.F32, **kw):
    with mfile.MFileWriter(path, _spec(cfg, ftype=ftype, **kw)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = p[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            else:
                x = p[parts[-1]][int(parts[1])]
                x = x.reshape(-1) if parts[-1] == "ssm_conv_w" else \
                    x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ------------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    assert mfile.ARCH_FALCON_H1 == 0xABCD0A
    assert mfile.ARCH_NAMES[0xABCD0A] == "falcon_h1"
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_FALCON_H1] == (31, 32) + tuple(range(41, 61))
    assert mfile.KEY_MAX == 60
    path = str(tmp_path / "f.m")
    _write_model(path, want["np"])
    spec = mfile.read_spec(path)
    assert (spec.arch, spec.ssm_heads, spec.ssm_state, spec.ssm_groups,
            spec.ssm_conv, spec.head_dim) == (mfile.ARCH_FALCON_H1, 4, 24, 2, 4, 16)
    # 1e11 passes the i32 of key 12: the float key carries it
    assert spec.rope_theta == np.float32(1e11) and spec.rope_theta > 2 ** 31
    assert abs(spec.mup_key - 0.7) < 1e-7 and abs(spec.mup_embedding - 5.66) < 1e-6
    names = [t.name for t in mfile.tensor_plan(spec)]
    at = names.index("layers.0.wo")
    assert names[at + 1:at + 10] == ["layers.0." + n for n in (
        "ssm_in", "ssm_dt", "ssm_conv_w", "ssm_conv_b", "ssm_a_log", "ssm_dt_bias",
        "ssm_d", "ssm_norm", "ssm_out")]
    with mfile.MFile(path) as mf:
        assert mf.info("layers.0.ssm_in").shape == (64 + 160, 64)   # z | xBC: 224
        assert mf.info("layers.0.ssm_dt").shape == (4, 64)          # of W_in's 228
        cfg, p = load_params(mf)
    assert cfg.has_ssm and cfg.keeps_state and cfg.folds_state
    assert not cfg.attention_free and not cfg.periodic and not cfg.rope_interleaved
    assert (cfg.ssm_inner, cfg.ssm_channels, cfg.head_size) == (64, 160, 16)
    assert p["ssm_dt"].dtype == np.float32 and p["ssm_dt"].shape == (3, 64, 4)
    assert p["ssm_conv_w"].shape == (3, 160, 4)
    for k in ("wq", "ssm_in", "ssm_dt", "ssm_conv_w", "ssm_a_log", "ssm_out",
              "w2", "wcls"):
        assert np.array_equal(np.asarray(p[k], np.float32), want["np"][k]), k


@pytest.mark.parametrize("kw,says", [
    (dict(head_dim=3), "states its attention head size"),
    (dict(ssm_heads=0), "states its state-space mixer's sizes"),
    (dict(ssm_groups=3), "whole groups"),
    (dict(ssm_conv=1), "states its convolution's taps"),
    (dict(n_experts=4, n_active_experts=2), "dense SwiGLU"),
    (dict(mup_key=0.0), "a positive float"),
    (dict(window=8, window_period=3), "no sliding window and no periods"),
    (dict(arch=mfile.ARCH_LLAMA, head_dim=0, n_heads=8), "keys 41..60 describe a falcon_h1"),
])
def test_header_rules_are_refused_by_name(kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), "x.m")


def _published(**kw):
    base = dict(
        arch=mfile.ARCH_FALCON_H1, dim=5120, hidden_dim=21504, n_layers=18,
        n_heads=20, n_kv_heads=4, n_experts=0, n_active_experts=0,
        vocab_size=261120, seq_len=1024, hidden_act=mfile.ACT_SILU,
        rope_theta=1e11, norm_eps=1e-5, head_dim=128, ssm_heads=32,
        ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_conv=4,
        dtype=jnp.bfloat16)
    base.update(kw)
    return config_mod.ModelConfig(**base)


def test_published_widths_give_the_issues_state_pages_and_chunk():
    """Falcon-H1-34B's widths at the cell's 32 slots and 2080 pages: ONE layer
    of ONE slot owns pages and a state matrix and rings."""
    cfg = _published()
    assert (cfg.head_size, cfg.ssm_inner, cfg.ssm_channels) == (128, 4096, 5120)
    assert cfg.prefill_chunk() == retention.MAX_ROWS == 32
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 2080, 16, slots=32,
                                               max_pages=64))
    planes = {n: (a.shape, a.dtype) for n, a in pool.planes().items()}
    assert planes["k"] == ((18, 2080, 16, 4, 128), jnp.bfloat16)
    assert planes["rs"] == ((18, 32, 32, 256, 128), jnp.float32)
    assert planes["rk"] == ((18, 32, 2, 128, 256), jnp.bfloat16)
    assert planes["rv"] == ((18, 32, 32, 128, 128), jnp.bfloat16)
    assert planes["rg"] == ((18, 32, 1, 128, 32), jnp.float32)
    assert planes["cz"] == ((18, 32, 1, 64, 5120), jnp.bfloat16)
    assert "rz" not in planes and set(pool.pool_planes()) == {"k", "v"}
    size = lambda n: int(np.prod(planes[n][0])) * jnp.dtype(planes[n][1]).itemsize  # noqa: E731
    assert size("rs") == 32 * 18 * 4_194_304                # 2.42 GB of states
    assert size("k") + size("v") == 2080 * 589_824          # 1.23 GB of pages
    rings = sum(size(n) for n in ("rk", "rv", "rg", "cz"))
    assert round((size("rs") + rings) / 1e9, 2) == 3.48
    with pytest.raises(ValueError, match="needs the number of slots"):
        init_kv_pool(cfg, 100, 16)
    with pytest.raises(ValueError, match="no int8 form"):
        init_kv_pool(cfg, 100, 16, quant=True, slots=2)


# ---- the operator: three forms and the attention form ---------------------------

def _attention_form(c, b, x, dt, a):
    """(B, H, N, P) float64: the masked, decayed scores over ``dt * x``."""
    bsz, h, n, _ = x.shape
    m = h // c.shape[1]
    cs = np.cumsum(dt.astype(np.float64) * a[None, :, None], -1)   # (B, H, N)
    out = np.zeros(x.shape)
    for i in range(h):
        g = i // m
        s = np.einsum("btn,bjn->btj", c[:, g].astype(np.float64),
                      b[:, g].astype(np.float64))
        w = np.tril(np.ones((n, n))) * s * np.exp(
            np.minimum(cs[:, i][:, :, None] - cs[:, i][:, None, :], 0.0))
        out[:, i] = np.einsum("btj,bjp->btp", w * dt[:, i][:, None, :],
                              x[:, i].astype(np.float64))
    return out


class _Sizes:
    n_layers, ssm_heads, ssm_groups, ssm_state, ssm_head_dim, ssm_channels = \
        1, 4, 2, 24, 16, 8


def _walk(c, b, x, dt, a, calls, floor=None):
    """The operator through its planes, call by call (a call: its rows, or
    ``(rows, n_real)`` where the rows past ``n_real`` are padding ahead of the
    clock): ``(y (B, H, n, P), watermark, planes)``."""
    bsz = x.shape[0]
    planes = ssm.init_planes(_Sizes, bsz, jnp.float32)
    layer, a = jnp.int32(0), jnp.asarray(a, jnp.float32)

    @jax.jit
    def call(planes, c, b, x, dt, pos, n_real):
        t = x.shape[2]
        w, wn = retention.clock(planes["rw"], pos, t, n_real)
        rs = ssm.fold(planes["rs"], planes["rk"], planes["rv"], planes["rg"],
                      a, layer, w, wn)
        live = ssm.live_dt(dt.transpose(0, 2, 1), pos, floor, n_real)
        rk, rv, rg = ssm.write(planes["rk"], planes["rv"], planes["rg"], b, x,
                               live, layer, pos)
        y = ssm.read(c, rs, rk, rv, rg, a, layer, pos, wn)
        return y, dict(planes, rs=rs, rk=rk, rv=rv, rg=rg,
                       rw=wn.reshape(planes["rw"].shape))

    pos, ys = 0, []
    for t in calls:
        t, n_real = t if isinstance(t, tuple) else (t, t)
        sl = slice(pos, pos + t)
        y, planes = call(planes, c[:, :, sl], b[:, :, sl], x[:, :, sl],
                         dt[:, :, sl], jnp.full((bsz,), pos, jnp.int32),
                         jnp.full((bsz,), n_real, jnp.int32))
        ys.append(np.asarray(y)[:, :, :n_real])
        pos += n_real
    return np.concatenate(ys, axis=2), np.asarray(planes["rw"]).ravel(), planes


@pytest.fixture(scope="module")
def heads():
    rng = np.random.RandomState(1)
    n = 2 * C + 37
    c, b = rng.standard_normal((2, 2, 2, n, 24)).astype(np.float32)
    x = rng.standard_normal((2, 4, n, 16)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (2, 4, n)).astype(np.float32)
    return c, b, x, dt, -rng.uniform(0.5, 2.0, 4)


@pytest.mark.parametrize("calls", [
    [1] * (2 * C + 37),                                  # the state read, row by row
    [32] * 9 + [5],                                      # the block form
    [32, 16, 1, 1, 32, 7, 32, 1, 1, 16, 32, 1, 13, 11, 32, 32, 30],  # mixed widths
    # padding ahead of the clock: a decoded row in a step of 16, a ragged chunk
    # in its bucket, a row that rides along; the folds land where they would
    [(16, 1)] * 40 + [(32, 17), (16, 0), (32, 32), (32, 20)] * 3 + [(16, 1)] * 30,
], ids=["state-read", "block", "mixed", "padded"])
def test_the_three_forms_agree_with_the_attention_form(heads, calls):
    c, b, x, dt, a = heads
    n = sum(k[1] if isinstance(k, tuple) else k for k in calls)
    got, w, _ = _walk(c, b, x, dt, a, calls)
    wanted = _attention_form(c[:, :, :n], b[:, :, :n], x[:, :, :n], dt[:, :, :n], a)
    assert np.abs(got - wanted).max() < 2e-6 * np.abs(wanted).max()
    assert (w == retention.watermark(0, n)).all() and w[0] >= C


def test_the_operator_left_padded(heads):
    """A ragged batch's padding (positions before ``floor``) has ``dt`` 0: it
    neither decays nor feeds the state, and nothing downstream masks."""
    c, b, x, dt, a = heads
    floor = np.asarray([0, 70], np.int32)
    got, _, _ = _walk(c, b, x, dt, a, [32] * 6 + [1, 1, 30], jnp.asarray(floor))
    n = 224
    dt0 = np.where(np.arange(n)[None, None, :] >= floor[:, None, None],
                   dt[:, :, :n], 0.0)
    wanted = _attention_form(c[:, :, :n], b[:, :, :n], x[:, :, :n], dt0, a)
    err = np.abs(got - wanted)
    assert err[0].max() < 1e-5 and err[1, :, 70:].max() < 1e-5


def test_rows_that_hold_no_token_leave_the_state_bit_equal(heads):
    """A call whose ``n_real`` is 0 (a slot that rides along), at a clock where a
    real call would fold: no plane that a later read sees moves by a bit."""
    c, b, x, dt, a = heads
    _, w, before = _walk(c, b, x, dt, a, [32] * 7)
    _, w2, after = _walk(c, b, x, dt, a, [32] * 7 + [(16, 0)] * 3)
    assert (w == w2).all()
    assert np.array_equal(np.asarray(before["rs"]), np.asarray(after["rs"]))
    held = np.arange(int(w[0]), 224) % C          # the ring's live positions
    for n in ("rk", "rv", "rg"):
        assert np.array_equal(np.asarray(before[n])[:, :, :, held],
                              np.asarray(after[n])[:, :, :, held]), n
    # what the padding wrote ahead of the clock carries dt = 0
    ahead = np.arange(224, 240) % C
    assert not np.asarray(after["rg"])[:, :, :, ahead].any()


# ---- the one-stream engine -------------------------------------------------------

def test_prefill_then_decode_through_state_ring_and_cache(params, want):
    """A prompt of 3 C + 20 in chunks of 32 and a bucketed tail, then 40 tokens
    one by one: every position's logits are the reference's."""
    n = 3 * C + 20
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    before = obs_metrics.SSM_FOLDS.json_value()
    lg, _ = eng.prefill([int(t) for t in TOKS[:n]])
    assert np.abs(lg[0] - want["a"][n - 1]).max() < TOL
    for i in range(n, n + 40):
        lg, _ = eng.decode_one(int(TOKS[i]))
        assert np.abs(lg[0] - want["a"][i]).max() < TOL, i
    assert eng.pos == n + 40 and eng._state_lo == retention.watermark(0, n + 40)
    assert int(np.asarray(eng.cache.rw).ravel()[0]) == eng._state_lo >= 2 * C
    folds = obs_metrics.SSM_FOLDS.json_value() - before
    assert folds == eng._state_lo // A * CFG.n_layers


def test_the_state_matters(params, want):
    """With the state zeroed after 300 tokens the next token's logits are far
    off: the toy's decays let a context hundreds of positions old through."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:300]])
    held = np.asarray(eng.cache.rg)
    assert 0.005 < np.median(held[held != 0]) < 0.2
    eng.cache = eng.cache._replace(rs=jnp.zeros_like(eng.cache.rs))
    lg, _ = eng.decode_one(int(TOKS[300]))
    assert np.abs(lg[0] - want["a"][300]).max() > 100 * TOL


def test_a_bfloat16_state_would_not_pass(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:300]])
    eng.cache = eng.cache._replace(
        rs=eng.cache.rs.astype(jnp.bfloat16).astype(jnp.float32))
    lg, _ = eng.decode_one(int(TOKS[300]))
    assert np.abs(lg[0] - want["a"][300]).max() > 5 * TOL


@pytest.mark.parametrize("wrong", ["no_" + n[4:] for n in MUPS] + [
    "no_decay", "no_conv", "norm_before_gate", "one_group", "no_skip", "no_ssm",
    "no_attn"])
def test_each_wrong_computation_is_seen(want, wrong):
    """Every multiplier matters: a reference with one set to 1 (or a branch, the
    decay, the taps, the grouping, the order of gate and norm wrong) is out of
    the tolerance the engine is held to."""
    bad = ref.np_forward_falcon_h1(want["np"], CFG, TOKS[:200], wrong=wrong)
    assert np.abs(bad - want["a"][:200]).max() > 5 * TOL


@pytest.mark.parametrize("product,chunk", [(4 * 16 * 64, 16),
                                           (config_mod.PREFILL_PRODUCT_BYTES, 32)])
def test_chunked_prefill_equals_one_pass_at_every_chunk_width(
        params, want, monkeypatch, product, chunk):
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", product)
    assert CFG.prefill_chunk() == chunk
    n = 2 * C + chunk + 3
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:n]])
    assert np.abs(lg[0] - want["a"][n - 1]).max() < TOL
    lg, _ = eng.decode_one(int(TOKS[n]))
    assert np.abs(lg[0] - want["a"][n]).max() < TOL


def _burst(eng, n_prompt, burst, steps, **kw):
    return [t for t, _ in eng.generate_stream(
        [int(t) for t in TOKS[:n_prompt]], n_prompt + steps, temperature=0.0,
        chunk=burst, **kw)]


@pytest.mark.parametrize("j", [1, 7, 31])
def test_a_rewind_inside_the_ring_resumes_as_a_fresh_forward(params, want, j):
    """After bursts past a fold, ``pos`` set back by ``j`` and decoding resumed
    with another token: the logits are the fresh forward's (the state, both
    rings and the convolution's ring all still address those positions)."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    seq = _burst(eng, 150, 16, 1 + 48)
    assert eng.pos == 150 + 48 and eng._state_lo == 2 * A
    before = obs_metrics.SSM_STATE_REWINDS.json_value().get("in_ring", 0)
    eng.pos -= j
    kept = seq[:eng.pos]
    lg, _ = eng.decode_one(77)
    assert np.abs(lg[0] - _logits(want["np"], kept + [77])[-1]).max() < TOL
    assert obs_metrics.SSM_STATE_REWINDS.json_value()["in_ring"] == before + 1
    greedy = _logits(want["np"], seq[:-1]).argmax(-1)
    assert seq[150:] == greedy[149:].tolist()


def test_a_deeper_rewind_is_refused_by_name_and_counted(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:200]])
    for i in range(8):
        eng.decode_one(int(TOKS[200 + i]))
    assert (eng._state_lo, eng._state_hi) == (2 * A, 208)
    # the convolution's ring holds 64 positions, the state lags by 32 to 95:
    # here the convolution's ring is what gives out first
    assert eng._state_ring_lo == 208 - 64 and eng.cfg.ssm_conv - 1 == 3
    assert eng.state_holds(208 - 61) and not eng.state_holds(208 - 62)
    assert eng.state_holds(0)
    before = obs_metrics.SSM_STATE_REWINDS.json_value().get("refused", 0)
    eng.pos = 100
    with pytest.raises(StateRewindTooDeep, match="prefill the conversation again"):
        eng.decode_one(3)
    assert obs_metrics.SSM_STATE_REWINDS.json_value()["refused"] == before + 1
    assert not eng.resume_at(90) and eng.pos == 0           # counted, and reset
    eng.prefill([int(t) for t in TOKS[:5]])                 # from 0: a new sequence
    eng.pos = 9
    with pytest.raises(StateRewindTooDeep, match="has not seen"):
        eng.decode_one(3)
    # where the watermark binds: just folded, the convolution's ring reaches lower
    eng.reset()
    eng.prefill([int(t) for t in TOKS[:A + R]])
    assert (eng._state_lo, eng._state_ring_lo) == (A, A + R - 64)
    assert eng.state_holds(A) and not eng.state_holds(A - 1)


def test_a_burst_is_capped_to_what_the_ring_rewinds(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert eng._max_burst(8) == 8 and eng._max_burst(64) == 16


def test_prompt_lookup_decoding_rejects_drafts_over_the_state(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    prompt = [int(t) for t in TOKS[:60]] * 2
    out = eng.generate_pld(prompt, len(prompt) + 24, k=5)
    greedy = _logits(want["np"], out[:-1]).argmax(-1)
    assert out[len(prompt):] == greedy[len(prompt) - 1:].tolist()


def test_ragged_batch_matches_each_row_alone(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    prompts = [[int(t) for t in TOKS[:29]], [int(t) for t in TOKS[30:37]]]
    outs = eng.generate_batch(prompts, 29 + 76, temperature=0.0, chunk=4)
    assert int(np.asarray(eng.cache.rw).ravel()[0]) == A
    for p, o in zip(prompts, outs):
        greedy = _logits(want["np"], o[:-1]).argmax(-1)
        assert o[len(p):] == greedy[len(p) - 1:].tolist()


def test_snapshot_carries_the_state_and_its_account(params, tmp_path):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert set(eng._cache_arrays()) == {"cache." + n for n in (
        "k", "v", "rs", "rk", "rv", "rg", "rw", "cz")}
    first = _burst(eng, 150, 3, 5)
    path = str(tmp_path / "e.snap")
    eng.snapshot(path)
    rest = [t for t, _ in eng.generate_stream([first[-1]], 12, temperature=0.0,
                                              chunk=3)]
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng2.restore(path)
    assert (eng2._state_lo, eng2.pos) == (A, 154) and eng2._state_hi >= 154
    assert eng2._state_ring_lo == 160 - 64   # the conv ring: a burst ahead was written
    again = [t for t, _ in eng2.generate_stream([first[-1]], 12, temperature=0.0,
                                                chunk=3)]
    assert again == rest


# ---- the slot path: a state and pages in the same layer of the same slot --------

PAGES, PS, WIDTH = 160, 4, 80


def _row_tokens(r, lo, hi):
    return (TOKS if r % 2 == 0 else TOKS[::-1])[lo:hi]


def _table(b):
    """Slot ``r`` owns pages ``1 + r * WIDTH ..`` (page 0 is the scratch page)."""
    return jnp.asarray(1 + np.arange(b)[:, None] * WIDTH // 2
                       + np.arange(WIDTH // 2)[None, :], jnp.int32)


@jax.jit
def _chunk_step(params, tk, cache, pos, n, table):
    return forward_slots(params, CFG, tk, cache, pos, n, table)


def _slot_state(params, hist):
    """A paged slot cache whose slots have consumed ``hist[b]`` tokens each,
    through ``forward_slots`` in chunks of 16."""
    b = len(hist)
    cache = init_kv_pool(CFG, PAGES, PS, slots=b, max_pages=WIDTH // 2)
    pos = np.zeros((b,), np.int32)
    while (pos < hist).any():
        n = np.minimum(hist - pos, 16)
        tk = np.zeros((b, 16), np.int32)
        for r in range(b):
            tk[r, :n[r]] = _row_tokens(r, pos[r], pos[r] + n[r])
        _, cache = _chunk_step(params, jnp.asarray(tk), cache, jnp.asarray(pos),
                               jnp.asarray(n), _table(b))
        pos = pos + n
    return cache


@pytest.mark.parametrize("buckets", [(), (16,)], ids=["unpacked", "packed"])
def test_one_step_with_rows_of_0_1_5_and_16_tokens(params, want, monkeypatch,
                                                   buckets):
    """A mixed step past folds on a paged pool: a slot that rides along
    (n_valid 0, its state kept), a decoding slot, a ragged last chunk and a
    whole chunk of a new tenant; the same packed (PR 42) and over every row."""
    monkeypatch.setattr(packing, "BUCKETS", buckets)
    hist = np.asarray([137, 150, 144, 0], np.int32)
    cache = _slot_state(params, hist)
    nv = np.asarray([0, 1, 5, 16], np.int32)
    tk = np.zeros((4, 16), np.int32)
    for r in range(4):
        tk[r, :nv[r]] = _row_tokens(r, hist[r], hist[r] + nv[r])
    assert (packing.plan(jnp.asarray(nv), 4, 16) is not None) == bool(buckets)
    before = {n: np.asarray(a) for n, a in cache.planes().items()}
    lg, cache = forward_slots(params, CFG, jnp.asarray(tk), cache,
                              jnp.asarray(hist), jnp.asarray(nv), _table(4))
    for r in (1, 2, 3):
        wanted = _logits(want["np"], _row_tokens(r, 0, hist[r] + nv[r]))[-1]
        assert np.abs(np.asarray(lg)[r] - wanted).max() < TOL, r
    # the slot that rode along: its state matrix is bit-equal
    assert np.array_equal(np.asarray(cache.rs)[:, 0], before["rs"][:, 0])
    # it goes on from its own state; slot 1 is taken by a new tenant at
    # position 0 over its predecessor's state and pages, which it must not see
    nv2 = np.asarray([1, 7, 0, 0], np.int32)
    tk2 = np.zeros((4, 16), np.int32)
    tk2[0, 0] = TOKS[137]
    tk2[1, :7] = TOKS[40:47]
    pos2 = np.asarray([137, 0, 149, 16], np.int32)
    lg, _ = forward_slots(params, CFG, jnp.asarray(tk2), cache,
                          jnp.asarray(pos2), jnp.asarray(nv2), _table(4))
    assert np.abs(np.asarray(lg)[0] - want["a"][137]).max() < TOL
    assert np.abs(np.asarray(lg)[1] - _logits(want["np"], TOKS[40:47])[-1]).max() < TOL


def test_verify_step_keeps_every_position_and_a_rejected_draft(params, want):
    hist = np.asarray([A + R - 4], np.int32)
    cache = _slot_state(params, hist)
    h = int(hist[0])
    draft = np.asarray([[TOKS[h], TOKS[h + 1], 9, 9, 9]], np.int32)
    lg, cache = forward_slots_all(params, CFG, jnp.asarray(draft), cache,
                                  jnp.asarray(hist), jnp.asarray([5], np.int32),
                                  _table(1))
    assert np.abs(np.asarray(lg)[0, :2] - want["a"][h:h + 2]).max() < TOL
    lg, _ = forward_slots(params, CFG, jnp.asarray([[TOKS[h + 2]]], np.int32),
                          cache, jnp.asarray([h + 2], np.int32),
                          jnp.asarray([1], np.int32), _table(1))
    assert np.abs(np.asarray(lg)[0] - want["a"][h + 2]).max() < TOL


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_the_scheduler_serves_the_reference_token_for_token(params, want, paged):
    """Five requests on two slots, their lengths apart so that the slots are out
    of step and fold in different steps, each slot taken over by a new request
    with the last tenant's state (and pages) left in place: every stream is the
    reference's greedy stream.  Admission is by pages and slot together."""
    kw = dict(kv_pages=150, kv_page_size=4) if paged else {}
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, seq_len=256, **kw)
    assert eng.paged == paged and eng.slot_state == "state-space mixers' state"
    sched = SlotScheduler(eng, prefill_chunk=16, prefix_reuse=True, preempt=True)
    assert sched.prefix_cache is None and (sched.pool is not None) == paged
    try:
        prompts = [[int(t) for t in TOKS[a:a + n]]
                   for a, n in ((0, 150), (10, 37), (3, 217), (50, 133), (7, 16))]
        tickets = [sched.submit(p, max_new=14 + 5 * i)
                   for i, p in enumerate(prompts)]
        for p, t in zip(prompts, tickets):
            out = list(t.tokens())
            greedy = _logits(want["np"], p + out[:-1]).argmax(-1)
            assert out == greedy[len(p) - 1:].tolist()
    finally:
        sched.close()


# ---- the loader, the ledger, the refusals ---------------------------------------

def test_loader_packed_agrees_with_the_reference(tmp_path, want):
    """A Q40 file through the normal loader (``wqkv`` and ``w13`` joined,
    ``ssm_in`` and ``ssm_out`` packed, the ``dt`` projection float32): prefill
    and decode against the reference of the dequantized weights."""
    path = str(tmp_path / "q.m")
    _write_model(path, want["np"], ftype=quants.Q40)
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf, dtype=jnp.float32, keep_quantized=True)
        _, dense = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    assert "wqkv" in p and "w13" in p and p["ssm_dt"].dtype == np.float32
    assert type(p["ssm_in"]).__name__ == "QTensor" == type(p["ssm_out"]).__name__
    deq = {k: np.asarray(v, np.float32) for k, v in dense.items()}
    wanted = ref.np_forward_falcon_h1(deq, cfg, TOKS[:150])
    eng = Engine(cfg.with_(quant_impl="xla"), p, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:149]])
    assert np.abs(lg[0] - wanted[148]).max() < 0.1 * wanted[148].std()
    lg, _ = eng.decode_one(int(TOKS[149]))
    assert np.abs(lg[0] - wanted[149]).max() < 0.1 * wanted[149].std()


def test_the_gauges_and_the_ledger_name_the_three_owners(params):
    obs_dispatch.reset()
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, seq_len=64, kv_pages=40,
                 kv_page_size=4)
    by_kind = obs_metrics.KV_CACHE_BYTES.json_value()
    planes = eng.cache.planes()
    assert by_kind["full"] == int(planes["k"].nbytes) * 2
    assert by_kind["ssm"] == sum(int(a.nbytes) for n, a in planes.items()
                                 if n not in ("k", "v"))
    assert by_kind["retention"] == by_kind["conv"] == 0
    # a cached token costs its keys and values; the state is depth-free
    per_token = CFG.n_layers * 2 * CFG.kv_dim * 4
    assert eng.kv_bytes_per_token == per_token
    assert set(eng.cache.pool_planes()) == {"k", "v"}
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=2)
    eng2.generate_batch([[5, 6, 7], [8, 9]], 3 + 4, temperature=0.0, chunk=2)
    sites = obs_dispatch.dispatches()
    assert {"ssm/state-read", "ssm/block", "ssm/fold", "conv/ring"} <= set(sites)
    obs_dispatch.reset()


def test_scopes_tell_the_two_mixers_of_one_layer_apart(params):
    cache = init_kv_pool(CFG, 12, 4, slots=1, max_pages=12)
    table = jnp.asarray(np.arange(12, dtype=np.int32)[None])
    text = jax.jit(lambda c: forward_slots(
        params, CFG, jnp.zeros((1, 4), jnp.int32), c, jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 4, jnp.int32), table)).lower(cache).as_text(debug_info=True)
    for name in ("qkv/ssm", "wo/ssm", "kv_write/fold", "kv_write/recent",
                 "kv_write/conv", "attn/state", "attn/recent", "attn/conv", "w1",
                 "page_idx"):
        assert name in text, name
    assert "attn/full" not in text and "qkv/retention" not in text


# ---- the converter ---------------------------------------------------------------

FALCON_HF = dict(
    model_type="falcon_h1", hidden_size=64, intermediate_size=96,
    num_hidden_layers=3, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, vocab_size=128, max_position_embeddings=512, hidden_act="silu",
    rms_norm_eps=1e-5, rope_theta=100000000000, rope_scaling=None,
    attention_bias=False, mamba_proj_bias=False, mlp_bias=False,
    projectors_bias=False, tie_word_embeddings=False, attn_layer_indices=None,
    mamba_conv_bias=True, mamba_rms_norm=True, mamba_use_mlp=True,
    mamba_norm_before_gate=False, mamba_d_ssm=64, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=24, mamba_n_groups=2, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=128,
    embedding_multiplier=5.66, lm_head_multiplier=0.25,
    attention_in_multiplier=0.9, attention_out_multiplier=0.6,
    ssm_in_multiplier=0.5, ssm_out_multiplier=0.8, key_multiplier=0.7,
    mlp_multipliers=[0.6, 0.45], ssm_multipliers=[0.7, 1.5, 1.4, 1.3, 0.7])


def _hf_checkpoint(p, cfg):
    """A toy checkpoint under the names the converter ASSUMES: unverified until
    the published files are here."""
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.final_layernorm.weight": p["rms_final"],
          "lm_head.weight": p["wcls"].T}
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        for ours, theirs in (("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"),
                             ("wv", "self_attn.v_proj"), ("wo", "self_attn.o_proj"),
                             ("ssm_out", "mamba.out_proj"),
                             ("w1", "feed_forward.gate_proj"),
                             ("w2", "feed_forward.down_proj"),
                             ("w3", "feed_forward.up_proj")):
            hf[f"{base}{theirs}.weight"] = p[ours][i].T
        hf[base + "mamba.in_proj.weight"] = np.concatenate(
            [p["ssm_in"][i].T, p["ssm_dt"][i].T])                 # z | xBC | dt
        hf[base + "mamba.conv1d.weight"] = p["ssm_conv_w"][i][:, None, :]
        for ours, theirs in (("ssm_conv_b", "mamba.conv1d.bias"),
                             ("ssm_a_log", "mamba.A_log"),
                             ("ssm_dt_bias", "mamba.dt_bias"), ("ssm_d", "mamba.D"),
                             ("ssm_norm", "mamba.norm.weight"),
                             ("rms_att", "input_layernorm.weight"),
                             ("rms_ffn", "pre_ff_layernorm.weight")):
            hf[base + theirs] = p[ours][i]
    return {k: np.ascontiguousarray(v, np.float32) for k, v in hf.items()}


def test_convert_round_trip_and_the_logits(tmp_path, want):
    from safetensors.numpy import save_file

    import convert_hf

    p = want["np"]
    (tmp_path / "config.json").write_text(json.dumps(FALCON_HF))
    save_file(_hf_checkpoint(p, CFG), str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "falcon.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    mf = mfile.MFile(out)
    assert (mf.spec.arch, mf.spec.ssm_state) == (mfile.ARCH_FALCON_H1, 24)
    got_cfg, params = load_params(mf)
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32), p[k], err_msg=k)
    eng = Engine(got_cfg.with_(dtype=jnp.float32), params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:70]])
    # the header carries the multipliers as float32
    assert np.abs(lg[0] - want["a"][69]).max() < 10 * TOL


@pytest.mark.parametrize("key,value,says", [
    ("attention_bias", True, "attention_bias is True"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling is"),
    ("tie_word_embeddings", True, "tie_word_embeddings is True"),
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate is True"),
    ("mamba_conv_bias", False, "mamba_conv_bias is false"),
    ("mamba_d_ssm", 128, "is not mamba_d_ssm"),
])
def test_convert_refuses_what_the_file_cannot_carry(tmp_path, key, value, says):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(FALCON_HF, **{key: value})))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)
