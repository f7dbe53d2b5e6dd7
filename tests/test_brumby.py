"""Brumby (``ARCH_BRUMBY``): power-retention layers on the normal path of both
engines against the float32 ATTENTION-form reference (``reference_impl.
np_forward_brumby``: no state, no ring, no ``phi``), seeded random weights at
``tiny_brumby()``.

The gates: a bias-free ``W_g`` over a zero-mean input gives ``log gamma`` a
median of -0.69, a decay that hides everything older than a few tens of
positions, the state included.  ``_init`` gives the embedding a shared direction
and ``W_g`` a part along it, so that ``log gamma`` is -0.01 .. -0.5 and what was
folded into the state hundreds of positions ago still moves every logit
(``test_the_gates_show_the_state``).
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
from dllama_tpu.models.config import tiny_brumby
from dllama_tpu.models.params import init_params, load_params
from dllama_tpu.models.transformer import (forward_slots, forward_slots_all,
                                           init_kv_cache, init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import retention
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine, StateRewindTooDeep
from dllama_tpu.runtime.scheduler import SlotScheduler
from dllama_tpu.runtime.spec import Proposer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "converter"))

CFG = tiny_brumby()
N = 3 * retention.RING + 60           # a context past several folds
TOKS = np.random.RandomState(0).randint(3, 128, (N + 40,)).astype(np.int32)
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums (the state's D = 144 products a head, four layers), a
# few 1e-6 of logits whose spread is about 0.4.  A state rounded to bfloat16
# moves a logit by 3e-4 and more, a dropped gate or a missing quotient by 1e-1
# (``test_each_wrong_computation_is_seen``, ``test_a_bfloat16_state_would_not_pass``).
TOL = 3e-5
A, R, C = retention.FOLD, retention.REWIND, retention.RING


def _init(cfg, seed=5):
    """Random params whose gates are not saturated (module docstring)."""
    p = init_params(cfg, seed=seed, scale=0.08)
    rng = np.random.RandomState(seed + 1)
    e = rng.standard_normal(cfg.dim)
    e /= np.linalg.norm(e)
    along = rng.uniform(0.15, 0.5, (cfg.n_layers, 1, cfg.n_kv_heads))
    wg = 0.02 * rng.standard_normal(p["wg"].shape) + along * e[None, :, None]
    return dict(p, embedding=p["embedding"] + jnp.asarray(e, p["embedding"].dtype),
                wg=jnp.asarray(wg, jnp.float32))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_brumby(p, CFG, TOKS[:N]), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _logits(p, toks, cfg=CFG):
    return ref.np_forward_brumby(p, cfg, np.asarray(toks, np.int32))


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=0, n_active_experts=0, vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, hidden_act=cfg.hidden_act,
        rope_theta=cfg.rope_theta, weights_ftype=ftype,
        norm_eps=cfg.norm_eps, retention_degree=cfg.retention_degree)
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
                x = x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ------------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    assert mfile.ARCH_BRUMBY == 0xABCD08 and mfile.ARCH_NAMES[0xABCD08] == "brumby"
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_BRUMBY] == (31, 39)
    assert mfile.KEY_MAX == 60   # since PR 60: key 60, Falcon-H1's float rope_theta, is the last
    path = str(tmp_path / "b.m")
    _write_model(path, want["np"])
    spec = mfile.read_spec(path)
    assert (spec.arch, spec.retention_degree) == (mfile.ARCH_BRUMBY, 2)
    assert abs(spec.norm_eps - 1e-6) < 1e-12
    names = [t.name for t in mfile.tensor_plan(spec)]
    at = names.index("layers.0.wo")
    assert names[at + 1:at + 4] == ["layers.0.wg", "layers.0.q_norm",
                                    "layers.0.k_norm"]
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf)
    assert cfg.attention_free and cfg.qk_head_norm and cfg.keeps_state
    assert not cfg.periodic and not cfg.rope_interleaved
    assert p["wg"].dtype == np.float32 and p["wg"].shape == (4, 160, 2)
    for k in ("wq", "wg", "q_norm", "w2", "wcls"):
        assert np.array_equal(np.asarray(p[k], np.float32), want["np"][k]), k


@pytest.mark.parametrize("kw,says", [
    (dict(retention_degree=3), "released degree"),
    (dict(retention_degree=0), "a brumby file states it"),
    (dict(arch=mfile.ARCH_LLAMA), "key 39"),
    (dict(n_experts=4, n_active_experts=2), "dense SwiGLU"),
])
def test_header_rules_are_refused_by_name(kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), "x.m")


def test_published_widths_give_the_issues_state_and_chunk():
    """Brumby-14B-Base's widths: D, a slot's state, the rows of a call."""
    cfg = config_mod.ModelConfig(
        arch=mfile.ARCH_BRUMBY, dim=5120, hidden_dim=17408, n_layers=20,
        n_heads=40, n_kv_heads=8, n_experts=0, n_active_experts=0,
        vocab_size=151936, seq_len=6144, hidden_act=mfile.ACT_SILU,
        rope_theta=1e6, norm_eps=1e-6, retention_degree=2, dtype=jnp.bfloat16)
    assert cfg.head_size == 128 and retention.state_dim(128) == 9216
    assert cfg.prefill_chunk() == retention.MAX_ROWS == 32
    shapes = jax.eval_shape(lambda: init_kv_cache(cfg, 8))
    planes = {n: (a.shape, a.dtype) for n, a in shapes.planes().items()}
    assert planes["rs"] == ((20, 8, 8, 9216, 128), jnp.float32)
    assert planes["rz"] == ((20, 8, 8, 1, 9216), jnp.float32)
    assert planes["rk"] == ((20, 8, 8, 128, 128), jnp.bfloat16)
    assert planes["k"][0] == (0, 8, 8, 0, 128)           # no layer, no position
    state = sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                for n, (s, d) in planes.items() if n in ("rs", "rz"))
    assert round(state / 1e9, 2) == 6.09                   # eight slots' states
    # a pool of a model with no paged layer is its slots' states alone
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 100, 16, slots=8))
    assert {n: a.shape for n, a in pool.planes().items()} == {
        n: s for n, (s, _) in planes.items()}


# ---- the operator: three forms and the attention form ---------------------------

def test_phi_is_the_symmetric_square():
    rng = np.random.RandomState(3)
    for dh in (16, 8, 128, 6):
        a, b = rng.standard_normal((2, 5, dh)).astype(np.float32)
        got = np.sum(np.asarray(retention.phi(a)) * np.asarray(retention.phi(b)), -1)
        assert np.allclose(got, np.sum(a * b, -1) ** 2 / dh, rtol=2e-5, atol=1e-5)
        assert retention.phi(a).shape[-1] == retention.state_dim(dh)
    assert retention.state_dim(16) == 144 and retention.state_dim(128) == 9216


def _attention_form(q, k, v, lg, floor=None):
    """(B, Hq, N, dh) float64: the masked, decayed, squared scores."""
    b, hq, n, dh = q.shape
    m = hq // k.shape[1]
    live = np.ones((b, n), bool) if floor is None else \
        np.arange(n)[None, :] >= floor[:, None]
    cs = np.cumsum(np.where(live[:, None], lg, 0).astype(np.float64), -1)
    out = np.zeros((b, hq, n, dh))
    for h in range(hq):
        g = h // m
        s = np.einsum("btd,bjd->btj", q[:, h].astype(np.float64),
                      k[:, g].astype(np.float64)) / np.sqrt(dh)
        a = np.tril(np.ones((n, n))) * s * s * np.exp(
            np.minimum(cs[:, g][:, :, None] - cs[:, g][:, None, :], 0.0))
        a = a * live[:, None, :]
        out[:, h] = np.einsum("btj,bjd->btd", a, v[:, g].astype(np.float64)) / (
            a.sum(-1, keepdims=True) + retention.EPS)
    return out


def _walk(q, k, v, lg, calls, floor=None):
    """The operator through its planes, call by call (a call: its rows, or
    ``(rows, n_real)`` where the rows past ``n_real`` are padding ahead of the
    clock): ``(y (B, Hq, n, dh), watermark)``."""
    b, g, dh = q.shape[0], k.shape[1], q.shape[3]
    planes = retention.init_planes(1, b, g, dh, jnp.float32)
    layer = jnp.int32(0)

    @jax.jit
    def call(planes, q, k, v, lg, pos, n_real):
        t = q.shape[2]
        w, wn = retention.clock(planes["rw"], pos, t, n_real)
        rs, rz = retention.fold(planes["rs"], planes["rz"], planes["rk"],
                                planes["rv"], planes["rg"], layer, w, wn, floor)
        rk, rv, rg = retention.write(planes["rk"], planes["rv"], planes["rg"],
                                     k, v, lg, layer, pos)
        y = retention.read(q, rs, rz, rk, rv, rg, layer, pos, wn, floor)
        return y, dict(rs=rs, rz=rz, rk=rk, rv=rv, rg=rg,
                       rw=wn.reshape(planes["rw"].shape))

    pos, ys = 0, []
    for t in calls:
        t, n_real = t if isinstance(t, tuple) else (t, t)
        sl = slice(pos, pos + t)
        y, planes = call(planes, q[:, :, sl], k[:, :, sl], v[:, :, sl],
                         lg[:, :, sl], jnp.full((b,), pos, jnp.int32),
                         jnp.full((b,), n_real, jnp.int32))
        ys.append(np.asarray(y)[:, :, :n_real])
        pos += n_real
    return np.concatenate(ys, axis=2), np.asarray(planes["rw"]).ravel()


@pytest.fixture(scope="module")
def heads():
    rng = np.random.RandomState(1)
    n = 2 * C + 37
    q = rng.standard_normal((2, 10, n, 16)).astype(np.float32)
    k, v = rng.standard_normal((2, 2, 2, n, 16)).astype(np.float32)
    lg = (-0.05 * np.abs(rng.standard_normal((2, 2, n)))).astype(np.float32)
    return q, k, v, lg


@pytest.mark.parametrize("calls", [
    [1] * (2 * C + 37),                                  # the state read, row by row
    [32] * 9 + [5],                                      # the block form
    [32, 16, 1, 1, 32, 7, 32, 1, 1, 16, 32, 1, 13, 11, 32, 32, 30],  # mixed widths
    # padding ahead of the clock: a decoded row in a step of 16, a ragged chunk
    # in its bucket, a row that rides along; the folds land where they would
    [(16, 1)] * 40 + [(32, 17), (16, 0), (32, 32), (32, 20)] * 3 + [(16, 1)] * 30,
], ids=["state-read", "block", "mixed", "padded"])
def test_the_three_forms_agree_with_the_attention_form(heads, calls):
    """Decoded rows, prefill blocks and every mix of them, through folds: each
    is the masked, decayed ``(Q K^T)^2 V`` over its quotient."""
    q, k, v, lg = heads
    n = sum(c[1] if isinstance(c, tuple) else c for c in calls)
    with jax.default_matmul_precision("highest"):
        got, w = _walk(q, k, v, lg, calls)
    wanted = _attention_form(q[:, :, :n], k[:, :, :n], v[:, :, :n], lg[:, :, :n])
    assert np.abs(got - wanted).max() < 2e-6 * np.abs(wanted).max()
    assert (w == retention.watermark(0, n)).all() and w[0] >= C


def test_the_operator_left_padded(heads):
    """A ragged batch's padding (positions before ``floor``) enters neither
    the ring's read nor the fold."""
    q, k, v, lg = heads
    floor = np.asarray([0, 70], np.int32)
    got, _ = _walk(q, k, v, lg, [32] * 6 + [1, 1, 30], jnp.asarray(floor))
    n = 224
    wanted = _attention_form(q[:, :, :n], k[:, :, :n], v[:, :, :n], lg[:, :, :n],
                             floor)
    err = np.abs(got - wanted)
    assert err[0].max() < 1e-5 and err[1, :, 70:].max() < 1e-5


@pytest.mark.parametrize("w,clock,after", [
    (0, 1, 0), (0, A + R - 1, 0), (0, A + R, A), (A, 2 * A + R - 1, A),
    (A, 2 * A + R, 2 * A), (0, 5 * A + 3, 4 * A), (3 * A, 64, 3 * A)])
def test_the_watermark_rule(w, clock, after):
    assert retention.watermark(w, clock) == after
    assert int(retention.watermark(jnp.int32(w), jnp.int32(clock))) == after
    assert retention.watermark(np.asarray([w]), np.asarray([clock]))[0] == after
    # what is folded lies at least REWIND behind the clock, and the widest
    # call's padding past the clock still fits the ring beside what waits
    assert after == w or after <= clock - R
    assert clock + retention.MAX_ROWS - max(after, (clock - R) // A * A) <= C


def test_a_call_wider_than_the_ring_allows_is_refused_by_name():
    with pytest.raises(ValueError, match="feed at most 32 rows a call"):
        retention.clock(jnp.zeros((1, 1, 1, 1, 1), jnp.int32),
                        jnp.zeros((1,), jnp.int32), 33)


# ---- the one-stream engine -------------------------------------------------------

def test_prefill_then_decode_through_the_cache_past_several_folds(params, want):
    """A prompt of 3 C + 20 in chunks of 32 and a bucketed tail, then 40
    tokens one by one: every position's logits are the reference's."""
    n = 3 * C + 20
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    before = obs_metrics.RETENTION_FOLDS.json_value()
    lg, _ = eng.prefill([int(t) for t in TOKS[:n]])
    assert np.abs(lg[0] - want["a"][n - 1]).max() < TOL
    for i in range(n, n + 40):
        lg, _ = eng.decode_one(int(TOKS[i]))
        assert np.abs(lg[0] - want["a"][i]).max() < TOL, i
    assert eng.pos == n + 40 and eng._state_lo == retention.watermark(0, n + 40)
    assert int(np.asarray(eng.cache.rw).ravel()[0]) == eng._state_lo >= 2 * C
    folds = obs_metrics.RETENTION_FOLDS.json_value() - before
    assert folds == eng._state_lo // A * CFG.n_layers


def test_the_gates_show_the_state(params, want):
    """What the seeded gates are for: with the state zeroed after 300 tokens,
    the next token's logits are far off; ``log gamma`` is -0.01 .. -0.5."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:300]])
    lg = np.asarray(eng.cache.rg)
    held = lg[lg != 0]
    assert -0.6 < np.median(held) < -0.01 and held.min() > -1.5
    eng.cache = eng.cache._replace(rs=jnp.zeros_like(eng.cache.rs),
                                   rz=jnp.zeros_like(eng.cache.rz))
    lg, _ = eng.decode_one(int(TOKS[300]))
    assert np.abs(lg[0] - want["a"][300]).max() > 100 * TOL


def test_a_bfloat16_state_would_not_pass(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:300]])
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    eng.cache = eng.cache._replace(rs=low(eng.cache.rs), rz=low(eng.cache.rz))
    lg, _ = eng.decode_one(int(TOKS[300]))
    assert np.abs(lg[0] - want["a"][300]).max() > 5 * TOL


@pytest.mark.parametrize("wrong", ["no_gate", "no_quotient", "degree_1", "no_rope",
                                   "no_head_norm", "gate_per_query_head"])
def test_each_wrong_computation_is_seen(want, wrong):
    bad = ref.np_forward_brumby(want["np"], CFG, TOKS[:200], wrong=wrong)
    assert np.abs(bad - want["a"][:200]).max() > 100 * TOL


@pytest.mark.parametrize("product,chunk", [(4 * 16 * 160, 16), (4 * 32 * 160, 32),
                                           (config_mod.PREFILL_PRODUCT_BYTES, 32)])
def test_chunked_prefill_equals_one_pass_at_every_chunk_width(
        params, want, monkeypatch, product, chunk):
    """Chunks of 16 and 32 (the widest call: the product's own rule would give
    64) with a ragged last chunk in its bucket: the rows past the prompt's end
    are rows ahead of the clock, and the folds land where the one rule puts
    them whatever the chunking."""
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
    """After bursts past a fold, ``pos`` set back by ``j`` (a stop string's
    hold-back, a cancelled request, a burst's overshoot) and decoding resumed
    with another token: the logits are the fresh forward's."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    seq = _burst(eng, 150, 16, 1 + 48)
    # the next burst is already written (to 214): two blocks are folded
    assert eng.pos == 150 + 48 and eng._state_lo == 2 * A
    before = obs_metrics.RETENTION_REWINDS.json_value().get("in_ring", 0)
    eng.pos -= j
    kept = seq[:eng.pos]
    lg, _ = eng.decode_one(77)
    assert np.abs(lg[0] - _logits(want["np"], kept + [77])[-1]).max() < TOL
    assert obs_metrics.RETENTION_REWINDS.json_value()["in_ring"] == before + 1
    greedy = _logits(want["np"], seq[:-1]).argmax(-1)
    assert seq[150:] == greedy[149:].tolist()


def test_a_second_turn_after_an_end_token_inside_a_burst(params, want):
    """The engine's own rewind: a burst yields the end token third, the next
    burst is already written; the second turn's prefill and decode are the
    reference's, across a fold."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    seq = _burst(eng, 170, 8, 30)
    eng.reset()
    # the first token new to the stream from a burst's third place on
    stop = next(s for s in range(3, 30) if seq[170 + s] not in seq[170:170 + s])
    again = _burst(eng, 170, 8, 30, eos_ids=(seq[170 + stop],))
    assert again == seq[:170 + stop + 1] and eng.pos == 170 + stop
    assert eng._state_hi > eng.pos + 8       # the next burst was written
    turn = [int(t) for t in TOKS[60:73]]
    lg, _ = eng.prefill(turn)
    fed = seq[:170 + stop] + turn
    assert np.abs(lg[0] - _logits(want["np"], fed)[-1]).max() < TOL
    lg, _ = eng.decode_one(5)
    assert np.abs(lg[0] - _logits(want["np"], fed + [5])[-1]).max() < TOL


def test_a_rewind_past_what_was_folded_is_refused_by_name_and_counted(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:200]])      # chunks of 32 and a tail of 8 in 16
    assert (eng._state_lo, eng._state_hi) == (2 * A, 200)  # the ring is written to 208
    for i in range(8):
        eng.decode_one(int(TOKS[200 + i]))
    assert (eng._state_lo, eng._state_hi) == (2 * A, 208)
    assert eng.state_holds(2 * A) and not eng.state_holds(2 * A - 1)
    assert eng.state_holds(0)
    before = obs_metrics.RETENTION_REWINDS.json_value().get("refused", 0)
    eng.pos = 100
    with pytest.raises(StateRewindTooDeep, match="prefill the conversation again"):
        eng.decode_one(3)
    assert obs_metrics.RETENTION_REWINDS.json_value()["refused"] == before + 1
    assert not eng.resume_at(90) and eng.pos == 0           # counted, and reset
    assert obs_metrics.RETENTION_REWINDS.json_value()["refused"] == before + 2
    eng.prefill([int(t) for t in TOKS[:5]])                 # from 0: a new sequence
    eng.pos = 9
    with pytest.raises(StateRewindTooDeep, match="has not seen"):
        eng.decode_one(3)


def test_a_burst_is_capped_to_what_the_ring_rewinds(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert eng._max_burst(8) == 8 and eng._max_burst(64) == 16
    assert 2 * retention.max_burst() - 1 <= R


def test_prompt_lookup_decoding_rejects_drafts_over_the_state(params, want):
    """``--spec`` on the one-stream engine: a verify block writes its rows and
    the rejected tail is rewound over, across a fold."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    prompt = [int(t) for t in TOKS[:60]] * 2
    out = eng.generate_pld(prompt, len(prompt) + 24, k=5)
    greedy = _logits(want["np"], out[:-1]).argmax(-1)
    assert out[len(prompt):] == greedy[len(prompt) - 1:].tolist()


def test_ragged_batch_matches_each_row_alone(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    # one call prefills a ragged batch, so its bucket is a call's 32 rows; the
    # streams then decode past a fold, the short row's padding left out of it
    prompts = [[int(t) for t in TOKS[:29]], [int(t) for t in TOKS[30:37]]]
    outs = eng.generate_batch(prompts, 29 + 76, temperature=0.0, chunk=4)
    assert int(np.asarray(eng.cache.rw).ravel()[0]) == A
    for p, o in zip(prompts, outs):
        greedy = _logits(want["np"], o[:-1]).argmax(-1)
        assert o[len(p):] == greedy[len(p) - 1:].tolist()


# ---- the slot path -----------------------------------------------------------

def _row_tokens(r, lo, hi):
    return (TOKS if r % 2 == 0 else TOKS[::-1])[lo:hi]


@jax.jit
def _chunk_step(params, tk, cache, pos, n):
    return forward_slots(params, CFG, tk, cache, pos, n)


def _slot_state(params, hist):
    """A slot cache whose slots have consumed ``hist[b]`` tokens each, through
    ``forward_slots`` in chunks of 16 (one compiled program a batch width: the
    history is the same packed or not)."""
    b = len(hist)
    cache = init_kv_cache(CFG, b)
    pos = np.zeros((b,), np.int32)
    while (pos < hist).any():
        n = np.minimum(hist - pos, 16)
        tk = np.zeros((b, 16), np.int32)
        for r in range(b):
            tk[r, :n[r]] = _row_tokens(r, pos[r], pos[r] + n[r])
        _, cache = _chunk_step(params, jnp.asarray(tk), cache,
                               jnp.asarray(pos), jnp.asarray(n))
        pos = pos + n
    return cache


@pytest.mark.parametrize("buckets", [(), (16,)], ids=["unpacked", "packed"])
def test_one_step_with_rows_of_0_1_5_and_16_tokens(params, want, monkeypatch,
                                                   buckets):
    """A mixed step past folds: a slot that rides along (n_valid 0, its state
    kept), a decoding slot, a ragged last chunk and a whole chunk of a new
    tenant; the same packed (PR 42) and over every row."""
    monkeypatch.setattr(packing, "BUCKETS", buckets)
    hist = np.asarray([137, 200, 176, 0], np.int32)
    cache = _slot_state(params, hist)
    nv = np.asarray([0, 1, 5, 16], np.int32)
    tk = np.zeros((4, 16), np.int32)
    for r in range(4):
        tk[r, :nv[r]] = _row_tokens(r, hist[r], hist[r] + nv[r])
    assert (packing.plan(jnp.asarray(nv), 4, 16) is not None) == bool(buckets)
    lg, cache = forward_slots(params, CFG, jnp.asarray(tk), cache,
                              jnp.asarray(hist), jnp.asarray(nv))
    for r in (1, 2, 3):
        wanted = _logits(want["np"], _row_tokens(r, 0, hist[r] + nv[r]))[-1]
        assert np.abs(np.asarray(lg)[r] - wanted).max() < TOL, r
    # the slot with n_valid 0 goes on from its own state; slot 1 is taken by a
    # new tenant at position 0 over its predecessor's state, which it must not see
    nv2 = np.asarray([1, 7, 0, 0], np.int32)
    tk2 = np.zeros((4, 16), np.int32)
    tk2[0, 0] = TOKS[137]
    tk2[1, :7] = TOKS[40:47]
    pos2 = np.asarray([137, 0, 181, 16], np.int32)
    lg, _ = forward_slots(params, CFG, jnp.asarray(tk2), cache,
                          jnp.asarray(pos2), jnp.asarray(nv2))
    assert np.abs(np.asarray(lg)[0] - want["a"][137]).max() < TOL
    assert np.abs(np.asarray(lg)[1] - _logits(want["np"], TOKS[40:47])[-1]).max() < TOL


def test_verify_step_keeps_every_position_and_a_rejected_draft(params, want):
    """``forward_slots_all`` over 5 rows at a fold's edge, of which the slot
    accepts 2: the next step, 2 positions on, reads under the rejected rows."""
    hist = np.asarray([3 * A + R - 4], np.int32)
    cache = _slot_state(params, hist)
    h = int(hist[0])
    draft = np.asarray([[TOKS[h], TOKS[h + 1], 9, 9, 9]], np.int32)
    lg, cache = forward_slots_all(params, CFG, jnp.asarray(draft), cache,
                                  jnp.asarray(hist), jnp.asarray([5], np.int32))
    assert np.abs(np.asarray(lg)[0, :2] - want["a"][h:h + 2]).max() < TOL
    lg, _ = forward_slots(params, CFG, jnp.asarray([[TOKS[h + 2]]], np.int32),
                          cache, jnp.asarray([h + 2], np.int32),
                          jnp.asarray([1], np.int32))
    assert np.abs(np.asarray(lg)[0] - want["a"][h + 2]).max() < TOL


def test_the_scheduler_serves_the_reference_token_for_token(params, want):
    """Five requests on two slots with no pages, their lengths apart so that
    the slots are out of step and fold in different steps, each slot taken over
    by a new request with the last tenant's state left in place: every stream
    is the reference's greedy stream."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    assert not eng.paged and eng.slot_state == "retention layers' state"
    sched = SlotScheduler(eng, prefill_chunk=16, prefix_reuse=True, preempt=True)
    assert sched.prefix_cache is None and sched.pool is None
    try:
        prompts = [[int(t) for t in TOKS[a:a + n]]
                   for a, n in ((0, 150), (10, 37), (3, 217), (50, 133), (7, 16))]
        tickets = [sched.submit(p, max_new=14 + 9 * i)
                   for i, p in enumerate(prompts)]
        for p, t in zip(prompts, tickets):
            out = list(t.tokens())
            greedy = _logits(want["np"], p + out[:-1]).argmax(-1)
            assert out == greedy[len(p) - 1:].tolist()
    finally:
        sched.close()


class _HalfRightProposer(Proposer):
    """Drafts of which the first two are the stream's own next tokens and the
    rest are wrong: every verify step accepts some rows and rejects others."""
    name = "half-right"

    def __init__(self, stream):
        super().__init__(CFG.vocab_size)
        self.stream = stream

    def sync(self, slot, rid, prompt, emitted):
        self._states[slot] = len(emitted)

    def propose(self, want):
        props = {}
        for slot, k in want.items():
            done = self._states.get(slot)
            if done is None or k < 1:
                continue
            nxt = self.stream[done:done + k]
            props[slot] = [int(t) if j < 2 else int((t + 1) % self.vocab)
                           for j, t in enumerate(nxt)]
        return props


def test_the_scheduler_verifies_drafts_over_the_state(params, want):
    """``--spec`` on the slot path: rejected drafts leave rows above the slot's
    clock, which the ring holds harmlessly, across a fold."""
    p = [int(t) for t in TOKS[:118]]
    greedy = list(p)
    for _ in range(24):
        greedy.append(int(_logits(want["np"], greedy)[-1].argmax()))
    greedy = greedy[len(p):]
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    sched = SlotScheduler(eng, prefill_chunk=16, spec_k=4,
                          spec=_HalfRightProposer(greedy))
    try:
        ticket = sched.submit(p, max_new=24)
        assert list(ticket.tokens()) == greedy
        assert ticket.spec_proposed > ticket.spec_accepted > 0
    finally:
        sched.close()


# ---- the loader, the ledger, the refusals ---------------------------------------

def test_loader_packed_agrees_with_the_reference(tmp_path, want):
    """A Q40 file through the normal loader (``wqkv`` and ``w13`` joined, the
    gate float32): prefill and decode against the reference of the dequantized
    weights.  The packed path rounds each matmul's activation to bfloat16: it
    reads a few hundredths of the logits' spread; a stack read in the wrong
    order or a wrong split reads 1 and more."""
    path = str(tmp_path / "q.m")
    _write_model(path, want["np"], ftype=quants.Q40)
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf, dtype=jnp.float32, keep_quantized=True)
        _, dense = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    assert "wqkv" in p and "w13" in p and p["wg"].dtype == np.float32
    deq = {k: np.asarray(v, np.float32) for k, v in dense.items()}
    wanted = ref.np_forward_brumby(deq, cfg, TOKS[:150])
    eng = Engine(cfg.with_(quant_impl="xla"), p, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:149]])
    assert np.abs(lg[0] - wanted[148]).max() < 0.1 * wanted[148].std()
    lg, _ = eng.decode_one(int(TOKS[149]))
    assert np.abs(lg[0] - wanted[149]).max() < 0.1 * wanted[149].std()


def test_the_gauges_and_the_ledger_name_the_state(params):
    obs_dispatch.reset()
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    by_kind = obs_metrics.KV_CACHE_BYTES.json_value()
    planes = eng.cache.planes()
    assert by_kind["retention"] == sum(int(a.nbytes) for a in planes.values())
    assert by_kind["full"] == 0 and eng.kv_bytes_per_token == 0
    assert obs_metrics.KV_BYTES_PER_TOKEN.json_value() == 0
    assert set(eng.cache.pool_planes()) == {"k", "v"}
    eng.generate_batch([[5, 6, 7], [8, 9]], 3 + 4, temperature=0.0, chunk=2)
    sites = obs_dispatch.dispatches()
    assert {"retention/state-read", "retention/block", "retention/fold"} <= set(sites)
    obs_dispatch.reset()


def test_scopes_name_the_operator_inside_the_stages_it_passes(params):
    text = jax.jit(lambda c: forward_slots(
        params, CFG, jnp.zeros((1, 4), jnp.int32), c, jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 4, jnp.int32))).lower(init_kv_cache(CFG, 1)).as_text(
            debug_info=True)
    for name in ("qkv/retention", "qkv/qk_norm", "kv_write/fold", "kv_write/recent",
                 "attn/state", "attn/recent", "w1", "page_idx"):
        assert name in text, name
    assert "attn/full" not in text and "attn/conv" not in text


# ---- the converter ---------------------------------------------------------------

BRUMBY_HF = dict(
    model_type="brumby", hidden_size=160, intermediate_size=224,
    num_hidden_layers=4, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, vocab_size=128, max_position_embeddings=512, hidden_act="silu",
    rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
    attention_bias=False, use_sliding_window=False, sliding_window=None,
    max_window_layers=4, tie_word_embeddings=False)


def _hf_checkpoint(p, cfg):
    """A toy checkpoint under the names the converter ASSUMES (Qwen3's, with
    ``self_attn.g_proj``): unverified until the published files are here."""
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.norm.weight": p["rms_final"], "lm_head.weight": p["wcls"].T}
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        for ours, theirs in (("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"),
                             ("wv", "self_attn.v_proj"), ("wo", "self_attn.o_proj"),
                             ("wg", "self_attn.g_proj"), ("w1", "mlp.gate_proj"),
                             ("w2", "mlp.down_proj"), ("w3", "mlp.up_proj")):
            hf[f"{base}{theirs}.weight"] = p[ours][i].T
        for ours, theirs in (("q_norm", "self_attn.q_norm"),
                             ("k_norm", "self_attn.k_norm"),
                             ("rms_att", "input_layernorm"),
                             ("rms_ffn", "post_attention_layernorm")):
            hf[f"{base}{theirs}.weight"] = p[ours][i]
    return {k: np.ascontiguousarray(v, np.float32) for k, v in hf.items()}


def test_convert_round_trip_and_the_logits(tmp_path, want):
    from safetensors.numpy import save_file

    import convert_hf

    p = want["np"]
    (tmp_path / "config.json").write_text(json.dumps(BRUMBY_HF))
    save_file(_hf_checkpoint(p, CFG), str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "brumby.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    mf = mfile.MFile(out)
    assert (mf.spec.arch, mf.spec.retention_degree) == (mfile.ARCH_BRUMBY, 2)
    got_cfg, params = load_params(mf)
    assert got_cfg.with_(dtype=jnp.float32, norm_eps=1e-6) == CFG
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32), p[k], err_msg=k)
    eng = Engine(got_cfg.with_(dtype=jnp.float32), params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:70]])
    assert np.abs(lg[0] - want["a"][69]).max() < TOL


@pytest.mark.parametrize("key,value,says", [
    ("attention_bias", True, "attention_bias is true"),
    ("use_sliding_window", True, "use_sliding_window is true"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling is"),
    ("tie_word_embeddings", True, "tie_word_embeddings is true"),
    ("head_dim", 32, "is not hidden_size"),
])
def test_convert_refuses_what_the_file_cannot_carry(tmp_path, key, value, says):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(BRUMBY_HF, **{key: value})))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)
