"""LFM2 (``ARCH_LFM2_MOE``) at toy widths against a plain float32 reference
(``reference_impl.np_forward_lfm2_moe``: no cache, no state, the convolution a
sum of shifted copies of ``z`` over the whole sequence): the format, the
operator and its ring of positions, the contiguous engine (prefill, chunked
prefill, decode bursts, every rewind inside the ring and the refusal past it, a
second chat turn), the slot path (ragged ``n_valid``, packed and unpacked, two
slots out of step, a slot taken over with its ring left dirty, a verify step),
every ``moe_ffn`` strategy, snapshots, each refusal by name, the loader and the
converter.
"""

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
from dllama_tpu.models.config import ModelConfig, tiny_lfm2_moe
from dllama_tpu.models.params import (init_params, load_params, param_shapes,
                                      quantize_matmuls)
from dllama_tpu.models.transformer import (forward, forward_last, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool, moe_ffn)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import conv, q40
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine, StateRewindTooDeep
from dllama_tpu.runtime.scheduler import SlotScheduler
from dllama_tpu.runtime.spec import Proposer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "converter"))

CFG = tiny_lfm2_moe()
TOKS = np.random.RandomState(0).randint(3, 128, (100,)).astype(np.int32)
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums, a few 1e-6 of logits whose spread is about 0.5.
# bfloat16 activations in place of float32 move a logit by 1e-2 and more, and
# each wrong computation of ``test_each_wrong_computation_is_seen`` by 1e-2 or
# more at some position.
TOL = 5e-5
# the float32 product of 16 rows over the 16 experts: a prefill chunk of 16
SMALL_PRODUCT = 4 * 16 * 64 * 16
R = conv.RING


def _init(cfg, seed=5):
    """Random params whose choice bias is small and not 0."""
    p = init_params(cfg, seed=seed, scale=0.08)
    bias = np.random.RandomState(seed + 1).standard_normal(p["router_bias"].shape)
    return dict(p, router_bias=jnp.asarray(0.05 * bias, jnp.float32))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_lfm2_moe(p, CFG, TOKS), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_chunk(monkeypatch):
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    assert CFG.prefill_chunk() == 16


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _logits(p, toks, cfg=CFG):
    """The reference's (T, V) logits for any token list."""
    return ref.np_forward_lfm2_moe(p, cfg, np.asarray(toks, np.int32))


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=cfg.n_experts, n_active_experts=cfg.n_active_experts,
        vocab_size=cfg.vocab_size, seq_len=cfg.seq_len,
        hidden_act=cfg.hidden_act, rope_theta=cfg.rope_theta,
        weights_ftype=ftype,
        **{name: getattr(cfg, name) for k, name, _ in mfile.ALL_EXT_KEYS
           if k in mfile.ARCH_EXT_KEYS[mfile.ARCH_LFM2_MOE]})
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, p, cfg=CFG, ftype=quants.F32):
    """The runtime-layout ``p`` as a ``.m`` file: each plan tensor is its
    stack's slice (by kind, by segment or by layer), transposed back to the
    file's (d_out, n_in); the taps flat, channel by channel."""
    names = {"moe_router": "router", "moe_router_bias": "router_bias"}
    kind = {}  # layer -> index within its kind
    n_att = n_conv = 0
    for i in range(cfg.n_layers):
        if i % cfg.window_period == cfg.window_full_at:
            kind[i], n_att = n_att, n_att + 1
        else:
            kind[i], n_conv = n_conv, n_conv + 1
    with mfile.MFileWriter(path, _spec(cfg, ftype=ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = p[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            else:
                leaf, li = parts[-1], int(parts[1])
                stack = p[names.get(leaf, leaf)]
                if leaf in ("rms_att", "rms_ffn", "w1", "w2", "w3"):
                    x = stack[li]
                elif leaf in ("router", "moe_router", "moe_router_bias", "up",
                              "gate", "down"):
                    x = stack[li - cfg.n_dense_layers]
                else:
                    x = stack[kind[li]]
                if parts[2] == "experts":
                    x = x[int(parts[3])]
                x = x.reshape(-1) if leaf == "conv_taps" else (
                    x.T if x.ndim == 2 else x)
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ------------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    assert mfile.ARCH_LFM2_MOE == 0xABCD07
    assert mfile.ARCH_NAMES[mfile.ARCH_LFM2_MOE] == "lfm2_moe"
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_LFM2_MOE] == (19, 23, 24, 31, 32, 34, 37, 38)
    assert mfile.KEY_MAX >= 38
    path = tmp_path / "toy.m"
    _write_model(path, want["np"])
    mf = mfile.MFile(str(path))
    s = mf.spec
    assert (s.conv_taps, s.window_period, s.window_full_at, s.window, s.head_dim,
            s.n_dense_layers, s.moe_hidden_dim) == (3, 4, 2, 0, 8, 2, 32)
    names = [t.name for t in mf.plan]
    assert "layers.0.conv_in" in names and "layers.0.wq" not in names
    assert "layers.2.wq" in names and "layers.2.conv_in" not in names
    assert "layers.1.w1" in names and "layers.2.moe_router_bias" in names
    assert not any("shared" in n for n in names)
    assert mf.info("layers.0.conv_taps").shape == (64 * 3,)
    cfg, p = load_params(mf, dtype=jnp.float32)
    assert cfg.with_(norm_eps=CFG.norm_eps) == CFG
    assert {k: tuple(v.shape) for k, v in p.items()} == param_shapes(CFG)
    for k, v in p.items():
        np.testing.assert_array_equal(np.asarray(v), want["np"][k], err_msg=k)


@pytest.mark.parametrize("kw,says", [
    (dict(conv_taps=0), "conv_taps"),
    (dict(conv_taps=1), "conv_taps"),
    (dict(window=16), "no sliding window"),
    (dict(window_period=3), "whole periods"),
    (dict(window_full_at=4), "attention layer's place"),
    (dict(head_dim=0), "head size"),
    (dict(n_dense_layers=8), "dense layers lead"),
    (dict(moe_hidden_dim=0), "experts' width"),
    (dict(n_shared_experts=1), "no shared expert"),
    (dict(n_active_experts=0), "experts and a top-k"),
    (dict(arch=mfile.ARCH_EXAONE_MOE, window=16, n_groups=1, topk_groups=1),
     "key 38 describes an lfm2_moe file"),
])
def test_header_rules_are_refused_by_name(tmp_path, kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), "x.m")


def test_published_widths_give_the_issues_chunk_and_bytes():
    cfg = ModelConfig(arch=mfile.ARCH_LFM2_MOE, dim=2048, hidden_dim=11776,
                      n_layers=32, n_heads=32, n_kv_heads=8, n_experts=64,
                      n_active_experts=4, vocab_size=65536, seq_len=128000,
                      hidden_act=mfile.ACT_SILU, rope_theta=1e6, head_dim=64,
                      window_period=4, window_full_at=2, conv_taps=3,
                      moe_hidden_dim=1536, n_dense_layers=2,
                      dtype=jnp.bfloat16)
    assert cfg.prefill_chunk() == 1024
    assert (cfg.n_full_layers, cfg.n_conv_layers, cfg.n_window_layers) == (8, 24, 0)
    assert cfg.qk_head_norm and cfg.router_sigmoid and cfg.full_rotates
    assert cfg.router_norm_eps == 1e-6 and cfg.norm_topk_prob
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 1, 32768))
    assert cache.k.shape == (8, 1, 8, 32768, 64) and cache.wk is None
    assert cache.cz.shape == (24, 1, 1, R, 2048)
    per_token = 2 * cache.k.shape[0] * 8 * 64 * 2
    assert per_token == 16384
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 2056, 16, slots=16,
                                               max_pages=128))
    assert pool.k.shape == (8, 2056, 16, 4, 128)   # heads of 64 two to a row
    assert pool.cz.shape == (24, 16, 1, R, 2048)
    assert set(pool.pool_planes()) == {"k", "v"}
    # the deepest rewind the one-stream engine makes fits the ring
    assert conv.max_burst(R, 3) == 31 and 2 * 16 - 1 <= R - 2


# ---- the operator alone ---------------------------------------------------------

def _np_conv(z, c, w):
    """y over a whole sequence: z, c (T, D), w (D, K)."""
    t, k = len(z), w.shape[1]
    ext = np.concatenate([np.zeros((k - 1, z.shape[1]), np.float32), z])
    return c * sum(ext[j:j + t] * w[:, j] for j in range(k))


@pytest.mark.parametrize("t", [1, 2, 3, 17])
@pytest.mark.parametrize("start", [0, 1, R + 7])
def test_the_operator_from_any_position(t, start):
    """The state's read, the taps and the write at T rows from position
    ``start`` (0; 1; past a wrapped ring), fed in calls of 5 rows before it,
    against the convolution over the whole sequence."""
    rs = np.random.RandomState(t + start)
    n, d = start + t, 16
    z = rs.standard_normal((2, n, d)).astype(np.float32)
    c = rs.standard_normal((2, n, d)).astype(np.float32)
    w = rs.standard_normal((d, 3)).astype(np.float32)
    cz = jnp.zeros((3, 2, 1, R, d), jnp.float32)
    layer = jnp.int32(1)

    def call(cz, lo, hi):
        pos = jnp.full((2,), lo, jnp.int32)
        carried = conv.state_read(cz, layer, pos, 3)
        y = conv.taps_and_gate(jnp.asarray(z[:, lo:hi]), carried,
                               jnp.asarray(c[:, lo:hi]), jnp.asarray(w), pos)
        return conv.state_write(cz, jnp.asarray(z[:, lo:hi]), layer, pos, 3), y

    for lo in range(0, start, 5):
        cz, _ = call(cz, lo, min(lo + 5, start))
    cz, y = call(cz, start, n)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(y)[b], _np_conv(z[b], c[b], w)[start:],
                                   atol=1e-5)
    assert not np.asarray(cz[0]).any() and not np.asarray(cz[2]).any()


def test_the_operator_left_padded():
    """A ragged batch: row 1's first 3 positions are padding whose ``z`` must
    not enter its sequence, in the call and in the state."""
    rs = np.random.RandomState(3)
    d, n = 8, 9
    z = rs.standard_normal((2, n, d)).astype(np.float32)
    c = rs.standard_normal((2, n, d)).astype(np.float32)
    w = rs.standard_normal((d, 3)).astype(np.float32)
    floor = jnp.asarray([0, 3], jnp.int32)
    cz = jnp.zeros((1, 2, 1, R, d), jnp.float32)
    layer, out = jnp.int32(0), []
    for lo, hi in ((0, 4), (4, 5), (5, 9)):
        pos = jnp.full((2,), lo, jnp.int32)
        carried = conv.state_read(cz, layer, pos, 3, floor=floor)
        out.append(conv.taps_and_gate(jnp.asarray(z[:, lo:hi]), carried,
                                      jnp.asarray(c[:, lo:hi]), jnp.asarray(w),
                                      pos, floor=floor))
        cz = conv.state_write(cz, jnp.asarray(z[:, lo:hi]), layer, pos, 3)
    y = np.concatenate([np.asarray(o) for o in out], 1)
    np.testing.assert_allclose(y[0], _np_conv(z[0], c[0], w), atol=1e-5)
    np.testing.assert_allclose(y[1, 3:], _np_conv(z[1, 3:], c[1, 3:], w), atol=1e-5)


def test_a_call_wider_than_the_ring_keeps_the_rows_before_its_last_real_one():
    """A bucketed prefill: 100 rows of which 70 (or 3) are real; the state must
    end at the last real row, whatever the padding holds."""
    rs = np.random.RandomState(5)
    d = 8
    for n_real in (70, 3, 100):
        z = rs.standard_normal((1, 100, d)).astype(np.float32)
        cz = jnp.zeros((1, 1, 1, R, d), jnp.float32)
        pos = jnp.asarray([5], jnp.int32)
        cz = conv.state_write(cz, jnp.asarray(z), jnp.int32(0), pos, 3,
                              jnp.int32(n_real))
        got = conv.state_read(cz, jnp.int32(0), pos + n_real, 3)
        lo = max(n_real - 2, 0)
        np.testing.assert_array_equal(np.asarray(got)[0, -(n_real - lo):],
                                      z[0, lo:n_real])


@pytest.mark.parametrize("rows,n_real", [(1, 1), (16, 5), (62, 62), (63, 63),
                                         (100, 70), (100, 3), (100, 100),
                                         (256, 61), (256, 200)])
def test_the_hosts_account_and_the_devices_write_read_one_rule(rows, n_real):
    """``conv.written`` is the one statement of which rows a call leaves in the
    ring: as an int it is the engine's account, as an array the device's write,
    and the ring then holds exactly the positions the rule names."""
    first, count = conv.written(n_real, rows, R, 3)
    dev_first, dev_count = conv.written(jnp.int32(n_real), rows, R, 3)
    assert (int(dev_first), dev_count) == (first, count)
    assert count == min(rows, R - 2) and 0 <= first <= rows - count
    # a wide call's rows end at the last real one (or start at the call's first)
    assert first == 0 or first + count == n_real
    pos, d = 7, 4
    z = np.arange(1, rows + 1, dtype=np.float32)[None, :, None] * np.ones((1, 1, d), np.float32)
    cz = conv.state_write(jnp.zeros((1, 1, 1, R, d), jnp.float32), jnp.asarray(z),
                          jnp.int32(0), jnp.asarray([pos], jnp.int32), 3,
                          jnp.int32(n_real))
    held = np.asarray(cz)[0, 0, 0, :, 0]
    for i in range(first, first + count):   # row i of the call is position pos + i
        assert held[(pos + i) % R] == i + 1
    assert np.count_nonzero(held) == count


# ---- the contiguous path ------------------------------------------------------

def test_one_pass_prefill_is_the_reference(params, want):
    lg, _ = forward(params, CFG, jnp.asarray(TOKS)[None], init_kv_cache(CFG, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - want["a"]).max() < TOL


@pytest.mark.parametrize("wrong", [
    "split_order", "gate_after", "taps_reversed", "no_rope", "no_head_norm",
    "attention_first", "bias_in_weights", "softmax_router"])
def test_each_wrong_computation_is_seen(want, wrong):
    bad = ref.np_forward_lfm2_moe(want["np"], CFG, TOKS, wrong=wrong)
    assert np.abs(bad - want["a"]).max() > 100 * TOL, wrong


def test_bfloat16_would_not_pass(params, want):
    """The tolerance is tight enough that bfloat16 activations fail it."""
    cfg = CFG.with_(dtype=jnp.bfloat16)
    p = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32
         and not k.startswith("rms") and not k.endswith("_norm")
         and k not in ("router_bias", "conv_taps") else v
         for k, v in params.items()}
    lg, _ = forward(p, cfg, jnp.asarray(TOKS)[None], init_kv_cache(cfg, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - want["a"]).max() > 20 * TOL


@pytest.mark.parametrize("n", [5, 17, 33])
def test_prefill_then_decode_through_the_contiguous_cache(params, want, n):
    """A prompt that is no bucket's size through ``Engine.prefill`` (padded to
    its bucket: the state must stop at the prompt's end), then 45 tokens one by
    one: every position's logits are the reference's."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:n]])
    assert np.abs(lg[0] - want["a"][n - 1]).max() < TOL
    for i in range(n, n + 45):
        lg, _ = eng.decode_one(int(TOKS[i]))
        assert np.abs(lg[0] - want["a"][i]).max() < TOL, i
    assert eng.pos == n + 45


def test_chunked_prefill_equals_one_pass(params, want, small_chunk):
    """Chunks of 16 carry the state over edges at 16, 32, 48: every residue of
    3; the bucketed tail of 2 real rows in 16 stops at the prompt's end."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    before = obs_metrics.ENGINE_PREFILL_CHUNKS.json_value()
    lg, _ = eng.prefill([int(t) for t in TOKS[:50]])
    assert obs_metrics.ENGINE_PREFILL_CHUNKS.json_value() - before == 4
    assert np.abs(lg[0] - want["a"][49]).max() < TOL
    lg, _ = eng.decode_one(int(TOKS[50]))
    assert np.abs(lg[0] - want["a"][50]).max() < TOL


def test_a_prefill_wider_than_the_ring_in_one_call(params, want):
    """One call of 128 rows (the bucket of 90) over a ring of 64."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:90]])
    assert np.abs(lg[0] - want["a"][89]).max() < TOL
    lg, _ = eng.decode_one(int(TOKS[90]))
    assert np.abs(lg[0] - want["a"][90]).max() < TOL
    assert (eng._state_lo, eng._state_hi) == (28, 91)


def _burst(eng, p, n_prompt, burst, steps):
    """``steps`` greedy tokens after a prompt, in decode bursts of ``burst``."""
    out = [t for t, _ in eng.generate_stream([int(t) for t in TOKS[:n_prompt]],
                                             n_prompt + steps, temperature=0.0,
                                             chunk=burst)]
    return out


@pytest.mark.parametrize("j", range(1, 8))
def test_a_rewind_inside_a_burst_resumes_as_a_fresh_forward(params, want, j):
    """After bursts of 8, ``pos`` set back by each j in 1..7 and decoding
    resumed with another token: the logits are the fresh forward's of the kept
    tokens and the new one."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    seq = _burst(eng, want["np"], 17, 8, 1 + 16)
    assert eng.pos == 17 + 16 and len(seq) == 17 + 17
    before = obs_metrics.CONV_STATE_REWINDS.json_value().get("in_ring", 0)
    eng.pos -= j
    kept = seq[:eng.pos]
    lg, _ = eng.decode_one(77)
    wanted = _logits(want["np"], kept + [77])[-1]
    assert np.abs(lg[0] - wanted).max() < TOL
    assert obs_metrics.CONV_STATE_REWINDS.json_value()["in_ring"] == before + 1
    # and the greedy stream so far is the reference's own
    greedy = _logits(want["np"], seq[:-1]).argmax(-1)
    assert seq[17:] == greedy[16:].tolist()


def test_a_second_turn_after_an_end_of_sequence_token_inside_a_burst(params, want):
    """The engine's own rewind: the token a burst yields third is the
    end-of-sequence id, the next burst is already written; the second turn's
    prefill (no bucket's size) and its decode are the reference's."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    seq = _burst(eng, want["np"], 21, 8, 30)
    eng.reset()
    stop = 1 + 8 + 2                       # third token of the second burst
    while seq[21 + stop] in seq[21:21 + stop]:
        stop += 1
    again = [t for t, _ in eng.generate_stream(
        [int(t) for t in TOKS[:21]], 21 + 30, temperature=0.0, chunk=8,
        eos_ids=(seq[21 + stop],))]
    assert again == seq[:21 + stop + 1] and eng.pos == 21 + stop
    assert eng._state_hi > eng.pos + 8     # the speculative burst was written
    turn = [int(t) for t in TOKS[60:73]]
    lg, _ = eng.prefill(turn)
    fed = seq[:21 + stop] + turn
    assert np.abs(lg[0] - _logits(want["np"], fed)[-1]).max() < TOL
    lg, _ = eng.decode_one(5)
    assert np.abs(lg[0] - _logits(want["np"], fed + [5])[-1]).max() < TOL


def test_a_rewind_past_the_ring_is_refused_by_name(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng.prefill([int(t) for t in TOKS[:90]])
    for i in range(8):
        eng.decode_one(int(TOKS[90 + i]))
    assert eng.state_holds(98 - 62) and not eng.state_holds(98 - 63)
    assert eng.state_holds(0)
    before = obs_metrics.CONV_STATE_REWINDS.json_value().get("reprefill", 0)
    eng.pos = 20
    with pytest.raises(StateRewindTooDeep, match="prefill the conversation again"):
        eng.decode_one(3)
    assert obs_metrics.CONV_STATE_REWINDS.json_value()["reprefill"] == before + 1
    eng.pos = 99   # positions the state has not seen
    with pytest.raises(StateRewindTooDeep, match="has not seen"):
        eng.decode_one(3)
    eng.reset()    # from 0 everything is masked: fine
    eng.prefill([int(t) for t in TOKS[:5]])


def test_a_chat_resumed_past_the_ring_prefills_the_conversation_again(params, tmp_path):
    """``server/api.py``: the conversation cache resumes a chat at the cached
    turn's end while the state's ring still covers it (``Engine.resume_at``),
    and prefills the whole conversation again where it does not, counted as a
    ``reprefill``; the reply is the same either way."""
    from fixtures import write_tiny_tokenizer
    from dllama_tpu.server.api import ApiState, ChatMessage, InferenceParams
    from dllama_tpu.tokenizer.bpe import Tokenizer
    tok = Tokenizer(write_tiny_tokenizer(str(tmp_path / "tok.t")))

    def turns(state):
        msgs, out = [ChatMessage("user", "2+2?")], []
        for nxt in ("and 3?", None):
            reply, _, n, _ = state.complete(
                InferenceParams(messages=list(msgs), temperature=0.0, max_tokens=6),
                lambda _t: None)
            out.append((reply, n))
            msgs += [ChatMessage("assistant", reply), ChatMessage("user", nxt)]
        return out

    count = lambda k: obs_metrics.CONV_STATE_REWINDS.json_value().get(k, 0)  # noqa: E731
    cfg = tiny_lfm2_moe(seq_len=512)   # room for the chat template's tokens
    eng = Engine(cfg, params, mesh=_mesh(), batch=1)
    state = ApiState(eng, tok, default_temperature=0.0, chunk=4)
    resumed = turns(state)
    assert len(state.naive_cache.items) == 4
    # the same two turns with the state's account emptied between them
    eng2 = Engine(cfg, params, mesh=_mesh(), batch=1)
    state2 = ApiState(eng2, tok, default_temperature=0.0, chunk=4)
    before = count("reprefill")
    msgs = [ChatMessage("user", "2+2?")]
    first = state2.complete(InferenceParams(messages=list(msgs), temperature=0.0,
                                            max_tokens=6), lambda _t: None)
    eng2._state_lo = eng2._state_hi = eng2._state_hi + 100   # the ring moved on
    msgs += [ChatMessage("assistant", first[0]), ChatMessage("user", "and 3?")]
    second = state2.complete(InferenceParams(messages=list(msgs), temperature=0.0,
                                             max_tokens=6), lambda _t: None)
    assert count("reprefill") == before + 1
    assert [(first[0], first[2]), (second[0], second[2])] == resumed


def test_a_burst_is_capped_to_what_the_ring_rewinds(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert eng._max_burst(16) == 16 and eng._max_burst(64) == 31
    from dllama_tpu.models.config import tiny_config
    plain = Engine(tiny_config(), init_params(tiny_config()), mesh=_mesh())
    assert plain._max_burst(64) == 64 and plain.state_holds(7)


def test_ragged_batch_matches_each_row_alone(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    prompts = [[int(t) for t in TOKS[:19]], [int(t) for t in TOKS[30:37]]]
    outs = eng.generate_batch(prompts, 19 + 12, temperature=0.0, chunk=4)
    for p, o in zip(prompts, outs):
        greedy = _logits(want["np"], o[:-1]).argmax(-1)
        assert o[len(p):] == greedy[len(p) - 1:].tolist()


def test_prompt_lookup_decoding_rejects_drafts_over_the_state(params, want):
    """``--spec`` on the one-stream engine: a verify block writes its rows and
    the rejected tail is rewound over: the ring gives it."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    prompt = [int(t) for t in TOKS[:12]] * 2
    out = eng.generate_pld(prompt, len(prompt) + 20, k=5)
    greedy = _logits(want["np"], out[:-1]).argmax(-1)
    assert out[len(prompt):] == greedy[len(prompt) - 1:].tolist()


# ---- the slot path -----------------------------------------------------------

def _slot_state(params, hist):
    """A pool whose slots have consumed ``hist[b]`` tokens of TOKS each, through
    ``forward_slots`` in chunks of 16."""
    b = len(hist)
    cache = init_kv_pool(CFG, 40, 4, slots=b, max_pages=8)
    table = jnp.asarray(1 + np.arange(b * 8, dtype=np.int32).reshape(b, 8))
    pos = np.zeros((b,), np.int32)
    while (pos < hist).any():
        n = np.minimum(hist - pos, 16)
        tk = np.zeros((b, 16), np.int32)
        for r in range(b):
            tk[r, :n[r]] = TOKS[pos[r]:pos[r] + n[r]] if r % 2 == 0 \
                else TOKS[::-1][pos[r]:pos[r] + n[r]]
        _, cache = forward_slots(params, CFG, jnp.asarray(tk), cache,
                                 jnp.asarray(pos), jnp.asarray(n), table)
        pos = pos + n
    return cache, table


def _row_tokens(r, lo, hi):
    return (TOKS if r % 2 == 0 else TOKS[::-1])[lo:hi]


@pytest.mark.parametrize("buckets", [(), (16,)], ids=["unpacked", "packed"])
def test_one_step_with_rows_of_0_1_5_and_16_tokens(params, want, monkeypatch,
                                                   buckets):
    """A mixed step: a slot that rides along (n_valid 0, and its state kept), a
    decoding slot, a ragged last chunk and a whole chunk; the same packed (PR
    42) and over every row."""
    monkeypatch.setattr(packing, "BUCKETS", buckets)
    hist = np.asarray([9, 20, 16, 0], np.int32)
    cache, table = _slot_state(params, hist)
    nv = np.asarray([0, 1, 5, 16], np.int32)
    tk = np.zeros((4, 16), np.int32)
    for r in range(4):
        tk[r, :nv[r]] = _row_tokens(r, hist[r], hist[r] + nv[r])
    assert (packing.plan(jnp.asarray(nv), 4, 16) is not None) == bool(buckets)
    lg, cache = forward_slots(params, CFG, jnp.asarray(tk), cache,
                              jnp.asarray(hist), jnp.asarray(nv), table)
    for r in (1, 2, 3):
        wanted = _logits(want["np"], _row_tokens(r, 0, hist[r] + nv[r]))[-1]
        assert np.abs(np.asarray(lg)[r] - wanted).max() < TOL, r
    # the slot with n_valid 0 goes on from its own state
    nv2 = np.asarray([1, 0, 0, 0], np.int32)
    tk2 = np.zeros((4, 1), np.int32)
    tk2[0, 0] = TOKS[9]
    lg, _ = forward_slots(params, CFG, jnp.asarray(tk2), cache,
                          jnp.asarray(hist + nv), jnp.asarray(nv2), table)
    assert np.abs(np.asarray(lg)[0] - want["a"][9]).max() < TOL


def test_verify_step_keeps_every_position_and_a_rejected_draft(params, want):
    """``forward_slots_all`` over 5 rows, of which the slot accepts 2: the next
    step, 2 positions on, reads the state under the rejected rows."""
    hist = np.asarray([11], np.int32)
    cache, table = _slot_state(params, hist)
    draft = np.asarray([[TOKS[11], TOKS[12], 9, 9, 9]], np.int32)
    lg, cache = forward_slots_all(params, CFG, jnp.asarray(draft), cache,
                                  jnp.asarray(hist), jnp.asarray([5], np.int32),
                                  table)
    assert np.abs(np.asarray(lg)[0, :2] - want["a"][11:13]).max() < TOL
    lg, _ = forward_slots(params, CFG, jnp.asarray([[TOKS[13]]], np.int32), cache,
                          jnp.asarray([13], np.int32), jnp.asarray([1], np.int32),
                          table)
    assert np.abs(np.asarray(lg)[0] - want["a"][13]).max() < TOL


def test_a_pool_of_narrow_heads_is_lane_dense_and_reads_the_same():
    """Heads of 64 are stored two to a row of 128 lanes (``pool_rows``: the
    TPU's compact layout of a 64-wide minor axis puts the pages minor-most and
    every step then copies the pool whole): the same bytes in the same order,
    and every read of the pool takes either form."""
    from dllama_tpu.ops import attention as att
    assert att.pool_rows(8, 64) == (4, 128) and att.pool_rows(8, 128) == (8, 128)
    assert att.pool_rows(2, 8) == (2, 8) and att.pool_rows(3, 64) == (3, 64)
    assert att.pool_rows(16, 32) == (4, 128) and att.pool_rows(8, 96) == (8, 96)
    rng = np.random.RandomState(3)
    L, P, ps, hkv, dh, b, hq = 2, 24, 4, 4, 64, 3, 8
    plain = jnp.asarray(rng.standard_normal((2, L, P, ps, hkv, dh)), jnp.float32)
    dense = plain.reshape(2, L, P, ps, *att.pool_rows(hkv, dh))
    table = jnp.asarray(1 + rng.permutation(21).reshape(b, 7), jnp.int32)
    pos = jnp.asarray([0, 9, 22], jnp.int32)
    for t in (1, 5):
        kn, vn = (jnp.asarray(rng.standard_normal((b, hkv, t, dh)), jnp.float32)
                  for _ in range(2))
        q = jnp.asarray(rng.standard_normal((b, hq, t, dh)), jnp.float32)
        pidx, oidx = att.paged_write_indices(table, pos, jnp.full((b,), t), t, ps)
        pk, pv = att.paged_update_kv_rows(*plain, kn, vn, jnp.int32(1), pidx, oidx)
        dk, dv = att.paged_update_kv_rows(*dense, kn, vn, jnp.int32(1), pidx, oidx)
        assert dk.shape == (L, P, ps, 2, 128)
        np.testing.assert_array_equal(np.asarray(dk).reshape(pk.shape), np.asarray(pk))
        np.testing.assert_array_equal(np.asarray(dv).reshape(pv.shape), np.asarray(pv))
        want = att.paged_gqa_attention_at(q, pk, pv, jnp.int32(1), table, pos)
        got = att.paged_gqa_attention_at(q, dk, dv, jnp.int32(1), table, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if t == 1:  # the long-cache walk over live pages
            walk = att.paged_decode_attention(q, dk, dv, jnp.int32(1), table, pos)
            assert np.abs(np.asarray(walk) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("read", ["off", "interp"], ids=["gather", "fused-walk"])
def test_the_slot_path_at_heads_of_64_is_the_reference(monkeypatch, read):
    """The served geometry's head size at toy widths: the pool's rows hold two
    heads each; chunks of 16 with a ragged last one, then decoding, through
    the gather form and through the fused page walk over the folded rows (the
    kernel interpreted: what the chip runs since PR 48)."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", read)
    obs_dispatch.reset()
    cfg = tiny_lfm2_moe(head_dim=64)
    p = _init(cfg, seed=7)
    wanted = ref.np_forward_lfm2_moe({k: np.asarray(v) for k, v in p.items()},
                                     cfg, TOKS[:50])
    cache = init_kv_pool(cfg, 40, 4, slots=1, max_pages=16)
    assert cache.k.shape == (2, 40, 4, 1, 128)
    table = jnp.asarray(1 + np.arange(16, dtype=np.int32)[None])
    pos = 0
    for t, n in [(16, 16), (16, 16), (16, 9)] + [(1, 1)] * 9:
        tk = np.zeros((1, t), np.int32)
        tk[0, :n] = TOKS[pos:pos + n]
        lg, cache = forward_slots(p, cfg, jnp.asarray(tk), cache,
                                  jnp.asarray([pos], jnp.int32),
                                  jnp.asarray([n], jnp.int32), table)
        pos += n
        assert np.abs(np.asarray(lg)[0] - wanted[pos - 1]).max() < TOL, pos
    sites = obs_dispatch.dispatches()
    obs_dispatch.reset()
    assert ("kv_dense/paged-fused" in sites) == (read == "interp"), sites
    assert ("kv_dense/paged-gather" in sites) == (read == "off"), sites


@pytest.mark.parametrize("head_dim,fused", [(64, True), (8, False)],
                         ids=["rows-of-128-lanes", "rows-of-8-lanes"])
def test_the_cost_model_asks_what_the_trace_asks(monkeypatch, head_dim, fused):
    """``obs/cost.py`` files a paged step's attention under the family the
    trace records: it hands ``_fused_choice`` the width of the pool's rows,
    as ``paged_gqa_attention_at`` does, not the head size (heads of 64 two to
    a row take the walk on a TPU; the toy's heads of 8, which ``pool_rows``
    leaves a head a row, do not)."""
    from dllama_tpu.obs import cost as obs_cost
    cfg = tiny_lfm2_moe(head_dim=head_dim)
    eng = Engine(cfg, _init(cfg, seed=7), mesh=_mesh(), batch=2, kv_pages=60,
                 kv_page_size=4)
    assert eng.cache.k.shape[-1] == (128 if fused else 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "auto")
    assert obs_cost.model_from_engine(eng).fused is fused


@pytest.mark.parametrize("paged,head_dim", [(True, 8), (False, 8), (True, 64)],
                         ids=["paged", "contiguous", "paged-heads-of-64"])
def test_the_scheduler_serves_the_reference_token_for_token(params, want, paged,
                                                            head_dim):
    """Five requests on two slots, their lengths apart so that the slots are
    out of step, each slot taken over by a new request with the last tenant's
    ring left dirty: every stream is the reference's greedy stream.  At heads
    of 64 the pool's rows hold two heads each (``pool_rows``), the form the
    published widths serve in."""
    kw = dict(kv_pages=60, kv_page_size=4) if paged else {}
    cfg = CFG
    if head_dim != CFG.head_dim:
        cfg = tiny_lfm2_moe(head_dim=head_dim)
        params = _init(cfg, seed=7)
        want = {"np": {k: np.asarray(v) for k, v in params.items()}}
    eng = Engine(cfg, params, mesh=_mesh(), batch=2, **kw)
    if head_dim == 64:
        assert eng.cache.k.shape[3:] == (1, 128)
    sched = SlotScheduler(eng, prefill_chunk=16)
    # preemption parks a request page by page: it only exists over a pool
    assert sched.prefix_cache is None and not (paged and sched.preempt)
    try:
        prompts = [[int(t) for t in TOKS[a:a + n]]
                   for a, n in ((0, 5), (10, 37), (3, 17), (50, 33), (7, 16))]
        tickets = [sched.submit(p, max_new=14 + 3 * i)
                   for i, p in enumerate(prompts)]
        for p, t in zip(prompts, tickets):
            out = list(t.tokens())
            greedy = _logits(want["np"], p + out[:-1], cfg).argmax(-1)
            assert out == greedy[len(p) - 1:].tolist()
    finally:
        sched.close()


class _HalfRightProposer(Proposer):
    """Drafts of which the first two are the stream's own next tokens and the
    rest are wrong: every verify step accepts some rows and rejects others."""
    name = "half-right"

    def __init__(self, stream, n_prompt):
        super().__init__(CFG.vocab_size)
        self.stream, self.n_prompt = stream, n_prompt

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
    clock, which the ring holds harmlessly, and the stream is the greedy one."""
    p = [int(t) for t in TOKS[:18]]
    greedy = list(p)
    for _ in range(24):
        greedy.append(int(_logits(want["np"], greedy)[-1].argmax()))
    greedy = greedy[len(p):]
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, kv_pages=60, kv_page_size=4)
    sched = SlotScheduler(eng, prefill_chunk=16, spec_k=4,
                          spec=_HalfRightProposer(greedy, len(p)))
    try:
        ticket = sched.submit(p, max_new=24)
        assert list(ticket.tokens()) == greedy
        # drafts were accepted and drafts were rejected: rows above the clock
        assert ticket.spec_proposed > ticket.spec_accepted > 0
    finally:
        sched.close()


# ---- moe_ffn: every strategy ---------------------------------------------------

STRATEGIES = [
    ("dense", None, {}, {1: "select", 2: "select", 4: "select", 16: "dense"}),
    ("q40-kernel", "pallas_interpret", {},
     {1: "select-chosen", 2: "select-chosen", 4: "select-chosen", 16: "all-experts"}),
    ("q40-xla-scan", "xla", {}, {1: "select", 2: "select", 4: "select", 16: "scan"}),
    ("q40-xla-unrolled", "xla", dict(n_experts=8), {1: "select", 16: "unrolled"}),
]


@pytest.mark.parametrize("rows", [1, 2, 4, 16])
@pytest.mark.parametrize("name,impl,over,paths", STRATEGIES,
                         ids=[s[0] for s in STRATEGIES])
def test_every_strategy_against_the_float32_loop(name, impl, over, paths, rows):
    """``moe_ffn`` at 1, 2, 4 and 16 rows on every strategy against
    ``reference_impl.lfm2_moe_layer`` over the same (dequantized) weights: the
    sigmoid scores, the bias in the choice only, the chosen scores over their
    sum + 1e-6, routed scale 1, no shared expert."""
    if rows not in paths:
        pytest.skip("covered at 1 and 16 rows")
    cfg = tiny_lfm2_moe(**over)
    p = {k: np.asarray(v) for k, v in _init(cfg, 11).items()}
    lp = {k: p[k][2] for k in ("router", "router_bias", "up", "gate", "down")}
    x = np.random.RandomState(rows).standard_normal((rows, cfg.dim)).astype(np.float32)
    run_cfg, run_lp, ref_lp = cfg, dict(lp), dict(lp)
    if impl:
        run_cfg = cfg.with_(quant_impl=impl)
        for k in ("up", "gate", "down"):
            qt = q40.quantize(lp[k][None])
            ref_lp[k] = np.asarray(q40.dequantize(qt))[0]
            run_lp[k] = q40.QLayerView(jax.tree.map(jnp.asarray, qt), jnp.int32(0))
    obs_dispatch.reset()
    got = np.asarray(moe_ffn(jnp.asarray(x), {
        k: v if isinstance(v, q40.QLayerView) else jnp.asarray(v)
        for k, v in run_lp.items()}, run_cfg))
    site = [k for k in obs_dispatch.dispatches() if k.startswith("moe/")]
    assert site == ["moe/" + paths[rows]]
    wanted = ref.lfm2_moe_layer(x, ref_lp, cfg)
    tol = 2e-5 if impl is None else 0.03 * wanted.std()
    assert np.abs(got - wanted).max() < tol


def test_the_normalising_sum_carries_the_config_s_epsilon():
    """LFM2's ``+ 1e-6`` comes from the config and K-EXAONE's path has none: at
    scores this small the term is a fifth of the sum."""
    from dllama_tpu.models.config import tiny_exaone_moe
    assert tiny_exaone_moe().router_norm_eps == 0.0 and CFG.router_norm_eps == 1e-6
    lp = {"router": jnp.zeros((CFG.dim, 16)),
          "router_bias": jnp.zeros((16,)), "up": jnp.ones((16, CFG.dim, 32)),
          "gate": jnp.ones((16, CFG.dim, 32)), "down": jnp.ones((16, 32, CFG.dim))}
    x = jnp.ones((1, CFG.dim))
    tiny = jnp.full((1, 16), -15.0)      # sigmoid: 3.06e-7 each, four chosen: 1.2e-6
    got = moe_ffn(x, lp, CFG, tiny)
    whole = moe_ffn(x, lp, CFG, jnp.zeros((1, 16)))
    share = float(got[0, 0] / whole[0, 0])
    s = 1.0 / (1.0 + np.exp(15.0))
    assert abs(share - 4 * s / (4 * s + 1e-6)) < 1e-3 and share < 0.6


# ---- loader, engine, refusals, snapshots ---------------------------------------

@pytest.fixture(scope="module")
def q40_file(tmp_path_factory, want):
    path = tmp_path_factory.mktemp("lfm2") / "toy_q40.m"
    _write_model(path, want["np"], ftype=quants.Q40)
    return str(path)


def test_loader_packed_and_dense_agree_with_the_reference(q40_file):
    mf = mfile.MFile(q40_file)
    cfg, dense = load_params(mf, dtype=jnp.float32)
    assert cfg.conv_taps == 3 and cfg.window == 0
    assert {k: tuple(v.shape) for k, v in dense.items()} == param_shapes(cfg)
    toks = TOKS[:60]
    wanted = ref.np_forward_lfm2_moe({k: np.asarray(v) for k, v in dense.items()},
                                     cfg, toks)
    lg, _ = forward(dense, cfg, jnp.asarray(toks)[None], init_kv_cache(cfg, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - wanted).max() < TOL
    _, packed = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    assert packed["wqkv"].logical_nd == (64, 64 + 32)
    assert packed["conv_in"].logical_nd == (64, 192)
    assert packed["conv_out"].logical_nd == (64, 64)
    assert packed["conv_in"].qpacked.shape[0] == 6 and packed["wo"].qpacked.shape[0] == 2
    assert packed["conv_taps"].shape == (6, 64, 3) and packed["conv_taps"].dtype == np.float32
    assert packed["up"].qpacked.shape[:2] == (6, 16) and packed["w13"].qpacked.shape[0] == 2
    lg, _ = forward(packed, cfg.with_(quant_impl="xla"), jnp.asarray(toks)[None],
                    init_kv_cache(cfg, 1), jnp.int32(0))
    worst = np.abs(np.asarray(lg)[0] - wanted).max(1) / wanted.std()
    # the packed path rounds each matmul's activation to bfloat16: it reads a
    # median of 0.060 and at most 0.27 of the logits' spread here (three
    # products a convolution layer, a routing flip at some positions); a stack
    # read in the wrong order or a wrong split reads 1 and more
    assert np.median(worst) < 0.08 and worst.max() < 0.4, worst
    again = quantize_matmuls({k: np.asarray(v) for k, v in dense.items()}, cfg)
    assert set(again) == set(packed)


def test_the_gauges_and_the_ledger_name_the_state(params):
    obs_dispatch.reset()
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, kv_pages=30, kv_page_size=4)
    by_kind = obs_metrics.KV_CACHE_BYTES.json_value()
    assert by_kind["conv"] == eng.cache.cz.nbytes == 6 * 2 * R * 64 * 4
    assert by_kind["full"] == 2 * eng.cache.k.nbytes and by_kind["window"] == 0
    # what a token adds: the attention layers' keys and values alone
    assert eng.kv_bytes_per_token == 2 * 2 * 2 * 8 * 4
    assert eng.slot_state == "convolution layers' state" and eng.ring_pages == 0
    one = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert one.slot_state == "" and one.kv_bytes_per_token == 2 * 2 * 2 * 8 * 4
    one.prefill([int(t) for t in TOKS[:5]])
    assert "conv/ring" in obs_dispatch.dispatches()


def test_snapshot_carries_the_state_and_its_account(params, tmp_path):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert set(eng._cache_arrays()) == {"cache.k", "cache.v", "cache.cz"}
    first = [t for t, _ in eng.generate_stream([int(t) for t in TOKS[:40]], 45,
                                               temperature=0.0, chunk=3)]
    path = str(tmp_path / "e.snap")
    eng.snapshot(path)
    rest = [t for t, _ in eng.generate_stream([first[-1]], 12, temperature=0.0,
                                              chunk=3)]
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng2.restore(path)
    assert (eng2._state_lo, eng2._state_hi) == (0, 44) and eng2.pos == 44
    again = [t for t, _ in eng2.generate_stream([first[-1]], 12, temperature=0.0,
                                                chunk=3)]
    assert again == rest


def test_scopes_name_the_operator_under_the_four_stages(params):
    cache = init_kv_pool(CFG, 12, 4, slots=1, max_pages=12)
    table = jnp.asarray(np.arange(12, dtype=np.int32)[None])
    text = jax.jit(lambda c: forward_slots(
        params, CFG, jnp.zeros((1, 4), jnp.int32), c, jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 4, jnp.int32), table)).lower(cache).as_text(debug_info=True)
    for name in ("qkv/conv", "kv_write/conv", "attn/conv", "wo/conv",
                 "qkv/qk_norm", "attn/full", "moe/router", "w1", "page_idx"):
        assert name in text, name
    assert "attn/window" not in text and "moe/shared" not in text


# ---- the converter ---------------------------------------------------------------

LFM2_HF = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=8, num_attention_heads=8,
    num_key_value_heads=2, vocab_size=128, max_position_embeddings=128,
    num_experts=16, num_experts_per_tok=4, num_dense_layers=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    norm_eps=1e-5, conv_L_cache=3, conv_bias=False,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=["conv", "conv", "full_attention", "conv"] * 2)


def _hf_checkpoint(p, cfg, tied=True):
    """A toy checkpoint under transformers' Lfm2Moe tensor names; the head
    tied to the embedding (no ``lm_head.weight``) as published."""
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.embedding_norm.weight": p["rms_final"]}
    if not tied:
        hf["lm_head.weight"] = p["wcls"].T
    n_att = n_conv = 0
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        hf[base + "operator_norm.weight"] = p["rms_att"][i]
        hf[base + "ffn_norm.weight"] = p["rms_ffn"][i]
        if i % 4 == 2:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "out_proj")):
                hf[f"{base}self_attn.{theirs}.weight"] = p[ours][n_att].T
            hf[base + "self_attn.q_layernorm.weight"] = p["q_norm"][n_att]
            hf[base + "self_attn.k_layernorm.weight"] = p["k_norm"][n_att]
            n_att += 1
        else:
            hf[base + "conv.in_proj.weight"] = p["conv_in"][n_conv].T
            hf[base + "conv.conv.weight"] = p["conv_taps"][n_conv][:, None, :]
            hf[base + "conv.out_proj.weight"] = p["conv_out"][n_conv].T
            n_conv += 1
        if i < cfg.n_dense_layers:
            for leaf in ("w1", "w2", "w3"):
                hf[f"{base}feed_forward.{leaf}.weight"] = p[leaf][i].T
            continue
        m = i - cfg.n_dense_layers
        hf[base + "feed_forward.gate.weight"] = p["router"][m].T
        hf[base + "feed_forward.expert_bias"] = p["router_bias"][m]
        for e in range(cfg.n_experts):
            for ours, theirs in (("gate", "w1"), ("down", "w2"), ("up", "w3")):
                hf[f"{base}feed_forward.experts.{e}.{theirs}.weight"] = p[ours][m, e].T
    return {k: np.ascontiguousarray(v, np.float32) for k, v in hf.items()}


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "own-head"])
def test_convert_round_trip_and_the_logits(tmp_path, tied):
    from safetensors.numpy import save_file

    import convert_hf

    p = {k: np.asarray(v, np.float32) for k, v in _init(CFG, seed=9).items()}
    if tied:
        p["wcls"] = p["embedding"].T
    (tmp_path / "config.json").write_text(json.dumps(LFM2_HF))
    save_file(_hf_checkpoint(p, CFG, tied), str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "lfm2.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    mf = mfile.MFile(out)
    assert mf.spec.arch == mfile.ARCH_LFM2_MOE
    got_cfg, params = load_params(mf)
    got_cfg = got_cfg.with_(dtype=jnp.float32)
    assert got_cfg.with_(norm_eps=1e-5) == CFG
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32), p[k], err_msg=k)
    toks = TOKS[:40]
    wanted = ref.np_forward_lfm2_moe(p, CFG, toks)
    logits, _ = forward(params, got_cfg, jnp.asarray(toks)[None],
                        init_kv_cache(got_cfg, 1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits)[0], wanted, atol=TOL, rtol=1e-4)


@pytest.mark.parametrize("key,value,says", [
    ("conv_bias", True, "conv_bias is true"),
    ("layer_types", ["conv"] * 5 + ["full_attention"] * 3, "is not whole periods"),
    ("layer_types", ["conv", "sliding_attention", "full_attention", "conv"] * 2,
     "is not whole periods"),
    ("num_experts_per_tok", 17, "is more than num_experts"),
    ("norm_topk_prob", False, "norm_topk_prob is false"),
    ("use_expert_bias", False, "use_expert_bias is false"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}, "rope_type is 'yarn'"),
    ("num_dense_layers", 8, "leaves no expert layer"),
])
def test_convert_refuses_variants_by_name(tmp_path, key, value, says):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(LFM2_HF, **{key: value})))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)


def test_convert_refuses_a_share_of_this_arch(tmp_path):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(LFM2_HF))
    with pytest.raises(SystemExit, match="write a share of an exaone_moe"):
        convert_hf.load_spec(str(tmp_path), quants.F32, experts_held=4)
