"""Granite-4.0-H (``ARCH_GRANITE_HYBRID``): periods of Mamba-2 mixer layers and
one position-free attention layer, a mixture of experts renormalised over the
chosen ones beside a shared MLP in every layer, four scalar multipliers, on the
normal path of both engines against the float32 ATTENTION-form reference
(``reference_impl.np_forward_granite_hybrid``: no state, no ring, no
convolution cache, no pages), seeded random weights at
``tiny_granite_hybrid()``: periods of five with the attention layer at 2, two
periods, ONE group, 12 experts top-3, every multiplier off 1.

A layer of a slot owns a state OR keys and values: the mixer's planes are
``n_ssm_layers`` (8) deep, ``k`` / ``v`` ``n_full_layers`` (2).  ``init_params``
draws ``A`` in 0.5..2 and ``dt`` near 0.01..0.1, so what was folded into the
state hundreds of positions ago still moves every logit.  The scheduler's run
is in ``test_granite_hybrid_serve.py``, a file of its own so that it runs on
another worker.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models import cache_kinds, config as config_mod, packing
from dllama_tpu.models.config import tiny_granite_hybrid
from dllama_tpu.models.params import init_params, load_params
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           init_kv_cache, init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import retention
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine, StateRewindTooDeep

CFG = tiny_granite_hybrid()
A, R, C = retention.FOLD, retention.REWIND, retention.RING
N = 2 * C + 60                        # a context past several folds
TOKS = np.random.RandomState(0).randint(3, 128, (N + 40,)).astype(np.int32)
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums (the ring's 128 products and the state's 12 a head,
# eight mixer layers) against the reference's float64 double sum: 1.6e-6 at
# worst over 250 positions of logits whose spread is 0.16.  A state rounded to
# bfloat16 moves the next tokens' logits by 3e-5 to 5e-5, a zeroed one by 3e-2,
# the key's multiplier's absence (the least of the faults below) by 4e-2, a
# softmax over all experts by 2e-1.
TOL = 5e-6
KEYS = (19, 20, 31, 32, 34, 37, 41, 42, 43, 44, 45, 46, 47, 49, 51, 52, 54)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=5, scale=0.08)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_granite_hybrid(p, CFG, TOKS[:N]), "np": p}


@pytest.fixture(scope="module")
def eng(params):
    """ONE one-stream engine for the module (a test resets it first): its
    programs compile once."""
    with jax.default_matmul_precision("highest"):
        return Engine(CFG, params, mesh=_mesh(), batch=1)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _logits(p, toks, cfg=CFG):
    return ref.np_forward_granite_hybrid(p, cfg, np.asarray(toks, np.int32))


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=cfg.n_experts, n_active_experts=cfg.n_active_experts,
        vocab_size=cfg.vocab_size, seq_len=cfg.seq_len,
        hidden_act=cfg.hidden_act, rope_theta=cfg.rope_theta,
        weights_ftype=ftype,
        **{name: getattr(cfg, name) for k, name, _ in mfile.ALL_EXT_KEYS
           if k in KEYS})
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, p, cfg=CFG, ftype=quants.F32):
    """``p``'s stacks into a file: a layer's attention or mixer tensors from its
    place among its kind, everything else from the layer's own index."""
    kind = {}
    for i in range(cfg.n_layers):
        full = i % cfg.window_period == cfg.window_full_at
        kind[i] = sum((j % cfg.window_period == cfg.window_full_at) == full
                      for j in range(i))
    names = {"moe_router": "router"}
    with mfile.MFileWriter(path, _spec(cfg, ftype=ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = p[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            elif parts[2] == "experts":
                x = p[parts[4]][int(parts[1])][int(parts[3])].T
            else:
                key = names.get(parts[-1], parts[-1])
                by_kind = key.startswith("ssm_") or key in ("wq", "wk", "wv", "wo")
                x = p[key][kind[int(parts[1])] if by_kind else int(parts[1])]
                x = x.reshape(-1) if key == "ssm_conv_w" else \
                    x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format and the planes' depths ---------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    assert mfile.ARCH_GRANITE_HYBRID == 0xABCD0B
    assert mfile.ARCH_NAMES[0xABCD0B] == "granitemoehybrid"
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_GRANITE_HYBRID] == KEYS
    assert mfile.KEY_MAX == 60          # no key of its own
    path = str(tmp_path / "g.m")
    _write_model(path, want["np"])
    spec = mfile.read_spec(path)
    assert (spec.window_period, spec.window_full_at, spec.ssm_groups,
            spec.n_shared_experts) == (5, 2, 1, 2)
    assert (spec.mup_attn_out, spec.mup_ssm_out, spec.mup_down) == (0.5,) * 3
    assert spec.mup_embedding == 3.0 and spec.mup_ssm_in == 1.0
    names = [t.name for t in mfile.tensor_plan(spec)]
    assert "layers.2.wq" in names and "layers.2.ssm_in" not in names
    assert "layers.3.ssm_in" in names and "layers.3.wq" not in names
    assert not any("q_norm" in n or "router_bias" in n for n in names)
    assert "layers.2.shared_w1" in names and "layers.0.shared_w2" in names
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf)
    assert cfg.has_ssm and cfg.periodic and cfg.kind_stacked and cfg.folds_state
    assert not cfg.full_rotates and cfg.norm_topk_prob and not cfg.router_sigmoid
    assert (cfg.n_ssm_layers, cfg.n_full_layers, cfg.n_conv_layers) == (8, 2, 0)
    assert p["ssm_in"].shape == (8, 64, 152) and p["wq"].shape == (2, 64, 64)
    assert p["ssm_dt"].dtype == np.float32 and p["up"].shape == (10, 12, 64, 32)
    for k, v in want["np"].items():
        assert np.array_equal(np.asarray(p[k], np.float32), v), k


@pytest.mark.parametrize("kw,says", [
    (dict(head_dim=1), "states its attention head size"),
    (dict(window=8), "no sliding window and no short convolution"),
    (dict(window_period=3), "whole periods of one attention"),
    (dict(window_full_at=5), "the attention layer's place"),
    (dict(ssm_heads=0), "states its state-space mixer's sizes"),
    (dict(ssm_groups=2), "one RMSNorm over all"),
    (dict(ssm_conv=1), "states its convolution's taps"),
    (dict(n_experts=0, n_active_experts=0), "has experts and a top-k"),
    (dict(n_shared_experts=3), "a whole number of experts wide"),
    (dict(mup_down=0.0), "a positive float"),
    (dict(mup_ssm_in=0.5), "the three branch outputs' multipliers alone"),
    (dict(arch=mfile.ARCH_LFM2_MOE, conv_taps=3, n_shared_experts=0,
          ssm_heads=0, ssm_head_dim=0, ssm_state=0, ssm_groups=0, ssm_conv=0,
          mup_embedding=1.0, mup_head=1.0, mup_key=1.0, mup_attn_out=1.0,
          mup_ssm_out=1.0),
     "keys 41..60 describe a falcon_h1"),
])
def test_header_rules_are_refused_by_name(kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), "x.m")


def _published(**kw):
    base = dict(
        arch=mfile.ARCH_GRANITE_HYBRID, dim=4096, hidden_dim=1536, n_layers=20,
        n_heads=32, n_kv_heads=8, n_experts=72, n_active_experts=10,
        vocab_size=100352, seq_len=2048, hidden_act=mfile.ACT_SILU,
        rope_theta=10000.0, norm_eps=1e-5, head_dim=128, window_period=10,
        window_full_at=5, moe_hidden_dim=768, n_shared_experts=2,
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, mup_embedding=12.0, mup_head=1 / 16,
        mup_key=0.0078125 * 128 ** 0.5, mup_attn_out=0.22, mup_ssm_out=0.22,
        mup_down=0.22, dtype=jnp.bfloat16)
    base.update(kw)
    return config_mod.ModelConfig(**base)


def test_published_widths_give_the_issues_state_and_pages():
    """Granite-4.0-H-Small's widths at the cell's 16 slots and 2056 pages, 20
    of 40 layers: 18 layers of a slot own a state, 2 own pages, none both."""
    cfg = _published()
    assert cfg.cache_kinds == (cache_kinds.FULL, cache_kinds.SSM)
    assert (cfg.n_ssm_layers, cfg.n_full_layers) == (18, 2)
    assert (cfg.ssm_inner, cfg.ssm_channels, cfg.head_size) == (8192, 8448, 128)
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 2056, 16, slots=16,
                                               max_pages=128))
    planes = {n: (a.shape, a.dtype) for n, a in pool.planes().items()}
    assert planes["k"] == ((2, 2056, 16, 8, 128), jnp.bfloat16)
    assert planes["rs"] == ((18, 16, 128, 128, 64), jnp.float32)
    assert planes["rk"] == ((18, 16, 1, 128, 128), jnp.bfloat16)
    # heads of 64 two to a row of the x ring (``ssm.heads_a_row``)
    assert planes["rv"] == ((18, 16, 64, 128, 128), jnp.bfloat16)
    assert planes["rg"] == ((18, 16, 1, 128, 128), jnp.float32)
    assert planes["cz"] == ((18, 16, 1, 64, 8448), jnp.bfloat16)
    assert planes["rw"][0] == (1, 16, 1, 1, 1)
    assert set(pool.pool_planes()) == {"k", "v"}
    size = lambda n: int(np.prod(planes[n][0])) * jnp.dtype(planes[n][1]).itemsize  # noqa: E731
    assert size("rs") == 16 * 18 * 4_194_304                 # 1.21 GB of states
    assert size("rv") == 16 * 18 * 2_097_152 and size("cz") == 16 * 18 * 1_081_344
    assert size("k") + size("v") == 2056 * 16 * 8_192        # 0.27 GB of pages
    slot = sum(size(n) for n in ("rs", "rk", "rv", "rg", "cz")) // 16
    assert round(slot / 1e6, 1) == 134.5
    contiguous = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    assert contiguous.k.shape == (2, 2, 8, 256, 128)
    assert contiguous.rs.shape[0] == 18 and contiguous.cz.shape[0] == 18
    with pytest.raises(ValueError, match="needs the number of slots"):
        init_kv_pool(cfg, 100, 16)
    with pytest.raises(ValueError, match="no int8 form"):
        init_kv_pool(cfg, 100, 16, quant=True, slots=2)


def test_falcon_h1_and_granite_share_the_tables_row():
    """One row serves both: Falcon-H1's planes are as deep as its blocks."""
    falcon = config_mod.tiny_falcon_h1()
    assert falcon.cache_kinds == CFG.cache_kinds == (cache_kinds.FULL,
                                                     cache_kinds.SSM)
    assert cache_kinds.SSM.depth(falcon) == falcon.n_layers == falcon.n_full_layers
    assert cache_kinds.SSM.depth(CFG) == 8 and CFG.n_full_layers == 2
    cache = init_kv_cache(falcon, 1, 32)
    assert cache.rs.shape[0] == cache.k.shape[0] == 3
    cache = init_kv_cache(CFG, 1, 32)
    assert (cache.rs.shape[0], cache.k.shape[0]) == (8, 2)


# ---- the one-stream engine ---------------------------------------------------

def test_prefill_then_decode_through_state_ring_and_cache(eng, want):
    """A prompt of 2 C + 20 in chunks of 32 and a bucketed tail, then 40 tokens
    one by one: every position's logits are the reference's."""
    n = 2 * C + 20
    eng.reset()
    obs_dispatch.reset()
    before = obs_metrics.SSM_FOLDS.json_value()
    lg, _ = eng.prefill([int(t) for t in TOKS[:n]])
    assert np.abs(lg[0] - want["a"][n - 1]).max() < TOL
    for i in range(n, n + 40):
        lg, _ = eng.decode_one(int(TOKS[i]))
        assert np.abs(lg[0] - want["a"][i]).max() < TOL, i
    assert eng.pos == n + 40 and eng._state_lo == retention.watermark(0, n + 40)
    assert int(np.asarray(eng.cache.rw).ravel()[0]) == eng._state_lo >= C
    # a fold is a block in each of the EIGHT layers that keep a state
    folds = obs_metrics.SSM_FOLDS.json_value() - before
    assert folds == eng._state_lo // A * CFG.n_ssm_layers
    # one ledger line a compiled call site, the mixer's beside the experts'
    assert {"ssm/state-read", "ssm/block", "ssm/fold", "conv/ring"} <= set(
        obs_dispatch.dispatches())


@pytest.mark.parametrize("state,least", [
    (lambda rs: jnp.zeros_like(rs), 100),
    (lambda rs: rs.astype(jnp.bfloat16).astype(jnp.float32), 5),
], ids=["zeroed", "bfloat16"])
def test_a_wrong_state_would_not_pass(eng, want, state, least):
    """With the state zeroed, or rounded to bfloat16, after 250 tokens the next
    four tokens' logits are out of the tolerance: the toy's decays let a context
    hundreds of positions old through."""
    eng.reset()
    eng.prefill([int(t) for t in TOKS[:250]])
    eng.cache = eng.cache._replace(rs=state(eng.cache.rs))
    err = max(np.abs(eng.decode_one(int(TOKS[i]))[0][0] - want["a"][i]).max()
              for i in range(250, 254))
    assert err > least * TOL


@pytest.mark.parametrize("wrong", [
    "no_embedding", "no_head", "no_key", "no_residual", "rope", "softmax_all",
    "no_shared", "no_decay", "no_conv", "norm_before_gate", "no_skip"])
def test_each_wrong_computation_is_seen(want, wrong):
    """A multiplier set to 1, a rotation applied, the softmax over all experts,
    the shared MLP dropped, or the mixer's decay, taps, gate order or skip wrong:
    each is out of the tolerance the engines are held to."""
    bad = ref.np_forward_granite_hybrid(want["np"], CFG, TOKS[:100], wrong=wrong)
    assert np.abs(bad - want["a"][:100]).max() > 100 * TOL


@pytest.mark.parametrize("j", [1, 31])
def test_a_rewind_inside_the_ring_resumes_as_a_fresh_forward(eng, want, j):
    eng.reset()
    seq = [t for t, _ in eng.generate_stream(
        [int(t) for t in TOKS[:150]], 150 + 49, temperature=0.0, chunk=16)]
    assert eng.pos == 150 + 48 and eng._state_lo == 2 * A
    before = obs_metrics.SSM_STATE_REWINDS.json_value().get("in_ring", 0)
    eng.pos -= j
    kept = seq[:eng.pos]
    lg, _ = eng.decode_one(77)
    assert np.abs(lg[0] - _logits(want["np"], kept + [77])[-1]).max() < TOL
    assert obs_metrics.SSM_STATE_REWINDS.json_value()["in_ring"] == before + 1


def test_a_deeper_rewind_is_refused_by_name_and_counted(eng):
    eng.reset()
    eng.prefill([int(t) for t in TOKS[:200]])
    before = obs_metrics.SSM_STATE_REWINDS.json_value().get("refused", 0)
    eng.pos = 100
    with pytest.raises(StateRewindTooDeep, match="prefill the conversation again"):
        eng.decode_one(3)
    assert obs_metrics.SSM_STATE_REWINDS.json_value()["refused"] == before + 1
    assert eng._max_burst(64) == 16


# ---- the slot path: a state OR pages in a layer of a slot ---------------------

PAGES, PS, WIDTH = 160, 4, 80


def _row_tokens(r, lo, hi):
    return (TOKS if r % 2 == 0 else TOKS[::-1])[lo:hi]


def _table(b):
    """Slot ``r`` owns pages ``1 + r * WIDTH / 2 ..`` (page 0 is the scratch page)."""
    return jnp.asarray(1 + np.arange(b)[:, None] * WIDTH // 2
                       + np.arange(WIDTH // 2)[None, :], jnp.int32)


def _slot_state(params, hist, _chunk_step):
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


def test_one_step_with_rows_of_0_1_5_and_16_tokens(params, want, monkeypatch):
    """A mixed step past folds on a paged pool, packed (models/packing.py): a
    slot that rides along (n_valid 0, its state kept), a decoding slot, a ragged
    last chunk and a whole chunk of a new tenant.  Then a slot decoding alone
    beside a slot reused at position 0 over its predecessor's state."""
    monkeypatch.setattr(packing, "BUCKETS", (16,))
    _chunk_step = jax.jit(lambda params, tk, cache, pos, n, table: forward_slots(
        params, CFG, tk, cache, pos, n, table))
    hist = np.asarray([137, 150, 144, 0], np.int32)
    cache = _slot_state(params, hist, _chunk_step)
    assert (cache.rs.shape[0], cache.k.shape[0]) == (8, 2)
    nv = np.asarray([0, 1, 5, 16], np.int32)
    tk = np.zeros((4, 16), np.int32)
    for r in range(4):
        tk[r, :nv[r]] = _row_tokens(r, hist[r], hist[r] + nv[r])
    assert packing.plan(jnp.asarray(nv), 4, 16) is not None
    before = np.asarray(cache.rs)
    lg, cache = _chunk_step(params, jnp.asarray(tk), cache, jnp.asarray(hist),
                            jnp.asarray(nv), _table(4))
    for r in (1, 2, 3):
        wanted = _logits(want["np"], _row_tokens(r, 0, hist[r] + nv[r]))[-1]
        assert np.abs(np.asarray(lg)[r] - wanted).max() < TOL, r
    # the slot that rode along: its state matrix is bit-equal
    assert np.array_equal(np.asarray(cache.rs)[:, 0], before[:, 0])
    # it goes on from its own state; slot 1 is taken by a new tenant at
    # position 0 over its predecessor's state and pages, which it must not see
    nv2 = np.asarray([1, 7, 0, 0], np.int32)
    tk2 = np.zeros((4, 16), np.int32)
    tk2[0, 0] = TOKS[137]
    tk2[1, :7] = TOKS[40:47]
    pos2 = np.asarray([137, 0, 149, 16], np.int32)
    lg, _ = _chunk_step(params, jnp.asarray(tk2), cache, jnp.asarray(pos2),
                        jnp.asarray(nv2), _table(4))
    assert np.abs(np.asarray(lg)[0] - want["a"][137]).max() < TOL
    assert np.abs(np.asarray(lg)[1] - _logits(want["np"], TOKS[40:47])[-1]).max() < TOL


# ---- the loader, the gauges, the ledger, the refusals -------------------------

def test_loader_packed_agrees_with_the_reference(tmp_path, want):
    """A Q40 file through the normal loader (``wqkv`` and ``shared_w13`` joined,
    ``ssm_in`` / ``ssm_out`` and the experts packed, the ``dt`` projection
    float32): prefill and decode against the reference of the dequantized
    weights."""
    path = str(tmp_path / "q.m")
    _write_model(path, want["np"], ftype=quants.Q40)
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf, dtype=jnp.float32, keep_quantized=True)
        _, dense = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    assert "wqkv" in p and "shared_w13" in p and p["ssm_dt"].dtype == np.float32
    assert {type(p[k]).__name__ for k in ("ssm_in", "ssm_out", "up", "wcls")} \
        == {"QTensor"}
    deq = {k: np.asarray(v, np.float32) for k, v in dense.items()}
    wanted = ref.np_forward_granite_hybrid(deq, cfg, TOKS[:32])
    cfg = cfg.with_(quant_impl="xla")
    got, _ = jax.jit(lambda tk, c: forward(p, cfg, tk, c, jnp.int32(0)))(
        jnp.asarray(TOKS[None, :32]), init_kv_cache(cfg, 1, 64))
    # the packed path's own rounding reads 0.003 to 0.017 over these 32
    # positions of logits whose spread is 0.16; a stack read at another
    # layer's place reads the spread itself
    assert np.abs(np.asarray(got)[0] - wanted).max() < 0.15 * wanted.std()


def test_the_gauges_count_each_kind_by_its_own_depth(params):
    eng = Engine(CFG, params, mesh=_mesh(), batch=2, seq_len=64, kv_pages=40,
                 kv_page_size=4)
    by_kind = obs_metrics.KV_CACHE_BYTES.json_value()
    planes = eng.cache.planes()
    assert planes["k"].shape[0] == 2 and planes["rs"].shape[0] == 8
    assert by_kind["full"] == int(planes["k"].nbytes) * 2
    assert by_kind["ssm"] == sum(int(a.nbytes) for n, a in planes.items()
                                 if n not in ("k", "v"))
    assert by_kind["retention"] == by_kind["conv"] == by_kind["window"] == 0
    # a cached token costs the TWO attention layers' keys and values
    assert eng.kv_bytes_per_token == CFG.n_full_layers * 2 * CFG.kv_dim * 4
    assert set(eng.cache.pool_planes()) == {"k", "v"}
    with pytest.raises(ValueError, match="state-space mixers' state cannot be "
                                         "carried page by page"):
        eng._refuse_slot_state("per-request hand-off (DLREQ01)")


def test_what_a_slot_owned_state_refuses_is_refused_by_name(params):
    with pytest.raises(ValueError, match="a state-space \\(granitemoehybrid\\) "
                                         "model runs on one device"):
        Engine(CFG, params, mesh=make_mesh(tp=2, devices=jax.devices()[:2]),
               batch=1)
    with pytest.raises(ValueError, match="int8"):
        Engine(CFG, params, mesh=_mesh(), batch=1, kv_dtype="q8")


# ---- the operator at half a lane row: two heads a row of the x ring ------------

def test_heads_of_64_are_stored_two_to_a_row_and_read_as_the_attention_form():
    """``ops/ssm.py heads_a_row``: at ``P`` = 64 (Granite's published head) the
    ring of ``x`` holds two heads side by side, 128 values a position, and the
    fold, the write and the read through it are the attention form's, through
    calls of every width the engines make (a chunk, a ragged chunk in its
    bucket, decoded rows alone and in a step of 16, a row that rides along)."""
    from dllama_tpu.ops import ssm

    class Sizes:
        n_layers, ssm_heads, ssm_groups, ssm_state, ssm_head_dim, ssm_channels = \
            1, 4, 1, 16, 64, 8

    assert ssm.heads_a_row(4, 64) == ssm.heads_a_row(128, 64) == 2
    assert ssm.heads_a_row(32, 128) == ssm.heads_a_row(4, 16) \
        == ssm.heads_a_row(4, 32) == ssm.heads_a_row(3, 64) == 1
    planes = ssm.init_planes(Sizes, 2, jnp.float32)
    assert planes["rv"].shape == (1, 2, 2, C, 128) and planes["rs"].shape[2:] == (4, 16, 64)
    rng = np.random.RandomState(1)
    n = C + 86                # 198 fed, and room for the last step's padding
    c, b = rng.standard_normal((2, 2, 1, n, 16)).astype(np.float32)
    x = rng.standard_normal((2, 4, n, 64)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (2, 4, n)).astype(np.float32)
    a = -rng.uniform(0.5, 2.0, 4)
    layer, a32 = jnp.int32(0), jnp.asarray(a, jnp.float32)

    @jax.jit
    def call(planes, c, b, x, dt, pos, n_real):
        w, wn = retention.clock(planes["rw"], pos, x.shape[2], n_real)
        rs = ssm.fold(planes["rs"], planes["rk"], planes["rv"], planes["rg"],
                      a32, layer, w, wn)
        rk, rv, rg = ssm.write(planes["rk"], planes["rv"], planes["rg"], b, x,
                               ssm.live_dt(dt.transpose(0, 2, 1), pos, None, n_real),
                               layer, pos)
        y = ssm.read(c, rs, rk, rv, rg, a32, layer, pos, wn)
        return y, dict(planes, rs=rs, rk=rk, rv=rv, rg=rg,
                       rw=wn.reshape(planes["rw"].shape))

    calls = [(32, 32), (32, 19), (16, 16)] + [(1, 1)] * 30 + [(16, 1)] * 40 \
        + [(16, 0), (32, 32)] + [(16, 1)] * 29
    pos, ys = 0, []
    for t, n_real in calls:
        sl = slice(pos, pos + t)
        y, planes = call(planes, c[:, :, sl], b[:, :, sl], x[:, :, sl], dt[:, :, sl],
                         jnp.full((2,), pos, jnp.int32), jnp.full((2,), n_real, jnp.int32))
        ys.append(np.asarray(y)[:, :, :n_real])
        pos += n_real
    assert pos == n - 16 and int(np.asarray(planes["rw"]).ravel()[0]) >= A
    cs = np.cumsum(dt.astype(np.float64) * a[None, :, None], -1)
    want = np.zeros((2, 4, pos, 64))
    s = np.einsum("btn,bjn->btj", c[:, 0, :pos].astype(np.float64),
                  b[:, 0, :pos].astype(np.float64))
    for i in range(4):
        w = np.tril(s * np.exp(np.minimum(
            cs[:, i, :pos, None] - cs[:, i, None, :pos], 0.0)))
        want[:, i] = np.einsum("btj,bjp->btp", w * dt[:, i, None, :pos],
                               x[:, i, :pos].astype(np.float64))
    got = np.concatenate(ys, axis=2)
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()


def test_the_synthesizer_writes_a_file_the_loader_takes(tmp_path):
    """``synth.py``'s named shapes carry the header keys past the fourteen: the
    toy one is written at packed size, parsed, loaded packed (a mixer head of
    64: two to a row of the ring of ``x``) and run."""
    from dllama_tpu import synth

    full = synth.model_cfg("granite-4.0-h-small")
    assert (full.n_layers, full.n_ssm_layers, full.n_full_layers) == (40, 36, 4)
    assert full.cache_kinds == (cache_kinds.FULL, cache_kinds.SSM)
    mpath, _ = synth.synth_model_files("cpu-tiny-granite", str(tmp_path), seed=3)
    with mfile.MFile(mpath) as mf:
        assert mf.spec.arch == mfile.ARCH_GRANITE_HYBRID and mf.spec.ssm_head_dim == 64
        cfg, p = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    assert (cfg.n_ssm_layers, cfg.n_full_layers) == (4, 1)
    cache = init_kv_cache(cfg, 1, 64)
    assert cache.rv.shape == (4, 1, 2, C, 128) and cache.rs.shape == (4, 1, 4, 32, 64)
    cfg = cfg.with_(quant_impl="xla")
    lg, _ = jax.jit(lambda tk, c: forward(p, cfg, tk, c, jnp.int32(0)))(
        jnp.asarray(TOKS[None, :8]), cache)
    assert lg.shape == (1, 8, 300) and bool(jnp.isfinite(lg).all())
