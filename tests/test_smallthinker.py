"""``ARCH_SMALLTHINKER`` (0xABCD05), SmallThinker at a toy size that keeps
every ratio (periods of one full and three window layers, a window of 16 under
sequences of 60, 7 query heads a kv head, a head of 8 on a hidden size of 96
that 28 heads do not divide, 64 experts of which 6 a token, ReLU): the format,
the program against the plain reference of ``tests/reference_impl.py`` (logits,
not tokens) in a one-pass prefill, a chunked prefill that crosses the window
and decoding through a wrapped ring; chunked against one-pass; the slot and
paged paths against the contiguous one; snapshots; the refusals; the tracing
names.  The converter's case is in ``tests/test_converter.py``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models import config as config_mod
from dllama_tpu.models.config import (ModelConfig, tiny_config,
                                      tiny_deepseek2, tiny_smallthinker)
from dllama_tpu.models.params import init_params, load_params, quantize_matmuls
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import window
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler
from fixtures import bf16_exact_scales

CFG = tiny_smallthinker()
TOKS = np.random.RandomState(0).randint(3, 128, (60,)).astype(np.int32)
TOKS2 = TOKS[::-1].copy()
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums (the program's online softmax over ring blocks and its
# einsums against numpy's loops), 1-3e-6 of logits whose spread is 0.68.  Each
# of the five wrong computations below moves some position by 0.17 or more.
TOL = 2e-5
# the experts' float32 product of 16 rows: a prefill chunk of 16, so a window
# layer's ring is 16 + 16 = 32 positions under sequences of 60
SMALL_PRODUCT = 4 * 64 * 96 * 16


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=5, scale=0.08)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_smallthinker(p, CFG, TOKS),
            "b": ref.np_forward_smallthinker(p, CFG, TOKS2), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_chunk(monkeypatch):
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    assert CFG.prefill_chunk() == 16 and CFG.window_ring(96) == 32


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=cfg.n_experts, n_active_experts=cfg.n_active_experts,
        vocab_size=cfg.vocab_size, seq_len=cfg.seq_len,
        hidden_act=cfg.hidden_act, rope_theta=cfg.rope_theta,
        weights_ftype=ftype, norm_eps=cfg.norm_eps, head_dim=cfg.head_dim,
        window=cfg.window, window_period=cfg.window_period)
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, params_np, ftype=quants.F32):
    """The runtime-layout ``params_np`` as a ``.m`` file: each plan tensor is
    the stack's slice, transposed back to the file's (d_out, n_in)."""
    with mfile.MFileWriter(path, _spec(ftype=ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = params_np[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            else:
                leaf = parts[-1]
                x = params_np["router" if leaf == "moe_router" else leaf][int(parts[1])]
                if parts[2] == "experts":
                    x = x[int(parts[3])]
                x = x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ------------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path, want):
    path = tmp_path / "toy.m"
    _write_model(path, want["np"])
    spec = mfile.read_spec(path)
    assert spec.arch == mfile.ARCH_SMALLTHINKER == 0xABCD05
    assert spec.arch_name == "smallthinker" and spec.hidden_act == mfile.ACT_RELU == 2
    assert (spec.head_dim, spec.window, spec.window_period) == (8, 16, 4)
    assert spec.head_size == 8 and spec.q_dim == 224 and spec.kv_dim == 32
    assert spec.norm_eps == pytest.approx(1e-6)
    # the fourteen keys and 31..34, nothing of DeepSeek-V2's
    assert spec.header_size == 8 + 8 * (14 + 4)
    cfg = ModelConfig.from_spec(spec)
    assert cfg.head_size == 8 and cfg.n_full_layers == 2 and cfg.n_window_layers == 6
    assert cfg.norm_topk_prob and not cfg.rope_interleaved
    plan = {t.name: t.shape for t in mfile.tensor_plan(spec)}
    assert plan["layers.0.wq"] == (224, 96) and plan["layers.0.wo"] == (96, 224)
    assert plan["layers.3.wk"] == (32, 96)
    assert plan["layers.7.experts.63.down"] == (96, 32)


@pytest.mark.parametrize("patch,says", [
    (dict(head_dim=0), "dim not divisible by n_heads"),
    (dict(head_dim=7), "states its head size"),
    (dict(window=0), "states its sliding window"),
    (dict(window_period=3), "whole periods"),
    (dict(window_period=1), "whole periods"),
    (dict(n_experts=0, n_active_experts=0), "has experts and a top-k"),
    (dict(hidden_act=3), "unknown activation id"),
    (dict(arch=mfile.ARCH_MIXTRAL), "keys 32..34 describe a smallthinker file"),
])
def test_header_refusals(tmp_path, patch, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**patch), tmp_path / "x.m")


def test_older_archs_keep_their_head_size_and_keys():
    old = tiny_config()
    assert old.head_size == old.dim // old.n_heads and old.q_dim == old.dim
    assert old.n_full_layers == old.n_layers and old.n_window_layers == 0
    spec = mfile.ModelSpec(dim=64, n_heads=4, n_kv_heads=2)
    assert spec.head_size == 16 and spec.q_dim == 64 and spec.kv_dim == 32
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_SMALLTHINKER] == (31, 32, 33, 34)


# ---- the program against the reference --------------------------------------

def test_one_pass_prefill(params, want):
    cache = init_kv_cache(CFG, 1)
    # at this toy size a chunk is far longer than the sequence: no ring wraps
    assert cache.k.shape == (2, 1, 4, 96, 8) and cache.wk.shape == (6, 1, 4, 96, 8)
    lg, _ = forward(params, CFG, jnp.asarray(TOKS)[None], cache, jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - want["a"]).max() < TOL


def test_chunked_prefill_across_the_window_then_decoding_a_wrapped_ring(
        params, want, small_chunk):
    """Chunks of 16 at a window of 16 over rings of 32: the third chunk wraps
    the ring and every chunk's queries reach into the one before; then 19
    decode steps, each writing one slot of a ring that has wrapped."""
    cache = init_kv_cache(CFG, 1)
    assert cache.k.shape == (2, 1, 4, 96, 8) and cache.wk.shape == (6, 1, 4, 32, 8)
    errs, p = [], 0
    for n in (16, 16, 9):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[p:p + n])[None], cache,
                            jnp.int32(p))
        errs.append(np.abs(np.asarray(lg)[0] - want["a"][p:p + n]).max())
        p += n
    for i in range(p, 60):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[i:i + 1])[None], cache,
                            jnp.int32(i))
        errs.append(np.abs(np.asarray(lg)[0, 0] - want["a"][i]).max())
    assert max(errs) < TOL, errs


@pytest.mark.parametrize("wrong", ["rope_on_full", "window_plus_one",
                                   "router_after_norm", "silu", "softmax_all"])
def test_each_wrong_computation_fails_the_tolerance(want, wrong):
    """RoPE on a full layer, a window off by one, a router fed the normed
    input, SiLU for ReLU, a softmax over all 64 without renormalising: the
    reference with that one fault is further from the true one than ``TOL`` by
    four orders of magnitude, so the comparisons above would see each."""
    bad = ref.np_forward_smallthinker(want["np"], CFG, TOKS, wrong=wrong)
    assert np.abs(bad - want["a"]).max() > 0.1


def test_engine_chunked_prefill_equals_one_pass(params, want, monkeypatch):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    before = obs_metrics.ENGINE_PREFILL_CHUNKS._value  # other files' chunks
    one, _ = eng.prefill([int(t) for t in TOKS[:45]])
    assert obs_metrics.ENGINE_PREFILL_CHUNKS._value == before
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert eng2.cache.wk.shape[3] == 32 and eng2.cache.k.shape[3] == 96
    before = obs_metrics.ENGINE_PREFILL_CHUNKS._value
    chunked, stats = eng2.prefill([int(t) for t in TOKS[:45]])
    assert obs_metrics.ENGINE_PREFILL_CHUNKS._value - before == 3
    assert eng2.pos == 45 and stats.generation_ms > 0
    # two chunk calls of one shape and a tail bucket: two programs
    assert {k[1] for k in eng2._compiled_steps} == {(1, 16)}
    assert np.abs(chunked[0] - want["a"][44]).max() < TOL
    assert np.abs(chunked[0] - one[0]).max() < TOL
    for i in range(45, 60):
        lg, _ = eng2.decode_one(int(TOKS[i]))
    assert np.abs(lg[0] - want["a"][59]).max() < TOL


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_slot_path_chunks_mixed_step_pure_decode(params, want, paged, small_chunk):
    """Two rows with clocks of their own: chunks of 8 (row b's first has 5 real
    tokens), a mixed step, then pure-decode steps past the window and, on the
    contiguous slots, around the ring.  Pages out of order."""
    if paged:
        # a pool for the 2 full layers, and for the 6 window layers 2 slots'
        # rings of 9 pages (window 16 + 16 rows - 1, and one more): 36
        # positions a slot under sequences of 53 and 57, so the rings wrap
        cache = init_kv_pool(CFG, 40, 4, slots=2)
        assert cache.k.shape == (2, 40, 4, 4, 8)
        assert cache.wk.shape == (6, 2 * 9, 4, 4, 8)
        table = jnp.asarray(np.stack([
            np.random.RandomState(1).permutation(np.arange(1, 20)),
            np.arange(20, 39)]).astype(np.int32))
    else:
        cache, table = init_kv_cache(CFG, 2, 72), None
        assert cache.wk.shape == (6, 2, 4, 32, 8)
    srcs, wants = (TOKS, TOKS2), (want["a"], want["b"])
    done, errs = [0, 0], []

    def step(n_valid, t):
        nonlocal cache
        tk = np.zeros((2, t), np.int32)
        for r in range(2):
            tk[r, :n_valid[r]] = srcs[r][done[r]:done[r] + n_valid[r]]
        lg, cache = forward_slots(
            params, CFG, jnp.asarray(tk), cache, jnp.asarray(done, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), table)
        for r in range(2):
            done[r] += n_valid[r]
            errs.append(np.abs(np.asarray(lg)[r] - wants[r][done[r] - 1]).max())

    for nv in ([8, 5], [8, 8], [8, 8], [8, 8]):
        step(nv, 8)
    step([1, 8], 8)            # mixed: a decodes, b prefills
    for _ in range(20):
        step([1, 1], 1)        # pure decode
    assert done == [53, 57]
    assert max(errs) < TOL, errs


def test_verify_window_keeps_every_position(params, want):
    """``forward_slots_all`` (the speculative verify step) over 5 tokens past
    the window on a pool: every position's logits are the reference's."""
    pool = init_kv_pool(CFG, 12, 4, slots=1, max_pages=8)
    table = jnp.asarray(np.array([[3, 7, 1, 9, 5, 2, 8, 4]], np.int32))
    _, pool = forward_slots(params, CFG, jnp.asarray(TOKS[None, :24]), pool,
                            jnp.zeros((1,), jnp.int32), jnp.full((1,), 24, jnp.int32),
                            table)
    lg, _ = forward_slots_all(params, CFG, jnp.asarray(TOKS[None, 24:29]), pool,
                              jnp.full((1,), 24, jnp.int32),
                              jnp.full((1,), 5, jnp.int32), table)
    assert np.abs(np.asarray(lg)[0] - want["a"][24:29]).max() < TOL


def test_ragged_batch_rows_see_their_own_window(params, want, small_chunk):
    """Two left-padded prompts in one contiguous batch (``offsets``): each
    row's logits are what it reads alone; the pad slots are behind a floor."""
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    a, b = [int(t) for t in TOKS[:14]], [int(t) for t in TOKS2[:9]]
    lg, _ = eng.prefill_ragged([a, b])
    assert np.abs(lg[0] - want["a"][13]).max() < TOL
    assert np.abs(lg[1] - want["b"][8]).max() < TOL


def test_a_call_wider_than_the_rings_slack_is_refused_by_name(params, small_chunk):
    cache = init_kv_cache(CFG, 1)
    with pytest.raises(ValueError, match="does not fit a window layer's ring"):
        forward(params, CFG, jnp.zeros((1, 24), jnp.int32), cache, jnp.int32(0))


def test_ring_attention_is_the_windowed_softmax():
    """``ops/window.py`` alone: a ring of 8 after 21 positions, queries at
    19..20, a window of 5, against numpy over the whole history."""
    rng = np.random.RandomState(3)
    k_all = rng.standard_normal((1, 2, 21, 4)).astype(np.float32)
    v_all = rng.standard_normal((1, 2, 21, 4)).astype(np.float32)
    q = rng.standard_normal((1, 4, 2, 4)).astype(np.float32)
    rk = jnp.zeros((1, 1, 2, 8, 4)), jnp.zeros((1, 1, 2, 8, 4))
    for p in range(21):
        rk = window.ring_write(*rk, jnp.asarray(k_all[:, :, p:p + 1]),
                               jnp.asarray(v_all[:, :, p:p + 1]), jnp.int32(0),
                               jnp.asarray([p], jnp.int32))
    got = np.asarray(window.ring_attention(jnp.asarray(q), *rk, jnp.int32(0),
                                           jnp.asarray([19], jnp.int32), 5))
    for h in range(4):
        for i, p in enumerate((19, 20)):
            keys = slice(p - 4, p + 1)
            sc = k_all[0, h // 2, keys] @ q[0, h, i] / 2.0
            w = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[0, h, i], (w / w.sum()) @ v_all[0, h // 2, keys], atol=1e-5)
    assert window.window_pages(4096, 16, 16, 1024) == 258
    assert window.window_pages(4096, 1, 16, 64) == 64


# ---- the loader, the engine, the scheduler ------------------------------------

@pytest.fixture(scope="module")
def q40_file(tmp_path_factory, want):
    path = tmp_path_factory.mktemp("st") / "toy_q40.m"
    _write_model(path, want["np"], ftype=quants.Q40)
    return str(path)


def test_loader_packed_and_dense_agree_with_the_reference(q40_file):
    mf = mfile.MFile(q40_file)
    cfg, dense = load_params(mf, dtype=jnp.float32)
    assert cfg.window == 16 and cfg.hidden_act == mfile.ACT_RELU
    wanted = ref.np_forward_smallthinker(
        {k: np.asarray(v) for k, v in dense.items()}, cfg, TOKS)
    lg, _ = forward(dense, cfg, jnp.asarray(TOKS)[None], init_kv_cache(cfg, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - wanted).max() < TOL
    _, packed = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    assert packed["wqkv"].logical_nd == (96, 224 + 64)
    assert packed["up"].qpacked.shape[:2] == (8, 64)
    lg, _ = forward(packed, cfg.with_(quant_impl="xla"), jnp.asarray(TOKS)[None],
                    init_kv_cache(cfg, 1), jnp.int32(0))
    # bf16 rounding inside the Q40 matmuls, and where it flips a near-tied
    # expert a position reads tenths: the median, and few such positions
    worst = np.abs(np.asarray(lg)[0] - wanted).max(1) / wanted.std()
    assert np.median(worst) < 0.05 and (worst > 0.1).sum() <= 6, worst


@pytest.mark.parametrize("t", [1, 2, 4])
def test_a_decoded_block_on_the_chosen_launch_equals_the_loop(q40_file, t):
    """One stream's step of ``t`` rows past a prompt of 20, every packed matmul
    on the kernel (interpret mode): each row's six experts are one launch a
    matrix (``select-chosen``, the router's logits handed in from the layer's
    input), and the logits are those of the loop of one launch an expert on
    the XLA path (``select``): the same roundings, another order of float32
    sums (the scales are exact in bf16 times a nibble: a row alone on the
    kernel rounds no weight, the XLA path each, and here that is the same)."""
    cfg, packed = load_params(mfile.MFile(q40_file), dtype=jnp.float32,
                              keep_quantized=True)
    packed = bf16_exact_scales(packed)
    toks = jnp.asarray(TOKS)[None]
    _, cache = forward(packed, cfg.with_(quant_impl="xla"), toks[:, :20],
                       init_kv_cache(cfg, 1), jnp.int32(0))
    logits = {}
    for impl, path in (("pallas_interpret", "select-chosen"), ("xla", "select")):
        obs_dispatch.reset()
        lg, _ = forward(packed, cfg.with_(quant_impl=impl), toks[:, 20:20 + t],
                        cache, jnp.int32(20))
        assert {k for k in obs_dispatch.dispatches() if k.startswith("moe/")} == \
            {"moe/" + path}
        logits[impl] = np.asarray(lg)[0]
    assert logits["xla"].shape == (t, 128)
    assert np.abs(logits["pallas_interpret"] - logits["xla"]).max() < \
        1e-3 * logits["xla"].std()


def test_engine_scheduler_contiguous_and_paged_serve_the_same_tokens(
        q40_file, monkeypatch):
    """Greedy tokens through the slot scheduler, on contiguous slots and on the
    paged pool, are the one-stream engine's, token for token, past the window
    and around the rings; the cache's gauges read its own arrays."""
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    mf = mfile.MFile(q40_file)
    cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    solo = Engine(cfg, params, mesh=_mesh(), batch=1)
    per_token = cfg.n_layers * 2 * cfg.kv_dim * 4
    # what a token adds to a paged pool: the full layers' pages alone
    pool_token = cfg.n_full_layers * 2 * cfg.kv_dim * 4
    assert solo.kv_bytes_per_token == per_token
    tok = 2 * cfg.kv_dim * 4
    assert obs_metrics.KV_CACHE_BYTES._values == {
        ("full",): 2 * 96 * tok, ("window",): 6 * 32 * tok, ("conv",): 0,
        ("retention",): 0, ("ssm",): 0}
    p1, p2 = [5, 9, 2], [int(t) for t in TOKS[:21]]
    wanted = []
    for p in (p1, p2):
        solo.reset()
        wanted.append([t for t, _ in solo.generate_stream(
            p, len(p) + 40, temperature=0.0, chunk=5)][len(p):])
    for kw in (dict(), dict(kv_pages=2 * (cfg.seq_len // 4) + 1, kv_page_size=4)):
        eng = Engine(cfg, params, mesh=_mesh(), batch=2, **kw)
        assert eng.kv_bytes_per_token == (pool_token if kw else per_token)
        sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0, decode_burst=4)
        try:
            tickets = [sched.submit(p, 40, temperature=0.0) for p in (p1, p2)]
            outs = [list(t.tokens()) for t in tickets]
        finally:
            sched.close()
        assert outs == wanted, kw
    # a pool and a table per layer kind: 2 full layers behind the page
    # tables, 6 window layers in 2 slots' rings of 9 pages
    assert eng.cache.k.shape == (2, 49, 4, 4, 8) and eng.ring_pages == 9
    assert eng.cache.wk.shape == (6, 18, 4, 4, 8)
    pages = eng.read_pool_pages([1, 2])
    assert {k: v.shape for k, v in pages.items()} == {
        "pages.k": (2, 2, 4, 4, 8), "pages.v": (2, 2, 4, 4, 8)}
    assert sched.prefix_cache is None and not sched.preempt


@pytest.mark.parametrize("prompt_seed", [13, 29])
def test_prompt_lookup_decoding_matches_greedy(q40_file, monkeypatch, prompt_seed):
    """The verify path of the one-stream engine (``generate_pld``) rewinds
    past rejected tokens; their rows lie ahead of the live position in a ring
    and are overwritten: its tokens are plain greedy decoding's.

    The one-row and the verify program round differently (their logits lie
    up to 0.01-0.035 sigma apart on this toy), so the prompts are ones whose
    greedy stream keeps its top two apart on both: 0.67 sigma at the
    narrowest of the 24 tokens for seed 13 (the stream settles on one token
    after rejecting the prompt's continuation), 0.075 for seed 29 (two
    tokens alternate; narrower than what a wrong cache row moves, 0.17).
    ``TOKS[:20] * 2`` had a tie of 0.0014 sigma and failed one run in a few."""
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", SMALL_PRODUCT)
    mf = mfile.MFile(q40_file)
    cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    eng = Engine(cfg, params, mesh=_mesh(), batch=1)
    toks = np.random.RandomState(prompt_seed).randint(3, 128, (20,))
    prompt = [int(t) for t in toks] * 2
    plain = [t for t, _ in eng.generate_stream(prompt, len(prompt) + 24,
                                               temperature=0.0, chunk=4)]
    eng.reset()
    assert eng.generate_pld(prompt, len(prompt) + 24) == plain


def test_snapshot_round_trip_of_the_two_cache_kinds(params, tmp_path, small_chunk):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert set(eng._cache_arrays()) == {"cache.k", "cache.v", "cache.wk", "cache.wv"}
    first = [t for t, _ in eng.generate_stream([int(t) for t in TOKS[:40]], 45,
                                               temperature=0.0, chunk=3)]
    path = str(tmp_path / "e.snap")
    eng.snapshot(path)
    held = {n: np.asarray(a) for n, a in eng.cache.planes().items()}
    rest = [t for t, _ in eng.generate_stream([first[-1]], 9, temperature=0.0, chunk=3)]
    eng2 = Engine(CFG, params, mesh=_mesh(), batch=1)
    eng2.restore(path)
    for plane in ("k", "v", "wk", "wv"):
        np.testing.assert_array_equal(np.asarray(getattr(eng2.cache, plane)),
                                      held[plane])
    assert eng2.pos == 44
    again = [t for t, _ in eng2.generate_stream([first[-1]], 9, temperature=0.0, chunk=3)]
    assert again == rest  # the restored rings continue the stream
    other = Engine(CFG.with_(window=8), params, mesh=_mesh(), batch=1)
    assert other.config_fingerprint() != eng.config_fingerprint()


def test_prefill_chunk_is_a_rule_from_the_shapes():
    real = ModelConfig(arch=mfile.ARCH_SMALLTHINKER, dim=2560, hidden_dim=768,
                       n_layers=52, n_heads=28, n_kv_heads=4, n_experts=64,
                       n_active_experts=6, vocab_size=151936, seq_len=16384,
                       hidden_act=mfile.ACT_RELU, rope_theta=1.5e6, head_dim=128,
                       window=4096, window_period=4)
    assert real.prefill_chunk() == 512 and real.window_ring(16384) == 4608
    assert real.window_ring(2048) == 2048 and real.q_dim == 3584
    # the other configurations of the benchmark: a prompt of their cells'
    # lengths is one call, as before
    mistral = tiny_config(dim=4096, n_heads=32)
    assert mistral.prefill_chunk() == 32768
    olmoe = tiny_config(dim=2048, n_heads=16, n_experts=64, n_active_experts=8)
    assert olmoe.prefill_chunk() == 1024
    # the archs no cell runs on one stream: DeepSeek-V2's 160 experts of 5120,
    # Mixtral's 8 of 4096, Grok-1's 8 of 6144
    assert tiny_deepseek2(dim=5120, n_experts=160).prefill_chunk() == 128
    assert tiny_config(dim=4096, n_experts=8).prefill_chunk() == 4096
    assert tiny_config(dim=6144, n_heads=48, n_experts=8).prefill_chunk() == 2048


@pytest.mark.parametrize("cfg", [
    tiny_deepseek2(),
    tiny_config(arch=mfile.ARCH_MIXTRAL, n_experts=4, n_active_experts=2),
    tiny_config(arch=mfile.ARCH_GROK1, n_experts=4, n_active_experts=2),
    tiny_config(arch=mfile.ARCH_OLMOE, n_experts=4, n_active_experts=2),
    tiny_config(),
], ids=["deepseek2", "mixtral", "grok1", "olmoe", "llama"])
def test_every_arch_chunked_prefill_equals_one_pass(cfg, monkeypatch):
    """``Engine.prefill`` chunks by the rule for every architecture (MLA's
    latent cache at ``pos > 0``, the grouped choice, a dense FFN): 45 tokens
    as two chunks of 16 and a tail equal one call, and so does decoding on."""
    toks = [int(t) for t in TOKS[:45]]
    p = init_params(cfg, seed=3, scale=0.08)
    one_eng = Engine(cfg, p, mesh=_mesh(), batch=1)
    one, _ = one_eng.prefill(toks)
    assert {k[1] for k in one_eng._compiled_steps} == {(1, 64)}
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES",
                        4 * max(cfg.n_experts, 1) * cfg.dim * 16)
    assert cfg.prefill_chunk() == 16
    eng = Engine(cfg, p, mesh=_mesh(), batch=1)
    before = obs_metrics.ENGINE_PREFILL_CHUNKS._value
    chunked, _ = eng.prefill(toks)
    assert obs_metrics.ENGINE_PREFILL_CHUNKS._value - before == 3
    assert {k[1] for k in eng._compiled_steps} == {(1, 16)} and eng.pos == 45
    assert np.abs(chunked - one).max() < TOL
    a, _ = one_eng.decode_one(int(TOKS[45]))
    b, _ = eng.decode_one(int(TOKS[45]))
    assert np.abs(a - b).max() < TOL


# ---- tracing -------------------------------------------------------------------

_OP_NAME = re.compile(r"op_name=\"([^\"]+)\"")


@pytest.mark.parametrize("t", [1, 6])
def test_layer_kinds_are_named_under_attn_and_the_router_under_moe(params, t):
    from dllama_tpu.ops.scopes import PARTS, SCOPES
    # "conv" is LFM2's; Brumby's "state" and "recent" follow it
    assert PARTS["attn"][-5:-2] == ("window", "full", "conv")
    obs_dispatch.reset()
    hlo = jax.jit(lambda p, tk, c: forward(p, CFG, tk, c, jnp.int32(3))).lower(
        params, jnp.zeros((1, t), jnp.int32), init_kv_cache(CFG, 1)
    ).compile().as_text()
    parts = set()
    for name in _OP_NAME.findall(hlo):
        comps = name.split("/")
        at = max((i for i, c in enumerate(comps) if c in SCOPES), default=None)
        if at is not None:
            parts |= {(comps[at], c) for c in comps[at + 1:]}
    assert {("attn", "window"), ("attn", "full"), ("moe", "router"),
            ("moe", "experts")} <= parts
    counts = obs_dispatch.dispatches()
    assert counts.get("attn/window-walk") == 3  # one a window layer of the period
    assert ("moe/select" if t == 1 else "moe/dense") in counts


def test_cost_model_charges_a_stated_head_size_and_the_window():
    """SmallThinker's published sizes: the query and output projections are
    28 x 128 wide on a hidden size of 2560, a token takes 6 experts of 768,
    and at 7000 positions a decoded token scores and reads every position in
    13 layers and 4096 in 39."""
    from dllama_tpu.obs import cost as obs_cost
    m = obs_cost.CostModel(
        dim=2560, hidden_dim=768, n_layers=52, n_heads=28, n_kv_heads=4,
        vocab_size=151936, weight_codec="q40", kv_codec="kv_bfloat16",
        kv_el_bytes=2, n_experts=64, n_active_experts=6, head_dim=128,
        window=4096, window_period=4)
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert m.params_per_token == 52 * (attn + 6 * 3 * 2560 * 768)
    assert m.kv_pos_bytes() == 2 * 512 * 2 and m.pair_flops == 4 * 3584
    assert m.attn_flops(6999, 1) == 4 * 3584 * (13 * 7000 + 39 * 4096)
    assert m.kv_read_bytes(6999, 1, True) == (13 * 7000 + 39 * 4096) * 2048
    assert m.kv_read_bytes(100, 1, True) == 52 * 101 * 2048
    old = obs_cost.CostModel(dim=4096, hidden_dim=14336, n_layers=32, n_heads=32,
                             n_kv_heads=8, vocab_size=32768, kv_el_bytes=2)
    assert old.pair_flops == 4 * 4096 and old.attn_flops(99, 1) == 4 * 4096 * 32 * 100
    assert old.kv_read_bytes(99, 1, True) == 100 * 32 * 2 * 1024 * 2


def test_a_decoded_token_walks_the_full_planes_in_larger_blocks(params, want, monkeypatch):
    """A full layer's read of one decoded token is the live walk at
    ``DECODE_BLOCK`` keys a trip where that divides the cache (a block of 32
    over 96 positions here, so the walk takes one to three trips), a prompt's
    rows keep ``gqa_attention_at``: both are the reference's."""
    from dllama_tpu.models import windowed
    monkeypatch.setattr(windowed, "DECODE_BLOCK", 32)
    obs_dispatch.reset()
    cache = init_kv_cache(CFG, 1)
    lg, cache = forward(params, CFG, jnp.asarray(TOKS[:30])[None], cache, jnp.int32(0))
    assert "attn/one-shot" in obs_dispatch.dispatches()
    errs = [np.abs(np.asarray(lg)[0] - want["a"][:30]).max()]
    for i in range(30, 60):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[i:i + 1])[None], cache,
                            jnp.int32(i))
        errs.append(np.abs(np.asarray(lg)[0, 0] - want["a"][i]).max())
    assert "attn/live-walk" in obs_dispatch.dispatches()  # the decoded tokens' trips
    assert max(errs) < TOL, errs
