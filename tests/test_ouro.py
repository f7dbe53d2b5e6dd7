"""Ouro (``ARCH_OURO``): a looped model on the normal path of both engines
against the float32 whole-sequence reference (``reference_impl.np_forward_ouro``:
no cache, every pass over the whole sequence), seeded random weights at
``tiny_ouro()``: three weight sets run three times, so the pass count, the
layer count and 1 are three numbers and nine cache planes.

The norm weights are drawn 1 + N(0, 0.1): with all four of a layer's norms at
exactly 1 a swapped pair of them would compute the same function.
"""

from __future__ import annotations

import hashlib
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
from dllama_tpu.models.config import tiny_config, tiny_ouro
from dllama_tpu.models.params import init_params, load_params, quantize_matmuls
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool)
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime import snapshot as snapfmt
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.faults import FAULTS, injected
from dllama_tpu.runtime.scheduler import SlotScheduler
from dllama_tpu.runtime.spec import make_proposer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "converter"))

CFG = tiny_ouro()
L, U = CFG.n_layers, CFG.n_loops
N = 56
TOKS = np.random.RandomState(0).randint(3, 128, (N,)).astype(np.int32)
# float32 on both sides at matmul precision "highest": what is left is the order
# of float32 sums over nine block applications, 3e-6 of logits whose spread is
# 0.6.  The same program in bfloat16 is off by 2e-2 and more
# (``test_bfloat16_does_not_pass_the_float32_tolerance``) and the least of the
# wrong computations by 1.4 (``test_each_wrong_computation_is_seen``).
TOL = 3e-5
PAGE = 4


def _init(cfg=CFG, seed=3):
    p = init_params(cfg, seed=seed, scale=0.08)
    rng = np.random.RandomState(seed + 1)
    return {k: (jnp.asarray(1 + 0.1 * rng.standard_normal(v.shape), jnp.float32)
                if k.startswith("rms") else v) for k, v in p.items()}


@pytest.fixture(scope="module")
def params():
    return _init()


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_ouro(p, CFG, TOKS), "np": p}


@pytest.fixture(autouse=True)
def _highest():
    FAULTS.clear()
    with jax.default_matmul_precision("highest"):
        yield
    FAULTS.clear()


def _mesh():
    return make_mesh(tp=1, devices=jax.devices()[:1])


def _logits(p, toks):
    return ref.np_forward_ouro(p, CFG, np.asarray(toks, np.int32))


def _greedy_ok(want, prompt, out):
    greedy = _logits(want["np"], list(prompt) + list(out[:-1])).argmax(-1)
    return list(out) == greedy[len(prompt) - 1:].tolist()


def _spec(cfg=CFG, ftype=quants.F32, **kw):
    fields = dict(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=0, n_active_experts=0, vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, hidden_act=cfg.hidden_act,
        rope_theta=cfg.rope_theta, weights_ftype=ftype,
        norm_eps=cfg.norm_eps, loops=cfg.loops)
    fields.update(kw)
    return mfile.ModelSpec(**fields)


def _write_model(path, p, cfg=CFG, ftype=quants.F32):
    with mfile.MFileWriter(path, _spec(cfg, ftype=ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                x = p[{"token_embedding": "embedding"}.get(t.name, t.name)]
                x = x.T if t.name == "wcls" else x
            else:
                x = p[parts[-1]][int(parts[1])]
                x = x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format and what is derived from it ----------------------------------

def test_arch_id_header_key_and_round_trip(tmp_path, want):
    assert mfile.ARCH_OURO == 0xABCD09 and mfile.ARCH_NAMES[mfile.ARCH_OURO] == "ouro"
    assert mfile.ARCH_EXT_KEYS[mfile.ARCH_OURO] == (31, 40)
    assert mfile.KEY_MAX >= 40
    names = [t.name for t in mfile.tensor_plan(_spec())]
    assert names[-6:-2] == ["layers.2.rms_att", "layers.2.rms_ffn",
                            "layers.2.rms_moe", "layers.2.rms_ffn2"]
    path = str(tmp_path / "o.m")
    _write_model(path, want["np"])
    with mfile.MFile(path) as mf:
        assert mf.spec.loops == 3 and abs(mf.spec.norm_eps - 1e-6) < 1e-12
        cfg, p = load_params(mf, dtype=jnp.float32)
    assert (cfg.n_loops, cfg.n_cache_planes, cfg.post_block_norms,
            cfg.rope_interleaved) == (3, 9, True, False)
    for k, v in want["np"].items():
        assert np.array_equal(np.asarray(p[k]), v), k


def test_every_other_arch_runs_its_layers_once():
    for arch in mfile.ARCH_NAMES:
        if arch != mfile.ARCH_OURO:
            c = tiny_config(arch=arch)
            assert (c.n_loops, c.n_cache_planes) == (1, c.n_layers)


@pytest.mark.parametrize("kw,says", [
    (dict(loops=0), "an ouro file states it"),
    (dict(loops=65), "how many times its stack runs"),
    (dict(arch=mfile.ARCH_LLAMA), "key 40 .the passes of a looped stack. describes an ouro file"),
    (dict(n_experts=4, n_active_experts=2), "an ouro layer has a dense FFN"),
])
def test_header_rules_are_refused_by_name(kw, says):
    with pytest.raises(ArtifactError, match=says):
        mfile.validate_spec(_spec(**kw), "x.m")


def test_published_widths_give_the_issues_cache():
    """Ouro-2.6B: 48 weight sets, 4 passes, 16 kv heads of 128: a cached
    position is 192 planes of keys and values, 1,572,864 B in bfloat16, and a
    page of 16 positions 25,165,824 B."""
    c = tiny_ouro(dim=2048, hidden_dim=5632, n_layers=48, n_heads=16,
                  n_kv_heads=16, vocab_size=49152, seq_len=65536, loops=4)
    assert (c.n_loops, c.n_cache_planes, c.head_size) == (4, 192, 128)
    pool = jax.eval_shape(lambda: init_kv_pool(c, 392, 16, dtype=jnp.bfloat16))
    assert pool.k.shape == (192, 392, 16, 16, 128)
    per_pos = 2 * pool.k.dtype.itemsize * int(np.prod(pool.k.shape)) // (392 * 16)
    assert per_pos == 1_572_864 and per_pos * 16 == 25_165_824
    cache = jax.eval_shape(lambda: init_kv_cache(c, 1, 768, dtype=jnp.bfloat16))
    assert cache.k.shape == (192, 1, 16, 768, 128)


# ---- the contiguous cache ----------------------------------------------------

def test_prefill_then_decode_through_the_cache(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    assert eng.cache.k.shape[0] == U * L
    lg, _ = eng.prefill([int(t) for t in TOKS[:20]])
    assert np.abs(lg[0] - want["a"][19]).max() < TOL
    for i in range(20, 30):
        lg, _ = eng.decode_one(int(TOKS[i]))
        assert np.abs(lg[0] - want["a"][i]).max() < TOL, i


@pytest.mark.parametrize("product,chunk", [(4 * 16 * 64, 16), (4 * 32 * 64, 32),
                                           (config_mod.PREFILL_PRODUCT_BYTES, 56)])
def test_chunked_prefill_equals_one_pass_at_every_chunk_width(
        params, want, monkeypatch, product, chunk):
    """A prompt in chunks of 16 and of 32 with a ragged last chunk, and in one
    call: every pass of a later chunk attends over what the same pass of the
    earlier chunks wrote."""
    monkeypatch.setattr(config_mod, "PREFILL_PRODUCT_BYTES", product)
    assert min(CFG.prefill_chunk(), 56) == chunk
    eng = Engine(CFG, params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:53]])
    assert np.abs(lg[0] - want["a"][52]).max() < TOL
    lg, _ = eng.decode_one(int(TOKS[53]))
    assert np.abs(lg[0] - want["a"][53]).max() < TOL


def test_ragged_batch_matches_each_row_alone(params, want):
    eng = Engine(CFG, params, mesh=_mesh(), batch=2)
    prompts = [[int(t) for t in TOKS[:23]], [int(t) for t in TOKS[30:37]]]
    outs = eng.generate_batch(prompts, 23 + 12, temperature=0.0, chunk=4)
    for p, o in zip(prompts, outs):
        assert _greedy_ok(want, p, o[len(p):])


def test_bfloat16_does_not_pass_the_float32_tolerance(params, want):
    """The same program with bfloat16 activations and weights: a thousand
    times the float32 tolerance, so the tolerance tells the two apart."""
    c = CFG.with_(dtype=jnp.bfloat16)
    p = {k: (v if v.dtype == jnp.float32 and k.startswith("rms")
             else v.astype(jnp.bfloat16)) for k, v in params.items()}
    lg, _ = forward(p, c, jnp.asarray(TOKS[None, :20]), init_kv_cache(c, 1),
                    jnp.int32(0))
    assert np.abs(np.asarray(lg, np.float32)[0] - want["a"][:20]).max() > 100 * TOL


@pytest.mark.parametrize("wrong", ["read_pass0", "write_next", "no_loop_norm",
                                   "no_post_norm", "one_pass_short"])
def test_each_wrong_computation_is_seen(params, want, wrong):
    """Every pass reading pass 0's plane, a pass reading what the pass before
    it wrote (its own written to the next pass's plane), the final norm left
    out between passes, a closing norm left out, two passes run for three:
    each moves a logit by 1.4 and more of a spread of 0.6, and the program's
    logits are 3e-6 from the right ones."""
    kw = dict(passes=U - 1) if wrong == "one_pass_short" else dict(wrong=wrong)
    bad = ref.np_forward_ouro(want["np"], CFG, TOKS[:20], **kw)
    assert np.abs(bad - want["a"][:20]).max() > 1.0
    lg, _ = forward(params, CFG, jnp.asarray(TOKS[None, :20]),
                    init_kv_cache(CFG, 1), jnp.int32(0))
    assert np.abs(np.asarray(lg)[0] - want["a"][:20]).max() < TOL


def test_a_position_advanced_by_the_pass_is_the_same_function(want):
    """The sixth wrong computation the issue lists cannot be seen, and this is
    why: RoPE is relative, a pass's queries and keys are rotated by the same
    pass, and a pass attends over its own keys alone, so advancing every
    position of pass ``u`` by ``u`` leaves every score where it was.  (It would
    be seen the day a pass reads another pass's keys.)"""
    moved = ref.np_forward_ouro(want["np"], CFG, TOKS[:20], wrong="rope_by_pass")
    assert np.abs(moved - want["a"][:20]).max() < TOL


# ---- the slot programs on the paged pool -------------------------------------

def _row_tokens(r, lo, hi):
    return TOKS[(5 * r + np.arange(lo, hi)) % N]


def _table(b, pages, seed=7):
    """A permuted page table: slot ``r``'s logical page ``j`` lives anywhere in
    the pool but page 0 (the scratch page)."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(1 + rng.permutation(b * pages).reshape(b, pages), jnp.int32)


def _slot_state(params, hist, table):
    b = len(hist)
    cache = init_kv_pool(CFG, 1 + table.size, PAGE)
    pos = np.zeros((b,), np.int32)
    while (pos < hist).any():
        n = np.minimum(hist - pos, 16)
        tk = np.zeros((b, 16), np.int32)
        for r in range(b):
            tk[r, :n[r]] = _row_tokens(r, pos[r], pos[r] + n[r])
        _, cache = forward_slots(params, CFG, jnp.asarray(tk), cache,
                                 jnp.asarray(pos), jnp.asarray(n), table)
        pos = pos + n
    return cache


@pytest.mark.parametrize("buckets", [(), (16,)], ids=["unpacked", "packed"])
def test_one_step_with_rows_of_0_1_5_and_16_tokens(params, want, monkeypatch,
                                                   buckets):
    """A mixed step over a permuted page table: a slot that rides along, a
    decoding slot, a ragged last chunk and a whole chunk of a new tenant, each
    in all nine planes; the same packed (the row-local regions run 9 times a
    step) and over every row."""
    monkeypatch.setattr(packing, "BUCKETS", buckets)
    table = _table(4, 10)
    hist = np.asarray([21, 30, 17, 0], np.int32)
    cache = _slot_state(params, hist, table)
    assert cache.k.shape == (U * L, 41, PAGE, CFG.n_kv_heads, CFG.head_size)
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
    # the slot that rode along goes on from its own planes
    tk2 = np.zeros((4, 1), np.int32)
    tk2[0, 0] = _row_tokens(0, 21, 22)[0]
    lg, _ = forward_slots(params, CFG, jnp.asarray(tk2), cache,
                          jnp.asarray([21, 31, 22, 16], np.int32),
                          jnp.asarray([1, 0, 0, 0], np.int32), table)
    wanted = _logits(want["np"], _row_tokens(0, 0, 22))[-1]
    assert np.abs(np.asarray(lg)[0] - wanted).max() < TOL


def test_verify_step_keeps_every_position_and_a_rejected_draft(params, want):
    table = _table(1, 10)
    hist = np.asarray([18], np.int32)
    cache = _slot_state(params, hist, table)
    seq = _row_tokens(0, 0, 24)
    draft = np.asarray([[seq[18], seq[19], 9, 9, 9]], np.int32)
    lg, cache = forward_slots_all(params, CFG, jnp.asarray(draft), cache,
                                  jnp.asarray(hist), jnp.asarray([5], np.int32),
                                  table)
    full = _logits(want["np"], seq)
    assert np.abs(np.asarray(lg)[0, :2] - full[18:20]).max() < TOL
    lg, _ = forward_slots(params, CFG, jnp.asarray([[seq[20]]], np.int32), cache,
                          jnp.asarray([20], np.int32), jnp.asarray([1], np.int32),
                          table)
    assert np.abs(np.asarray(lg)[0] - full[20]).max() < TOL


# ---- the scheduler -----------------------------------------------------------

def _paged_engine(params, batch=2, kv_pages=None):
    per_slot = -(-CFG.seq_len // PAGE)
    return Engine(CFG, params, mesh=_mesh(), batch=batch,
                  kv_pages=kv_pages or batch * per_slot + 1, kv_page_size=PAGE)


PROMPTS = [[int(t) for t in TOKS[a:a + n]]
           for a, n in ((0, 21), (10, 7), (3, 33), (20, 13), (7, 16), (30, 5),
                        (2, 26), (40, 9))]


def test_the_scheduler_serves_eight_requests_token_for_token(params, want):
    """Eight requests on three slots of a paged pool, their lengths apart:
    admission, retirement and slots reused over what the last tenant left in
    all nine planes; every stream is the reference's greedy stream."""
    eng = _paged_engine(params, batch=3)
    assert eng.kv_bytes_per_token == U * L * 2 * CFG.kv_dim * 4
    sched = SlotScheduler(eng, prefill_chunk=16, decode_burst=4)
    try:
        tickets = [sched.submit(p, max_new=6 + 2 * i) for i, p in enumerate(PROMPTS)]
        for p, t in zip(PROMPTS, tickets):
            assert _greedy_ok(want, p, list(t.tokens()))
        sched.pool.check()
    finally:
        sched.close()


def _serve(want, sched, prompts, max_new=10):
    try:
        tickets = [sched.submit(list(p), max_new=max_new, temperature=0.0)
                   for p in prompts]
        for p, t in zip(prompts, tickets):
            assert _greedy_ok(want, p, list(t.tokens())), p
        sched.pool.check()
        return sched.occupancy()
    finally:
        sched.close(timeout=60)


def test_prefix_reuse_binds_every_plane_of_a_shared_page(params, want):
    """Two prompts share four pages: the second binds the first's pages, which
    hold all nine planes of those positions, and decodes the reference's
    stream."""
    shared = [int(t) for t in TOKS[:4 * PAGE]]
    reused0 = obs_metrics.PREFIX_TOKENS_REUSED.value
    sched = SlotScheduler(_paged_engine(params), prefill_chunk=4, prefix_reuse=True)
    try:
        for tail in ([3, 1], [9, 4, 11]):
            t = sched.submit(shared + tail, max_new=8, temperature=0.0)
            assert _greedy_ok(want, shared + tail, list(t.tokens()))
    finally:
        sched.close()
    assert obs_metrics.PREFIX_TOKENS_REUSED.value - reused0 == 4 * PAGE


@pytest.mark.parametrize("host_pool_mb,counter", [(8, "spill"), (0, "preempt")])
def test_a_pool_under_pressure_spills_or_preempts_and_resumes(params, want,
                                                              host_pool_mb,
                                                              counter):
    """A pool of 9 usable pages against 20 of full-reservation demand: a
    victim's pages (nine planes each) go to host memory and come back, or,
    with no host pool, its slot is preempted and its request resumed; every
    stream is still the reference's."""
    def preempted():
        return sum((obs_metrics.snapshot_json().get("sched_preemptions") or {}).values())

    spilled0, pre0 = obs_metrics.KV_PAGES_SPILLED.value, preempted()
    sched = SlotScheduler(_paged_engine(params, kv_pages=10), prefill_chunk=8,
                          decode_burst=4, kv_reserve="optimistic",
                          spill_headroom=4, host_pool_mb=host_pool_mb)
    occ = _serve(want, sched, ([5, 9, 2], [7, 3, 11, 4, 6, 1, 8], [2, 4, 6]),
                 max_new=24)
    if counter == "spill":
        assert obs_metrics.KV_PAGES_SPILLED.value > spilled0
    else:
        assert preempted() > pre0
    assert occ["kv_pages_free"] == occ["kv_pages_total"]


def test_a_request_handed_off_resumes_in_another_pool(params, want):
    """A request exported mid-decode (its pages' nine planes in the record)
    and imported into a second scheduler: replayed and resumed tokens are the
    reference's stream; the record's fingerprint carries the pass count."""
    scheds = [SlotScheduler(_paged_engine(params), prefill_chunk=4,
                            max_wait_ms=20.0, decode_burst=4) for _ in range(2)]
    try:
        prompt = PROMPTS[1]
        with injected("engine.device_step=delay:0.05"):
            t = scheds[0].submit(prompt, 20, temperature=0.0)
            it = t.tokens()
            for _ in range(4):
                next(it)
            records = scheds[0].handoff_export_all()
        list(it)
        meta, _ = snapfmt.loads_request(records[t.rid])
        replayed = [int(x) for x in meta["extra"]["completion"]]
        t2, _ = scheds[1].import_request(records[t.rid])
        assert _greedy_ok(want, prompt, replayed + list(t2.tokens()))
        other = Engine(CFG.with_(loops=2), {**params}, mesh=_mesh(), batch=2,
                       kv_pages=33, kv_page_size=PAGE)
        assert other.handoff_fingerprint() != scheds[0].engine.handoff_fingerprint()
    finally:
        for s in scheds:
            s.close()


def test_prompt_lookup_drafts_are_verified_over_every_pass(params, want):
    """``--spec pld``: a verify block writes its rows into all nine planes and a
    rejected draft's rows are overwritten; the stream is the reference's."""
    eng = _paged_engine(params)
    sched = SlotScheduler(eng, prefill_chunk=4, decode_burst=6,
                          spec=make_proposer("pld", eng), spec_k=4)
    rep = [int(t) for t in TOKS[:6]] * 3
    _serve(want, sched, (rep, PROMPTS[1]), max_new=14)


# ---- the loader, the gauges, the ledger, the refusals ------------------------

def test_loader_packed_agrees_with_the_reference(tmp_path, want):
    """A Q40 file through the normal loader (``wqkv`` and ``w13`` joined, four
    norm stacks): prefill and decode against the reference of the dequantized
    weights.  The packed path rounds each matmul's activation to bfloat16: a
    few hundredths of the logits' spread over nine blocks; a stack in the
    wrong order or a swapped norm reads 1 and more."""
    path = str(tmp_path / "q.m")
    _write_model(path, want["np"], ftype=quants.Q40)
    with mfile.MFile(path) as mf:
        cfg, p = load_params(mf, dtype=jnp.float32, keep_quantized=True)
        _, dense = load_params(mf, dtype=jnp.float32, keep_quantized=False)
    assert "wqkv" in p and "w13" in p and p["rms_ffn2"].shape == (L, CFG.dim)
    deq = {k: np.asarray(v, np.float32) for k, v in dense.items()}
    wanted = ref.np_forward_ouro(deq, cfg, TOKS[:30])
    eng = Engine(cfg.with_(quant_impl="xla"), p, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:29]])
    assert np.abs(lg[0] - wanted[28]).max() < 0.1 * wanted[28].std()
    lg, _ = eng.decode_one(int(TOKS[29]))
    assert np.abs(lg[0] - wanted[29]).max() < 0.1 * wanted[29].std()


def test_the_gauges_and_the_ledger_name_the_loop(params):
    obs_dispatch.reset()
    eng = _paged_engine(params)
    assert obs_metrics.MODEL_LOOP_PASSES.json_value() == U
    per_token = U * L * 2 * CFG.kv_dim * 4
    assert eng.kv_bytes_per_token == per_token
    assert obs_metrics.KV_BYTES_PER_TOKEN.json_value() == per_token
    assert obs_metrics.KV_CACHE_BYTES.json_value()["full"] == \
        per_token * eng.kv_pages * PAGE
    Engine(CFG, params, mesh=_mesh(), batch=1).prefill([5, 6, 7])
    # one a compiled program (the prompt's), whatever the pass count
    assert obs_dispatch.dispatches()["loop/scan"] == 1
    obs_dispatch.reset()
    Engine(tiny_config(), init_params(tiny_config()), mesh=_mesh(), batch=1)
    assert obs_metrics.MODEL_LOOP_PASSES.json_value() == 1


# ---- the converter -----------------------------------------------------------

OURO_HF = dict(
    model_type="ouro", hidden_size=64, intermediate_size=96, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, vocab_size=128,
    max_position_embeddings=64, hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=1000000, rope_scaling=None, use_sliding_window=False,
    sliding_window=None, tie_word_embeddings=False, total_ut_steps=3,
    early_exit_threshold=1)


def _hf_checkpoint(p):
    """A toy checkpoint under the names the converter ASSUMES (Llama's, with
    ``input_layernorm_2`` / ``post_attention_layernorm_2`` and an exit gate):
    unverified until the published files are here."""
    import convert_hf
    hf = {"model.embed_tokens.weight": p["embedding"],
          "model.norm.weight": p["rms_final"], "lm_head.weight": p["wcls"].T,
          "model.early_exit_gate.weight": np.ones((1, CFG.dim), np.float32),
          "model.early_exit_gate.bias": np.zeros((1,), np.float32)}
    for i in range(L):
        for ours, theirs in convert_hf._OURO_LEAVES.items():
            x = p[ours][i]
            hf[f"model.layers.{i}.{theirs}.weight"] = x.T if x.ndim == 2 else x
    return {k: np.ascontiguousarray(v, np.float32) for k, v in hf.items()}


def test_convert_round_trip_and_the_logits(tmp_path, want, capsys):
    from safetensors.numpy import save_file

    import convert_hf

    p = want["np"]
    (tmp_path / "config.json").write_text(json.dumps(OURO_HF))
    save_file(_hf_checkpoint(p), str(tmp_path / "model.safetensors"))
    out = str(tmp_path / "ouro.m")
    convert_hf.convert(str(tmp_path), quants.F32, out)
    assert "skipping 2 early_exit_gate.* tensors" in capsys.readouterr().out
    mf = mfile.MFile(out)
    assert (mf.spec.arch, mf.spec.loops) == (mfile.ARCH_OURO, 3)
    got_cfg, params = load_params(mf)
    assert got_cfg.with_(dtype=jnp.float32, norm_eps=1e-6) == CFG
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32), p[k], err_msg=k)
    eng = Engine(got_cfg.with_(dtype=jnp.float32), params, mesh=_mesh(), batch=1)
    lg, _ = eng.prefill([int(t) for t in TOKS[:30]])
    assert np.abs(lg[0] - want["a"][29]).max() < TOL


@pytest.mark.parametrize("key,value,says", [
    ("early_exit_threshold", 0.5, "the exit gate is not computed"),
    ("use_sliding_window", True, "are not part of this block"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "are not part of this block"),
    ("tie_word_embeddings", True, "are not part of this block"),
    ("head_dim", 32, "is not hidden_size"),
])
def test_convert_refuses_what_the_file_cannot_carry(tmp_path, key, value, says):
    import convert_hf

    (tmp_path / "config.json").write_text(json.dumps(dict(OURO_HF, **{key: value})))
    with pytest.raises(SystemExit, match=says):
        convert_hf.load_spec(str(tmp_path), quants.F32)


# ---- no other arch pays for the loop -----------------------------------------

# sha256 of str(jax.make_jaxpr(...)) on the parent of this PR (148592e), at
# matmul precision "highest" as every test of this file runs, of the toy decode
# step and slot programs of the three paths run_blocks' own body
# serves: what is shared is shared, and an arch that runs its layers once traces
# to the program it had
PARENT_JAXPRS = {
    "llama/dense/decode": "656948fc5099e349",
    "llama/dense/forward_slots/t1": "2ff7339e4a1f337a",
    "llama/dense/forward_slots/t4": "63f58363a4ddef7a",
    "llama/dense/forward_slots_all/t3": "42fe582a09e2d206",
    "llama/q40/decode": "0eac039cfad6a916",
    "llama/q40/forward_slots/t1": "c0e7ce22f24e4e0d",
    "llama/q40/forward_slots/t4": "f739f1739d736dde",
    "llama/q40/forward_slots_all/t3": "4fc82cdf3662cd14",
    "olmoe/dense/decode": "3764303ee91ba650",
    "olmoe/dense/forward_slots/t1": "7ec06f93b112bf9c",
    "olmoe/dense/forward_slots/t4": "e6f112c977cad5c7",
    "olmoe/dense/forward_slots_all/t3": "26c3064bab836fe3",
    "olmoe/q40/decode": "e9603b6ec31bfc82",
    "olmoe/q40/forward_slots/t1": "c81c92f6e00ac5c9",
    "olmoe/q40/forward_slots/t4": "430ced292376743f",
    "olmoe/q40/forward_slots_all/t3": "14d9d6338ea4fd49",
    "grok1/dense/decode": "eab6d124748a199e",
    "grok1/dense/forward_slots/t1": "2806a238ce921c53",
    "grok1/dense/forward_slots/t4": "d3291528887e4155",
    "grok1/dense/forward_slots_all/t3": "d04c6541b5f5b60f",
    "grok1/q40/decode": "578f754e1e9374ce",
    "grok1/q40/forward_slots/t1": "e6857b106c2402a9",
    "grok1/q40/forward_slots/t4": "95d13a5072c202ef",
    "grok1/q40/forward_slots_all/t3": "a37fee3c50778c43",
}
OLDER = {
    "llama": dict(arch=mfile.ARCH_LLAMA),
    "olmoe": dict(arch=mfile.ARCH_OLMOE, n_experts=4, n_active_experts=2),
    "grok1": dict(arch=mfile.ARCH_GROK1, n_experts=4, n_active_experts=2,
                  hidden_act=mfile.ACT_GELU),
}


@pytest.mark.parametrize("case", sorted(PARENT_JAXPRS))
def test_older_programs_are_the_parents(case):
    name, weights, *prog = case.split("/")
    cfg = tiny_config(seq_len=64, **OLDER[name])
    p = init_params(cfg, seed=1)
    if weights == "q40":
        p = quantize_matmuls({k: np.asarray(v) for k, v in p.items()}, cfg)
        cfg = cfg.with_(quant_impl="xla")
    if prog == ["decode"]:
        jaxpr = jax.make_jaxpr(lambda p, t, ca, pos: forward(p, cfg, t, ca, pos))(
            p, jnp.zeros((1, 1), jnp.int32), init_kv_cache(cfg, 1), jnp.int32(0))
    else:
        fn = {"forward_slots": forward_slots,
              "forward_slots_all": forward_slots_all}[prog[0]]
        jaxpr = jax.make_jaxpr(
            lambda p, tk, ca, pr, nv, tb: fn(p, cfg, tk, ca, pr, nv, tb))(
            p, jnp.zeros((2, int(prog[1][1:])), jnp.int32), init_kv_pool(cfg, 9, 4),
            jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.zeros((2, 16), jnp.int32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == PARENT_JAXPRS[case]


def test_the_looped_program_holds_one_layer_body(params):
    """The pass is an outer scan around the one scan over layers: the traced
    step has two nested scans and one attention body whatever the pass count,
    not ``n_loops`` copies."""
    def trace(cfg, p):
        return str(jax.make_jaxpr(lambda p, t, ca, pos: forward(p, cfg, t, ca, pos))(
            p, jnp.zeros((1, 1), jnp.int32), init_kv_cache(cfg, 1), jnp.int32(0)))

    three, five = trace(CFG, params), trace(CFG.with_(loops=5), params)
    assert three.count("dot_general") == five.count("dot_general")
    assert three.count(" scan[") == 2
