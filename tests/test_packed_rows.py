"""The slot step at ``t > 1`` runs its row-local regions over the rows that hold
a token (``dllama_tpu/models/packing.py``, PR 42).

CPU, toy configurations of the four block kinds (dense, MoE, MLA, windowed;
SmallThinker besides, whose router reads the layer's input): the packed step
against the step forced to every row (``packing.BUCKETS`` emptied, which is
the parent's program to the letter: its jaxpr hash is pinned), on a pool that
already holds eight positions a slot: greedy tokens, logits, and the pool at
every valid position (at 2 and 4 tokens a slot, where the shipped list has no
bucket under the step's rows, under a list of one 24-row bucket); the issue's
longer list in one program; the verify forward; the bucket rule as a table, on the
device and in the host's mirror; the plan's two index lists; and the programs
that must not have moved: the ``t == 1`` slot step and a one-stream decode
step, hash-equal to the parent's.
"""

import contextlib
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.io import mfile
from dllama_tpu.models import packing
from dllama_tpu.models.config import (tiny_config, tiny_deepseek2,
                                      tiny_exaone_moe, tiny_smallthinker)
from dllama_tpu.models.params import init_params, quantize_matmuls
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool)
from dllama_tpu.obs import metrics as obs_metrics, trace as obs_trace
from dllama_tpu.parallel.mesh import active_mesh, make_mesh
from dllama_tpu.runtime.decode_loop import slot_chunk

B, PS, CTX = 16, 4, 8      # slots, page size, positions a slot holds already
KINDS = {
    "dense": lambda: tiny_config(),
    "moe": lambda: tiny_config(arch=mfile.ARCH_OLMOE, n_experts=8,
                               n_active_experts=2),
    "mla": tiny_deepseek2,
    "windowed": tiny_exaone_moe,
    "smallthinker": tiny_smallthinker,
}
# logits of the toys spread over ~0.1-0.6; a region at another row count may
# sum in another order, nothing else differs
TOL = 2e-5


@contextlib.contextmanager
def bucket_list(sizes):
    """Programs traced inside see ``sizes`` as the list of row buckets."""
    kept, packing.BUCKETS = packing.BUCKETS, tuple(sizes)
    try:
        yield
    finally:
        packing.BUCKETS = kept


def every_row():
    """The step forced to ``R = b * t``: no bucket lies under anything."""
    return bucket_list(())


def packed_list(t):
    """The list a packed case of ``t`` tokens a slot is traced under: the
    shipped one where it has a bucket under ``B * t`` rows, else (16 slots of
    2 or 4 tokens) one of 24 rows, so that every case packs."""
    return packing.BUCKETS if len(packing.buckets(B * t)) > 1 else (24,)


@functools.lru_cache(maxsize=None)
def _model(kind):
    # seed 4: with seed 3 one row of the MLA toy sits on a routing tie (a
    # chosen expert flips on the last bit of a sum and moves its logits by
    # 0.006, where every other row is equal to the bit)
    cfg = KINDS[kind]().with_(quant_impl="xla")
    return cfg, quantize_matmuls(init_params(cfg, seed=4, scale=0.08), cfg)


def _pool(cfg):
    maxp = cfg.seq_len // PS
    kw = dict(slots=B, max_pages=maxp) if cfg.window else {}
    pool = init_kv_pool(cfg, 1 + B * maxp, PS, **kw)
    table = 1 + np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    return pool, jnp.asarray(table)


@functools.lru_cache(maxsize=None)
def _step(kind, t, packed, every=False):
    """The jitted slot forward of ``kind`` at ``t`` tokens a slot; traced
    under the bucket list it is asked for."""
    cfg, _ = _model(kind)
    fwd = forward_slots_all if every else forward_slots
    fn = jax.jit(lambda p, tok, c, pr, nv, tab: fwd(p, cfg, tok, c, pr, nv, tab))

    def call(*args):
        with bucket_list(packed_list(t)) if packed else every_row():
            return fn(*args)
    return call


@functools.lru_cache(maxsize=None)
def _filled(kind):
    """A pool in which every slot holds ``CTX`` positions (written by the step
    that runs every row), its page table, and the tokens that went in."""
    cfg, params = _model(kind)
    pool, table = _pool(cfg)
    toks = np.random.RandomState(11).randint(3, cfg.vocab_size, (B, CTX))
    _, pool = _step(kind, CTX, False)(
        params, jnp.asarray(toks, jnp.int32), pool, jnp.zeros((B,), jnp.int32),
        jnp.full((B,), CTX, jnp.int32), table)
    return jax.tree.map(np.asarray, pool), table


def _n_valid(t, prefilling, seed):
    """``prefilling`` slots of B feed a chunk (full, but one of them ragged),
    the others one token."""
    rng = np.random.RandomState(seed)
    nv = np.ones((B,), np.int32)
    who = rng.permutation(B)[:prefilling]
    nv[who] = t
    if prefilling > 1:
        nv[who[0]] = max(t // 2 + 1, 2)
    return nv


def _valid_cells(cfg, pool, table, nv):
    """Every plane's values at the positions this step wrote a token to."""
    out = {}
    table = np.asarray(table)
    planes = {name: np.asarray(getattr(pool, name))
              for name in (("k", "v", "wk", "wv") if cfg.window else ("k", "v"))}
    for r in range(B):
        for j in range(int(nv[r])):
            p = CTX + j
            page, off = table[r, p // PS], p % PS
            for name in ("k", "v"):
                out[name, r, j] = planes[name][:, page, off]
            if cfg.window:
                ring = planes["wk"].shape[1] // B
                wpage = r * ring + (p // PS) % ring
                for name in ("wk", "wv"):
                    out[name, r, j] = planes[name][:, wpage, off]
    return out


CASES = [(kind, t, k) for kind in ("dense", "moe", "mla", "windowed")
         for t in (2, 4, 8, 16) for k in (0, 1, 3, 8, 16)]
CASES += [("smallthinker", 16, 3), ("smallthinker", 8, 1)]


@pytest.mark.parametrize("kind,t,prefilling", CASES,
                         ids=lambda v: str(v))
def test_packed_step_equals_the_step_over_every_row(kind, t, prefilling):
    cfg, params = _model(kind)
    pool0, table = _filled(kind)
    nv = _n_valid(t, prefilling, seed=t * 31 + prefilling)
    toks = np.random.RandomState(t + prefilling).randint(3, cfg.vocab_size, (B, t))
    args = (jnp.asarray(toks, jnp.int32), jax.tree.map(jnp.asarray, pool0),
            jnp.full((B,), CTX, jnp.int32), jnp.asarray(nv), table)
    got, pool_p = _step(kind, t, True)(params, *args)
    want, pool_e = _step(kind, t, False)(params, *args)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL * np.abs(want).max()
                               + TOL)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    cells_p, cells_e = (_valid_cells(cfg, p, table, nv) for p in (pool_p, pool_e))
    for key, w in cells_e.items():
        np.testing.assert_allclose(cells_p[key], w, rtol=0,
                                   atol=TOL * max(np.abs(w).max(), 1.0),
                                   err_msg=str(key))


@pytest.mark.parametrize("prefilling,run", [(1, 32), (3, 64), (5, 128), (8, 256)])
def test_a_longer_list_switches_between_its_bodies(prefilling, run):
    """The issue's list (32, 64, 128; PERF.md section 6 has why 64 alone
    ships): one program, four bodies a region, the step's own chosen on the
    device."""
    sizes = (32, 64, 128)
    cfg, params = _model("dense")
    pool0, table = _filled("dense")
    nv = _n_valid(16, prefilling, seed=prefilling)
    with bucket_list(sizes):
        assert packing.run_rows(int(nv.sum()), B, 16) == run
    toks = np.random.RandomState(prefilling).randint(3, cfg.vocab_size, (B, 16))
    args = (jnp.asarray(toks, jnp.int32), jax.tree.map(jnp.asarray, pool0),
            jnp.full((B,), CTX, jnp.int32), jnp.asarray(nv), table)
    with bucket_list(sizes):
        got, _ = _longer_list_step()(params, *args)
    want, _ = _step("dense", 16, False)(params, *args)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL * np.abs(want).max() + TOL)
    assert (got.argmax(-1) == want.argmax(-1)).all()


@functools.lru_cache(maxsize=None)
def _longer_list_step():
    cfg, _ = _model("dense")
    return jax.jit(lambda p, tok, c, pr, nv, tab: forward_slots(
        p, cfg, tok, c, pr, nv, tab))


@pytest.mark.parametrize("kind", ["dense", "moe", "mla", "windowed"])
def test_verify_forward_packed_equals_every_row(kind):
    """``forward_slots_all`` at a verify width: some rows carry proposals,
    the others ride along with one token; every valid position's logits."""
    cfg, params = _model(kind)
    pool0, table = _filled(kind)
    t = 5
    nv = np.ones((B,), np.int32)
    nv[[1, 6, 7, 12]] = [5, 3, 5, 2]
    toks = np.random.RandomState(5).randint(3, cfg.vocab_size, (B, t))
    args = (jnp.asarray(toks, jnp.int32), jax.tree.map(jnp.asarray, pool0),
            jnp.full((B,), CTX, jnp.int32), jnp.asarray(nv), table)
    assert packing.run_rows(int(nv.sum()), B, t) == 64
    got, _ = _step(kind, t, True, every=True)(params, *args)
    want, _ = _step(kind, t, False, every=True)(params, *args)
    valid = np.arange(t)[None, :] < nv[:, None]
    got, want = np.asarray(got)[valid], np.asarray(want)[valid]
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL * np.abs(want).max() + TOL)
    assert (got.argmax(-1) == want.argmax(-1)).all()


# ---- the bucket rule ------------------------------------------------------

# (slots, t, valid rows) -> rows run
RULE = [
    (16, 16, 16, 64), (16, 16, 31, 64), (16, 16, 32, 64), (16, 16, 33, 64),
    (16, 16, 46, 64), (16, 16, 64, 64), (16, 16, 65, 256), (16, 16, 128, 256),
    (16, 16, 129, 256), (16, 16, 256, 256), (16, 8, 23, 64), (16, 8, 65, 128),
    (16, 8, 128, 128), (16, 4, 19, 64), (16, 4, 33, 64), (16, 2, 17, 32),
    (16, 2, 32, 32), (16, 1, 16, 16), (8, 16, 23, 64), (8, 16, 65, 128),
    (4, 16, 19, 64), (4, 16, 34, 64), (2, 16, 17, 32), (1, 16, 16, 16),
]


@pytest.mark.parametrize("b,t,valid,run", RULE, ids=lambda v: str(v))
def test_bucket_rule_on_the_device_is_the_hosts_mirror(b, t, valid, run):
    assert packing.run_rows(valid, b, t) == run
    # some n_valid with that sum: full rows first, then a ragged one, then ones
    nv = np.zeros((b,), np.int32)
    left = valid
    for r in range(b):
        nv[r] = min(t, max(left - (b - 1 - r), 0))
        left -= nv[r]
    assert nv.sum() == valid
    plan = packing.plan(jnp.asarray(nv), b, t)
    sizes = packing.buckets(b * t)
    if plan is None:
        assert run == b * t and (t == 1 or len(sizes) == 1)
        return
    assert sizes[int(plan.bucket)] == run
    # the two lists are inverse on the valid rows, in slot order
    src, inv, ok = (np.asarray(a) for a in (plan.src, plan.inv, plan.valid))
    want_src = [r * t + j for r in range(b) for j in range(nv[r])]
    assert src[:valid].tolist() == want_src and (src[valid:] == 0).all()
    assert ok.sum() == valid and (inv[~ok] == 0).all()
    assert (src[inv[ok]] == np.flatnonzero(ok)).all()


def test_a_mesh_and_one_token_have_no_plan():
    nv = jnp.ones((16,), jnp.int32)
    assert packing.plan(nv, 16, 1) is None
    assert packing.plan(nv, 16, 16) is not None
    if len(jax.devices()) >= 2:
        mesh = make_mesh(tp=2, devices=jax.devices()[:2])
        with active_mesh(mesh):
            assert packing.plan(nv, 16, 16) is None
        assert packing.run_rows(31, 16, 16, mesh) == 256
    one = make_mesh(tp=1, devices=jax.devices()[:1])
    with active_mesh(one):
        assert packing.plan(nv, 16, 16) is not None
    assert packing.run_rows(31, 16, 16, one) == 64


def test_over_hands_back_slot_layout_with_zeros_where_no_token_is():
    b, t, d = 8, 16, 8
    nv = np.array([16, 1, 0, 5, 1, 1, 0, 2], np.int32)
    x = np.random.RandomState(0).standard_normal((b, t, d)).astype(np.float32)
    seen = []

    def fn(rows):
        seen.append(rows.shape)
        return rows * 2.0, rows[..., :3].astype(jnp.bfloat16)

    plan = packing.plan(jnp.asarray(nv), b, t)
    twice, cut = packing.over(plan, "qkv", fn, jnp.asarray(x))
    valid = np.arange(t)[None, :] < nv[:, None]
    want = np.where(valid[..., None], x * 2.0, 0.0)
    np.testing.assert_array_equal(np.asarray(twice), want)
    assert cut.shape == (b, t, 3) and cut.dtype == jnp.bfloat16
    assert not np.asarray(cut, np.float32)[~valid].any()
    # one body a bucket: 64 rows, then every row in place
    assert seen == [(64, d), (b * t, d)]
    same = packing.over(None, "qkv", lambda r: r + 1.0, jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(same), x + 1.0)


# ---- the programs that did not move ---------------------------------------

# sha256 of str(jax.make_jaxpr(...)) as PR 41 left them: the paged slot step
# at one token a slot (16 slots), the same step at 16 tokens a slot, and a
# one-stream decode step on the contiguous cache
PARENT_JAXPRS = {
    ("dense", "slot_t1"): "ed402cd09ed38a35",
    ("dense", "slot_t16"): "4c41e206b5bcb6dc",
    ("dense", "decode"): "325cf21cae220ed6",
    ("moe", "slot_t1"): "95458c57e16c54bd",
    ("moe", "slot_t16"): "27c4454d8cc06b2c",
    ("moe", "decode"): "9aa7958ea1187ffa",
    ("mla", "slot_t1"): "2afec353bd280373",
    ("mla", "slot_t16"): "04400fe064b6e6c1",
    ("mla", "decode"): "9358047e79f9a447",
    ("windowed", "slot_t1"): "f57e9ccee5e4b728",
    ("windowed", "slot_t16"): "107d00543b422c42",
    ("windowed", "decode"): "efa75c9419887a22",
    ("smallthinker", "slot_t1"): "efc0bae8ea8d4af9",
    ("smallthinker", "slot_t16"): "1260e6b6fefe257e",
    ("smallthinker", "decode"): "66a706e0b5f11871",
}


def _jaxpr_hash(kind, what):
    cfg = KINDS[kind]().with_(quant_impl="xla")
    params = quantize_matmuls(init_params(cfg, seed=1), cfg)
    if what == "decode":
        jaxpr = jax.make_jaxpr(
            lambda p, c, tok, pos: forward(p, cfg, tok, c, pos))(
            params, init_kv_cache(cfg, 1), jnp.zeros((1, 1), jnp.int32),
            jnp.int32(3))
    else:
        t = int(what[len("slot_t"):])
        maxp = cfg.seq_len // PS
        kw = dict(slots=B, max_pages=maxp) if cfg.window else {}
        pool = init_kv_pool(cfg, 80, PS, **kw)
        z = jnp.zeros((B,), jnp.float32)
        zi = jnp.zeros((B,), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, c, tok, pr, nv, k, tm, tp, tk, tab: slot_chunk(
                p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
                page_table=tab))(
            params, pool, jnp.zeros((B, t), jnp.int32), zi, zi + 1,
            jax.random.key(0), z, z, zi, jnp.zeros((B, maxp), jnp.int32))
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


@pytest.mark.parametrize("kind,what", sorted(PARENT_JAXPRS), ids=lambda v: str(v))
def test_programs_outside_the_packed_step_are_the_parents(kind, what):
    """``t == 1`` and the one-stream step skip the packing by a static test:
    their programs are the parent's.  The step at 16 tokens a slot is the
    parent's once no bucket lies under it, and another program with them."""
    if what == "slot_t16":
        assert _jaxpr_hash(kind, what) != PARENT_JAXPRS[kind, what]
        with every_row():
            assert _jaxpr_hash(kind, what) == PARENT_JAXPRS[kind, what]
    else:
        assert _jaxpr_hash(kind, what) == PARENT_JAXPRS[kind, what]


def test_the_packed_program_holds_one_body_a_bucket():
    """Three switches a dense layer (norm + qkv, wo, norm + FFN), each over
    ``buckets(256)``; the cache is no operand of any of them."""
    cfg, params = _model("dense")
    pool, table = _pool(cfg)
    z = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, tok, c, pr, nv, tab: forward_slots(
        p, cfg, tok, c, pr, nv, tab))(
        params, jnp.zeros((B, 16), jnp.int32), pool, z, z + 1, table)
    conds = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "cond":
                conds.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert len(conds) == 3  # the scan's body is traced once
    for eqn in conds:
        assert len(eqn.params["branches"]) == len(packing.buckets(B * 16)) == 2
        assert not any(v.aval.shape == pool.k.shape for v in eqn.invars)
        assert not any(v.aval.shape == pool.k.shape for v in eqn.outvars)


# ---- the counter and the span ---------------------------------------------

def test_scheduler_counts_valid_and_run_rows():
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler
    cfg = tiny_config(seq_len=64)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=8,
                 kv_pages=8 * 16 + 1, kv_page_size=4)
    sched = SlotScheduler(eng, prefill_chunk=16)

    def cells():
        with obs_metrics.SCHED_STEP_ROWS._lock:
            return dict(obs_metrics.SCHED_STEP_ROWS._children)

    before = cells()
    obs_trace.clear()
    try:
        prompt = [int(x) for x in
                  np.random.RandomState(2).randint(3, cfg.vocab_size, 20)]
        assert len(list(sched.submit(prompt, 4).tokens())) == 4
        sched.flush()
    finally:
        sched.close()
    rose = {k: v - before.get(k, 0) for k, v in cells().items()}
    spans = [s["args"] for s in obs_trace.TRACER.snapshot()
             if s["name"] == "sched.enqueue"]
    assert spans and all("valid_rows" in a and "run_rows" in a for a in spans)
    # 20 tokens on 8 slots (the free slots ride along with one row each): a
    # chunk of 16 (23 valid of 128: run at 64), then one of 4 (11 of 32: no
    # bucket lies under 32 rows)
    assert [(a["t"], a["valid_rows"], a["run_rows"]) for a in spans
            if a["t"] > 1] == [(16, 23, 64), (4, 11, 32)]
    assert rose["valid", "mixed"] == 34 and rose["run", "mixed"] == 96
    assert rose["valid", "decode"] == rose["run", "decode"] > 0
