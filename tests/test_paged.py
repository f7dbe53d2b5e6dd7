"""Paged KV pool + radix prefix cache tests (runtime/pagepool.py, the
paged primitives in ops/attention.py, and the scheduler's page plumbing).

The tentpole contracts, each pinned here on CPU with a tiny model:

* **byte parity** — a greedy request served through the paged pool is
  token-identical to the same request on the contiguous solo engine,
  alone and with ragged staggered neighbors (pages are an addressing
  change, never a numerics change);
* **recycling** — pages freed by retirement are rebound to later
  requests with no stale-KV leak: the recycled occupant still decodes
  byte-identically (write-before-visible holds per page);
* **refcounts** — after arbitrary churn the pool's refcount/free-list
  invariants hold exactly (``PagePool.check``);
* **prefix sharing** — a repeated prompt prefix matches the radix tree,
  binds shared pages copy-free (``prefix_tokens_reused_total`` counts
  it), decodes byte-identically, and does strictly less prefill work
  than the same traffic with reuse disabled (PR-7 flight phases);
* **memory win** — a pool holding fewer tokens than slots × seq_len
  still serves every slot concurrently: per-request reservation replaces
  the contiguous layout's worst-case per-slot allocation;
* **exhaustion** — an admission that cannot get pages defers (queued,
  ``kv_pool_exhausted_total``) and completes once retirements free
  pages; it never surfaces as a dispatch error.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import flight as obs_flight, metrics as obs_metrics
from dllama_tpu.ops.attention import (_rows_ceiling_attention,
                                      paged_decode_attention,
                                      paged_gather_layer,
                                      paged_update_kv_rows,
                                      paged_write_indices, quantize_kv)
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import ContextOverflow, Engine
from dllama_tpu.runtime.pagepool import (PagePool, PagePoolExhausted,
                                         RadixTree)
from dllama_tpu.runtime.scheduler import SlotScheduler
from fixtures import PAGE_GEOMETRIES, PAGE_GEOMETRY_IDS, pool_from_logical

CFG = tiny_config(seq_len=64)
PAGE = 8
P1 = [5, 9, 2]
P2 = [7, 3, 11, 4, 6, 1, 8]
P3 = [2, 4, 6]
P4 = [9, 8, 7, 6]
PROMPTS = (P1, P2, P3, P4)


# -- host-side allocator ---------------------------------------------------

def test_pool_alloc_refcount_exhaustion():
    pool = PagePool(5, 4)  # pages 1..4 usable
    assert pool.capacity == 4 and pool.available == 4
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3] and pool.in_use == 3
    with pytest.raises(PagePoolExhausted):
        pool.alloc(2)
    assert pool.available == 1  # a failed alloc must not leak pages
    pool.incref(a[:1])
    pool.decref(a)  # drops to refs: [2]=0 [3]=0, [1]=1
    assert pool.available == 3
    pool.decref(a[:1])
    assert pool.available == 4
    with pytest.raises(RuntimeError):
        pool.decref(a[:1])  # double free
    with pytest.raises(RuntimeError):
        pool.decref([0])  # scratch is pinned
    pool.check()


def test_pool_claim_and_check():
    pool = PagePool(4, 2)
    pool.claim(2)
    assert pool.in_use == 1
    with pytest.raises(RuntimeError):
        pool.claim(2)  # already live
    with pytest.raises(RuntimeError):
        pool.claim(0)
    pool.check()
    pool.decref([2])
    pool.check()


def test_radix_match_insert_evict():
    pool = PagePool(8, 2)
    tree = RadixTree(pool)
    toks = [1, 2, 3, 4, 5]  # two full blocks + a partial
    pages = pool.alloc(2)
    assert tree.insert(toks, pages) == 2
    assert len(tree) == 2
    # insert took its own refs: the "slot" frees, the tree retains
    pool.decref(pages)
    assert pool.in_use == 2
    matched, got = tree.match([1, 2, 3, 4, 9, 9])
    assert matched == 4 and got == pages
    assert tree.match([9, 9, 9, 9])[0] == 0
    assert tree.match([1, 2])[0] == 2  # one full block
    # a second request re-inserting the same blocks adds nothing
    assert tree.insert(toks, pages) == 0
    # eviction frees tree-only pages, deepest-leaf first
    assert tree.evict(2) == 2
    assert pool.available == pool.capacity and len(tree) == 0
    pool.check()


def test_radix_evict_spares_referenced_pages():
    pool = PagePool(8, 2)
    tree = RadixTree(pool)
    pages = pool.alloc(2)
    tree.insert([1, 2, 3, 4], pages)
    # a live slot still holds the pages (refs 2): nothing is evictable
    assert tree.evict(2) == 0
    pool.decref(pages[1:])  # leaf page now tree-only
    assert tree.evict(2) == 1
    assert len(tree) == 1
    pool.decref(pages[:1])
    pool.check()


def test_radix_export_restore_roundtrip():
    pool = PagePool(8, 2)
    tree = RadixTree(pool)
    pages = pool.alloc(3)
    tree.insert([1, 2, 3, 4], pages[:2])
    # a branching second prompt: same first block (existing node wins, no
    # new reference), fresh second block
    tree.insert([1, 2, 9, 9], [pages[0], pages[2]])
    pool.decref(pages)
    data = tree.export()
    pool2 = PagePool(8, 2)
    tree2 = RadixTree(pool2)
    tree2.restore(data)
    assert len(tree2) == 3 and pool2.in_use == 3
    assert tree2.match([1, 2, 9, 9]) == (4, [pages[0], pages[2]])
    pool2.check()
    with pytest.raises(RuntimeError):
        tree2.restore(data)  # only into an empty tree


# -- device-side paged attention ------------------------------------------

@pytest.mark.parametrize("hkv,ps,maxp", [(2, 128, 32), (8, 8, 6)],
                         ids=["hkv2-ps128", "hkv8-ps8"])
def test_paged_decode_matches_gather_attention(hkv, ps, maxp):
    """The page-walking decode fold must equal the one-shot gather-view
    attention on the same pool — they are the same logical computation, so
    any divergence is a fold-masking bug — and both must equal the same
    attention over the logical head-major KV the pool was laid out from
    (the pool's token-major order, pinned without the pool's readers).  The
    first geometry clears the blocked-decode dispatch threshold
    (s >= 4096); the second has Hkv == ps, where a swapped axis would keep
    every shape."""
    rng = np.random.RandomState(3)
    L, dh, b, hq = 1, 8, 3, 2 * hkv
    n_pages = 1 + b * maxp
    s = maxp * ps
    k_log = rng.randn(L, b, hkv, s, dh).astype(np.float32)
    v_log = rng.randn(L, b, hkv, s, dh).astype(np.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        b, maxp), jnp.int32)
    pool_k = jnp.asarray(pool_from_logical(k_log, table, n_pages, ps))
    pool_v = jnp.asarray(pool_from_logical(v_log, table, n_pages, ps))
    q = jnp.asarray(rng.randn(b, hq, 1, dh), jnp.float32)
    pos_rows = jnp.asarray([ps + 2, s - 1, 5 * ps + ps // 2], jnp.int32)
    layer = jnp.int32(0)
    got = paged_decode_attention(q, pool_k, pool_v, layer, table, pos_rows)
    k_l = paged_gather_layer(pool_k, layer, table)
    v_l = paged_gather_layer(pool_v, layer, table)
    np.testing.assert_array_equal(np.asarray(k_l), k_log[0])
    np.testing.assert_array_equal(np.asarray(v_l), v_log[0])
    want = _rows_ceiling_attention(q, k_l, v_l, pos_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hkv,ps", PAGE_GEOMETRIES, ids=PAGE_GEOMETRY_IDS)
@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_paged_write_lands_token_major(codec, hkv, ps):
    """One layer's step KV written through the page table lands at
    ``pool[layer, page, offset, head]`` — the token-major page — for a bf16
    pool and for an int8 pool's values and scale planes alike; tokens past
    ``n_valid`` go to scratch page 0 and the other layer stays untouched."""
    rng = np.random.RandomState(5)
    L, dh, b, t, maxp = 2, 16, 3, 5, 4
    n_pages = 1 + b * maxp
    table = rng.permutation(np.arange(1, n_pages)).reshape(b, maxp)
    pos_rows = np.asarray([0, ps - 2, 2 * ps + 1])  # row 1 straddles pages
    n_valid = np.asarray([t, t, 2])
    k_new = jnp.asarray(rng.randn(b, hkv, t, dh), jnp.float32)
    pidx, oidx = paged_write_indices(jnp.asarray(table, jnp.int32),
                                     jnp.asarray(pos_rows, jnp.int32),
                                     jnp.asarray(n_valid, jnp.int32), t, ps)
    if codec == "int8":
        vals, scale = quantize_kv(k_new)
        planes = [(vals, jnp.int8), (scale, jnp.float32)]
    else:
        planes = [(k_new, jnp.bfloat16)]
    for new, dt in planes:
        last = new.shape[-1]
        pool = jnp.zeros((L, n_pages, ps, hkv, last), dt)
        got, _ = paged_update_kv_rows(pool, pool, new, new, jnp.int32(1),
                                      pidx, oidx)
        got = np.array(got.astype(jnp.float32))
        want = np.zeros(got.shape, np.float32)
        new_np = np.asarray(new.astype(dt).astype(jnp.float32))
        for r in range(b):
            for j in range(int(n_valid[r])):
                p = int(pos_rows[r]) + j
                want[1, table[r, p // ps], p % ps] = new_np[r, :, j]
        got[1, 0] = 0.0  # scratch page: invalid tokens' writes, unordered
        np.testing.assert_array_equal(got, want)


# -- scheduler over the paged engine --------------------------------------

def make_contiguous_engine(batch=1):
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch)


def make_paged_engine(batch=4, kv_pages=None, page=PAGE):
    # default pool: every slot can hold a full seq_len (parity testing);
    # the memory-win test passes a smaller pool explicitly
    pages_per_slot = -(-CFG.seq_len // page)
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch,
                  kv_pages=kv_pages or batch * pages_per_slot + 1,
                  kv_page_size=page)


@pytest.fixture(scope="module")
def solo_refs():
    """Greedy solo completions per prompt on the CONTIGUOUS engine — the
    cross-layout parity oracle."""
    eng = make_contiguous_engine()
    refs = {}
    for p in PROMPTS:
        eng.reset()
        toks = [t for t, _ in eng.generate_stream(
            p, len(p) + 30, temperature=0.0, chunk=5)]
        refs[tuple(p)] = toks[len(p):]
    return refs


@pytest.fixture(scope="module")
def paged_stack():
    """One paged batch=4 engine + scheduler shared across tests — page
    recycling across tests IS part of the contract under test."""
    eng = make_paged_engine(4)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0,
                          decode_burst=4)
    yield eng, sched
    sched.close()


def _collect(sched, prompt, max_new=30, delay=0.0):
    time.sleep(delay)
    t = sched.submit(prompt, max_new, temperature=0.0)
    return t, list(t.tokens())


def test_paged_greedy_parity_ragged_traffic(solo_refs, paged_stack):
    """4 staggered greedy requests with ragged prompt lengths through the
    paged pool: every stream byte-identical to its solo contiguous run."""
    _, sched = paged_stack
    outs = {}

    def run(p, delay):
        _, toks = _collect(sched, p, max_new=30, delay=delay)
        outs[tuple(p)] = toks

    ths = [threading.Thread(target=run, args=(p, 0.02 * i))
           for i, p in enumerate(PROMPTS)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(120)
    for p in PROMPTS:
        want = solo_refs[tuple(p)][:len(outs[tuple(p)])]
        assert outs[tuple(p)] == want, f"prompt {p} diverged"
        assert len(outs[tuple(p)]) > 0


@pytest.mark.parametrize("hkv,ps", PAGE_GEOMETRIES, ids=PAGE_GEOMETRY_IDS)
@pytest.mark.parametrize("kv_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_greedy_parity_by_page_geometry(kv_dtype, hkv, ps):
    """Paged greedy output is byte-identical to the contiguous engine's at
    a page geometry where Hkv != ps and at one where Hkv == ps (a swapped
    page axis keeps every shape there), with the cache in f32 and bf16."""
    cfg = tiny_config(n_kv_heads=hkv, seq_len=64)
    params = init_params(cfg, seed=4)
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    solo = Engine(cfg, params, mesh=mesh, batch=1, kv_dtype=kv_dtype)
    eng = Engine(cfg, params, mesh=mesh, batch=2, kv_dtype=kv_dtype,
                 kv_pages=2 * (cfg.seq_len // ps) + 1, kv_page_size=ps)
    assert eng.cache.k.shape == (cfg.n_layers, eng.kv_pages, ps, hkv,
                                 cfg.head_size)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0,
                          decode_burst=4)
    try:
        tickets = [sched.submit(p, 20, temperature=0.0) for p in (P1, P2)]
        outs = [list(t.tokens()) for t in tickets]
    finally:
        sched.close()
    for p, out in zip((P1, P2), outs):
        solo.reset()
        want = [t for t, _ in solo.generate_stream(
            p, len(p) + 20, temperature=0.0, chunk=5)][len(p):]
        assert out == want, f"prompt {p} diverged at Hkv={hkv}, ps={ps}"


@pytest.mark.parametrize("hkv,ps", PAGE_GEOMETRIES, ids=PAGE_GEOMETRY_IDS)
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, "q8"],
                         ids=["bf16", "int8"])
def test_pool_pages_export_import_round_trip(kv_dtype, hkv, ps):
    """``read_pool_pages`` hands out whole token-major pages
    ``(L, n, ps, Hkv, Dh)`` (an int8 pool's scale planes beside them) and
    ``write_pool_pages`` puts them back at other page ids of another
    engine, value for value; pages nobody wrote stay zero."""
    cfg = tiny_config(n_kv_heads=hkv, seq_len=32)
    params = init_params(cfg, seed=4)
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    a, b = (Engine(cfg, params, mesh=mesh, batch=2, kv_dtype=kv_dtype,
                   kv_pages=9, kv_page_size=ps) for _ in range(2))
    rng = np.random.RandomState(11)
    fill = lambda x: jnp.asarray(  # noqa: E731
        rng.randint(-100, 100, x.shape), x.dtype)
    a.cache = jax.tree.map(fill, a.cache)
    src, dst = [3, 1, 7], [5, 6, 2]
    pages = a.read_pool_pages(src)
    names = {"pages.k": "k", "pages.v": "v"}
    if kv_dtype == "q8":
        names.update({"pages.k_scale": "k_scale", "pages.v_scale": "v_scale"})
    assert set(pages) == set(names)
    for name, attr in names.items():
        last = 1 if name.endswith("_scale") else cfg.head_size
        assert pages[name].shape == (cfg.n_layers, 3, ps, hkv, last)
        np.testing.assert_array_equal(
            pages[name], np.asarray(getattr(a.cache, attr))[:, src])
    b.write_pool_pages(dst, pages)
    back = b.read_pool_pages(dst)
    rest = [i for i in range(9) if i not in dst]
    for name, attr in names.items():
        np.testing.assert_array_equal(back[name], pages[name])
        assert not np.asarray(getattr(b.cache, attr))[:, rest].any()


def test_page_recycling_no_stale_kv(solo_refs, paged_stack):
    """Churn: two waves of more requests than slots force every page
    through free→bound→free→bound; recycled pages must never leak a
    previous occupant's KV into a new stream."""
    _, sched = paged_stack
    for _ in range(2):
        outs = {}

        def run(p):
            _, toks = _collect(sched, p, max_new=10)
            outs[tuple(p)] = toks

        ths = [threading.Thread(target=run, args=(p,)) for p in PROMPTS]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        for p in PROMPTS:
            assert outs[tuple(p)] == solo_refs[tuple(p)][:10], \
                f"stale KV: prompt {p} diverged after recycling"


def test_refcount_invariant_after_churn(paged_stack):
    _, sched = paged_stack
    with sched._cond:
        sched.pool.check()
        held = sum(len(s.pages) for s in sched.slots)
        # every in-use page is owned by a slot and/or the radix tree
        assert sched.pool.in_use >= held


def test_prefix_reuse_byte_identical_and_cheaper(solo_refs):
    """The tentpole acceptance: a shared system prompt makes later
    requests bind cached pages (prefix_tokens_reused_total > 0), decode
    byte-identically, and do strictly less prefill work than the same
    traffic with reuse disabled (PR-7 flight phases carry the receipts)."""
    rng = np.random.RandomState(11)
    system = [int(x) for x in rng.randint(1, CFG.vocab_size, 4 * PAGE)]
    prompt = system + [3, 1]

    def serve(prefix_reuse):
        eng = make_paged_engine(2)
        sched = SlotScheduler(eng, prefill_chunk=4,
                              prefix_reuse=prefix_reuse)
        try:
            t1, o1 = _collect(sched, prompt, max_new=8)
            t2, o2 = _collect(sched, prompt, max_new=8)
        finally:
            sched.close()
        return (t1, o1), (t2, o2)

    reused0 = obs_metrics.PREFIX_TOKENS_REUSED.value
    hits0 = obs_metrics.PREFIX_HITS.value
    (t1, o1), (t2, o2) = serve(True)
    assert o1 == o2, "prefix-reused decode diverged from the cold run"
    assert obs_metrics.PREFIX_HITS.value > hits0
    # the whole 4-page system prompt came from the tree
    assert obs_metrics.PREFIX_TOKENS_REUSED.value - reused0 == 4 * PAGE

    def prefill_tokens(t):
        rec = obs_flight.get(t.rid)
        assert rec is not None
        return sum(ph.get("tokens", 0) for ph in rec.get("phases", [])
                   if ph.get("kind") == "prefill_chunk")

    # receipts: the hit request prefilled only the suffix, and its record
    # carries the prefix_reuse span
    rec2 = obs_flight.get(t2.rid)
    kinds = [ph.get("kind") for ph in rec2.get("phases", [])]
    assert "prefix_reuse" in kinds, kinds
    assert prefill_tokens(t2) < prefill_tokens(t1)
    assert prefill_tokens(t2) == len(prompt) - 4 * PAGE

    # A/B: same traffic, reuse disabled — full prefill both times, and
    # strictly more prefill work than the reusing run did
    (t1n, o1n), (t2n, o2n) = serve(False)
    assert o1n == o1 and o2n == o1, "reuse changed the tokens"
    assert prefill_tokens(t2n) == len(prompt)
    assert prefill_tokens(t2) < prefill_tokens(t2n)


def test_pool_smaller_than_slots_times_seqlen_serves_all(solo_refs):
    """The memory win: 4 slots × seq_len 64 = 256 cache positions under
    the contiguous layout; a pool of 17 usable pages × 8 = 136 tokens
    serves the same 4 concurrent requests, because each reserves only
    min(len + max_new, seq_len) worth of pages."""
    eng = make_paged_engine(4, kv_pages=18)
    assert eng.kv_pages * PAGE < 4 * CFG.seq_len
    sched = SlotScheduler(eng, prefill_chunk=4)
    try:
        outs = {}

        def run(p, delay):
            _, toks = _collect(sched, p, max_new=10, delay=delay)
            outs[tuple(p)] = toks

        ths = [threading.Thread(target=run, args=(p, 0.02 * i))
               for i, p in enumerate(PROMPTS)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        # all four were concurrently resident and correct
        for p in PROMPTS:
            assert outs[tuple(p)] == solo_refs[tuple(p)][:10]
        sched.pool.check()
    finally:
        sched.close()


def test_exhaustion_defers_then_recovers():
    """A request that cannot get pages waits in the queue (counted by
    kv_pool_exhausted_total) and completes once a retirement frees pages;
    a request that could NEVER fit fails fast at submit."""
    # 6 usable pages × 8 = 48 tokens; each request reserves
    # min(3 + 40, 64) = 43 tokens → 6 pages, so only one can be resident
    eng = make_paged_engine(2, kv_pages=7)
    sched = SlotScheduler(eng, prefill_chunk=4, prefix_reuse=False)
    try:
        with pytest.raises(ContextOverflow):
            # needs ceil(64/8) = 8 pages > the 6-page capacity: this can
            # never be admitted, so it must fail fast, not queue forever
            sched.submit(list(range(1, 60)), 40)
        exhausted0 = obs_metrics.KV_POOL_EXHAUSTED.value
        outs = []

        def run():
            t = sched.submit(P1, 40, temperature=0.0)
            outs.append((list(t.tokens()), t.finish))

        ths = [threading.Thread(target=run) for _ in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        assert len(outs) == 2
        for toks, finish in outs:
            assert finish == "length" and len(toks) > 0
        assert outs[0][0] == outs[1][0]
        assert obs_metrics.KV_POOL_EXHAUSTED.value > exhausted0
        with sched._cond:
            assert sched.pool.available == sched.pool.capacity
            sched.pool.check()
    finally:
        sched.close()
