"""One-dispatch decode tests (docs/PERF.md "one-dispatch decode"): the
fused page-walk paged-attention Pallas kernel (ops/attention.py
``fused_paged_attention``) and the on-device sampling stage
(sampling.sample_on_device + the engine's device-resident RNG key
chain).

Contracts pinned here on CPU — the kernel runs in Pallas interpret mode
(``DLLAMA_FUSED_ATTN=interp``: same kernel logic, no TPU needed):

* **kernel parity** — the fused kernel matches the gather +
  rows-ceiling reference on a random ragged fixture at every step width
  (one token, a mixed step's chunk, a verify block), dense pools and the
  int8 pool's walk, at a non-zero layer (tolerance scaled to the reference
  magnitude: the two implementations associate the bf16 online-softmax
  folds differently, so 2e-5 elementwise is the wrong bar);
* **byte parity** — greedy decode through the paged scheduler is
  token-identical with the kernel forced on vs off, overlap on and
  off, dense and int8 pools (the fused kernel is a dispatch-structure
  change, never a numerics change at argmax granularity);
* **fixed-coin parity** — ``sample_on_device`` picks the same token as
  the host ``sample_with_coin`` for the same coin across a
  temperature × top-p × top-k × mask grid including ties;
* **device key chain** — sampled slot decode is deterministic given
  the engine seed, a snapshot/restore continues the sampled stream
  byte-identically (the device key rides DLSNAP02), hand-off records
  carry the device key + sampling-path flag, and a record from a
  different sampling path is refused before any state is touched.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.ops.attention import (_rows_ceiling_attention, dequant_kv,
                                      paged_decode_attention,
                                      fused_paged_attention,
                                      paged_gather_layer, quantize_kv)
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime import snapshot as snapfmt
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.faults import injected
from dllama_tpu.runtime.scheduler import SlotScheduler
from dllama_tpu.sampling import sample_on_device, sample_with_coin
from fixtures import PAGE_GEOMETRIES, PAGE_GEOMETRY_IDS, pool_from_logical

CFG = tiny_config(seq_len=64)
PAGE = 8
PROMPTS = ([5, 9, 2], [7, 3, 11, 4, 6, 1, 8], [2, 4, 6], [9, 8, 7, 6])


def make_paged_engine(batch=4, page=PAGE, **kw):
    pages_per_slot = -(-CFG.seq_len // page)
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch,
                  kv_pages=batch * pages_per_slot + 1,
                  kv_page_size=page, **kw)


# -- kernel vs gather reference --------------------------------------------

def _pool_fixture(quantized, hkv=2, ps=8, t=1, g=2, dh=16, nlayers=2,
                  fold=False):
    """A ragged paged read of ``t`` query tokens a row over a table wider
    than one chunk of the walk: the pool is laid out from logical head-major
    KV by :func:`fixtures.pool_from_logical`, and ``logical`` is that KV
    (dequantized for an int8 pool) for a reference that never touches the
    pool.  Rows, by what their block of ``t`` tokens does: starts at
    position 0; crosses a page boundary (its chunk reads its last live page
    again for the pages past it); crosses a boundary of the walk's
    chunks (``_WALK_PAGES`` pages); ends on the table's last position; and
    one whose table past its first token's page is scratch page 0, as a
    decode row's is in a mixed step (its tokens past the first see page 0's
    keys in both forms).  ``fold``: the pool's rows hold ``128 // dh`` heads
    each (``pool_rows``), the same bytes in the same order."""
    from dllama_tpu.ops.attention import _WALK_PAGES as cp, pool_rows
    rows = pool_rows(hkv, dh) if fold else (hkv, dh)
    b, maxp = 5, cp + 2
    npages = 1 + b * maxp
    rng = np.random.RandomState(3)
    table = rng.permutation(np.arange(1, npages)).reshape(b, maxp)
    pos_rows = jnp.asarray([0, ps - 1, cp * ps - max(1, t // 2),
                            maxp * ps - t, ps + 1], jnp.int32)
    q = jnp.asarray(rng.randn(b, hkv * g, t, dh) * 0.3, jnp.float32)
    shape = (nlayers, b, hkv, maxp * ps, dh)

    def place(a):
        pool = pool_from_logical(a, table, npages, ps)
        return jnp.asarray(pool.reshape(*pool.shape[:3], *rows)
                           if pool.shape[-1] == dh else pool)
    if quantized:
        (qk, sk), (qv, sv) = (quantize_kv(jnp.asarray(rng.randn(*shape),
                                                      jnp.float32))
                              for _ in range(2))
        pk, pv, scales = place(qk), place(qv), (place(sk), place(sv))
        logical = (dequant_kv(qk, sk), dequant_kv(qv, sv))
    else:
        k, v = (jnp.asarray(rng.randn(*shape) * 0.3, jnp.bfloat16)
                for _ in range(2))
        pk, pv, scales, logical = place(k), place(v), None, (k, v)
        # scratch page 0 holds what the last invalid write left there
        page0 = jnp.asarray(rng.randn(nlayers, ps, *rows) * 0.3, pk.dtype)
        pk, pv = pk.at[:, 0].set(page0), pv.at[:, 0].set(-page0)
    table[-1, int(pos_rows[-1]) // ps + 1:] = 0
    return q, pk, pv, jnp.asarray(table, jnp.int32), pos_rows, scales, logical


# the scheduler's mixed-step widths, one odd verify width (spec_k + 1), and
# the int8 pool's read at the one width its live walk takes
@pytest.mark.parametrize("hkv,ps", PAGE_GEOMETRIES, ids=PAGE_GEOMETRY_IDS)
@pytest.mark.parametrize(
    "quantized,t", [(False, t) for t in (1, 2, 4, 5, 8, 16)] + [(True, 1)],
    ids=[f"dense-t{t}" for t in (1, 2, 4, 5, 8, 16)] + ["kv_int8"])
def test_fused_kernel_matches_gather_reference(quantized, t, hkv, ps):
    """The page-walk kernel and the materialized-gather path compute the
    same attention read — ragged rows of ``t`` tokens under the per-row
    causal ceiling, layer 1 of 2 (the layer index rides scalar prefetch),
    dead pages fully masked — and the gather view IS the logical KV the pool
    was laid out from, exactly: the token-major page order
    (L, P, ps, Hkv, Dh) read back head-major."""
    q, pk, pv, table, pos_rows, scales, (k_log, v_log) = _pool_fixture(
        quantized, hkv, ps, t)
    assert pk.shape[2:4] == (ps, hkv)
    layer = jnp.int32(1)
    # an int8 pool is not the kernel's (its scale plane cannot be copied by
    # the page): its read is the XLA live walk
    out = paged_decode_attention(q, pk, pv, layer, table, pos_rows,
                                 scales=scales) if quantized else \
        fused_paged_attention(q, pk, pv, layer, table, pos_rows,
                              interpret=True)
    _assert_is_the_gather_read(out, q, pk, pv, layer, table, pos_rows,
                               (k_log, v_log), scales)


def _assert_is_the_gather_read(out, q, pk, pv, layer, table, pos_rows, logical,
                               scales=None):
    """``out`` against the materialized-gather read of the same pool, whose
    view must be the logical KV the pool was laid out from, exactly."""
    ks, vs = scales if scales is not None else (None, None)
    dh = q.shape[-1]
    k_l = paged_gather_layer(pk, layer, table, scale_pool=ks, dh=dh)
    v_l = paged_gather_layer(pv, layer, table, scale_pool=vs, dh=dh)
    # every row but the last has its whole table
    for view, log in zip((k_l, v_l), logical):
        np.testing.assert_array_equal(np.asarray(view[:-1], np.float32),
                                      np.asarray(log[1][:-1], np.float32))
    ref = _rows_ceiling_attention(q, k_l, v_l, pos_rows)
    assert out.shape == ref.shape == q.shape
    tol = 1e-2 * max(float(np.abs(np.asarray(ref, np.float32)).max()), 1e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


# heads narrower than a lane row, stored 128 // dh to a row of the pool: LFM2's
# 32 / 8 heads of 64 (two a row) and 16 / 16 of 32 (four a row); a page of 4
# tokens keeps the interpreter quick
@pytest.mark.parametrize("t", [1, 5, 16])
@pytest.mark.parametrize("hq,hkv,dh", [(32, 8, 64), (16, 16, 32)],
                         ids=["32x8x64-two-a-row", "16x16x32-four-a-row"])
def test_fused_kernel_walks_a_folded_pool(hq, hkv, dh, t):
    """The page walk reads a pool row that holds several heads as it lies:
    the query is widened to the row, the 128-lane contraction adds exactly 0
    in the other heads' lanes, a query row keeps the key rows its kv head
    lies in and takes its own lanes of the second dot.  Against the gather
    form (which unfolds the rows, and IS the logical KV) on the ragged
    fixture, at the tolerance of the whole-lane heads' cases."""
    from dllama_tpu.ops.attention import pool_rows
    ps = 4
    q, pk, pv, table, pos_rows, _, (k_log, v_log) = _pool_fixture(
        False, hkv, ps, t, hq // hkv, dh, fold=True)
    assert pk.shape[2:] == (ps,) + pool_rows(hkv, dh) and pk.shape[4] == 128
    layer = jnp.int32(1)
    out = fused_paged_attention(q, pk, pv, layer, table, pos_rows,
                                interpret=True)
    _assert_is_the_gather_read(out, q, pk, pv, layer, table, pos_rows,
                               (k_log, v_log))


# (t, hq, hkv, dh, int8, page, table pages, the pool's row width) -> fused?
# on one TPU device.  The rule reads the pool's rows: whole lanes take the
# walk, part of a lane row stays on the gather form whatever the head size
@pytest.mark.parametrize("args,fused", [
    ((1, 32, 8, 64, False, 16, 128, 128), True),     # LFM2's folded pool
    ((16, 32, 8, 64, False, 16, 128, 128), True),
    ((1, 32, 8, 64, False, 16, 128, 64), False),     # the same heads, one a row
    ((1, 32, 8, 64, False, 16, 128), False),         # no width given: dh
    ((1, 32, 8, 64, True, 16, 128, 128), False),     # int8: no folded form
    ((1, 16, 16, 32, False, 16, 128, 128), True),    # four heads a row
    ((1, 32, 8, 96, False, 16, 128, 96), False),     # 96 does not divide 128
    ((1, 32, 8, 256, False, 16, 128, 256), True),    # two lane rows a head
    # the tile is counted in the rows a token has in the pool (4, not 8):
    # 32 * t * 8 * 16 * 4 <= 1 Mi up to t = 64, where 8 rows a token stop at 32
    ((64, 32, 8, 64, False, 16, 128, 128), True),
    ((65, 32, 8, 64, False, 16, 128, 128), False),
    ((33, 32, 8, 128, False, 16, 128, 128), False),
    # a table shorter than the chunk bounds it: 4 pages, t up to 128
    ((128, 32, 8, 64, False, 16, 4, 128), True),
    ((129, 32, 8, 64, False, 16, 4, 128), False),
], ids=["folded-t1", "folded-t16", "one-head-a-row", "width-defaults-to-dh",
        "int8", "four-a-row", "dh96", "dh256", "tile-t64", "tile-t65",
        "tile-8-rows-t33", "short-table-t128", "short-table-t129"])
def test_fused_choice_reads_the_pools_row_width(monkeypatch, args, fused):
    from dllama_tpu.ops import attention as att
    from dllama_tpu.parallel.mesh import active_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "auto")
    assert att._fused_choice(*args) == (fused, False)
    with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
        assert att._fused_choice(*args) == (False, False)   # a mesh: gather
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert att._fused_choice(*args) == (False, False)       # auto off-TPU


@pytest.mark.parametrize("mode", ["auto", "on"])
def test_fused_choice_is_static_and_raises_on_tpu(monkeypatch, mode):
    """Platform patched to read ``tpu``: the attention ladder picks the
    fused kernel outside and inside ``jax.jit`` alike, and its lowering
    failure (this backend is really the CPU) propagates — no probe, no
    degrade to the gather path."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import attention as att
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", mode)
    q, pk, pv, table, pos_rows, _, _ = _pool_fixture(False, dh=128)
    assert att._fused_choice(1, 4, 2) == (True, False)
    assert att._fused_choice(1, 3, 2) == (False, False)  # hq % hkv
    assert att._fused_choice(1, 4, 2, 64) == (False, False)  # half a lane row
    assert att._fused_choice(1, 4, 2, 64, row=128) == (True, False)  # two heads fill it
    assert att._fused_choice(1, 4, 2, 128, True) == (False, False)  # int8 pool

    def read(qv):
        return att.paged_gqa_attention_at(qv, pk, pv, jnp.int32(0), table,
                                          pos_rows)

    for call in (lambda: read(q), lambda: jax.jit(read)(q)):
        obs_dispatch.reset()
        try:
            with pytest.raises(Exception):  # noqa: B017 — any lowering error
                jax.block_until_ready(call())
            assert obs_dispatch.dispatches() == {"kv_dense/paged-fused": 1}
            assert obs_dispatch.degraded() is False
        finally:
            obs_dispatch.reset()


# a mixed step's widths (a power of two up to --sched-prefill-chunk), a verify
# step's spec_k + 1, at the served cells' heads (query, kv) and Llama-2-7B's
@pytest.mark.parametrize("t", [1, 2, 4, 5, 8, 16])
def test_fused_choice_takes_every_step_width_on_one_tpu_device(monkeypatch, t):
    """On one TPU device the fused walk serves the pure-decode step's one
    token, every chunk width of a mixed step and a verify step's block, by
    a rule of shapes alone; an int8 pool and a mesh keep the gather form at
    the same widths, and so does a block whose score tile
    (``Hq * T`` rows by a chunk's ``tokens * Hkv`` keys) passes
    ``_SCORE_TILE_MAX``."""
    from dllama_tpu.ops import attention as att
    from dllama_tpu.parallel.mesh import active_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "auto")
    for hq, hkv in ((32, 8), (16, 16)):
        assert att._fused_choice(t, hq, hkv) == (True, False)
        assert att._fused_choice(t, hq, hkv, 128, True) == (False, False)
        with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
            assert att._fused_choice(t, hq, hkv) == (False, False)
    # 32 x 32 heads: 8 pages of 16 tokens are 4096 keys a chunk, so 1 Mi
    # score elements at 8 tokens a slot; a page of 64 tokens quarters that
    assert att._fused_choice(t, 32, 32) == (t <= 8, False)
    assert att._fused_choice(t, 32, 32, ps=64) == (t <= 2, False)
    assert att._fused_choice(64, 32, 8) == (False, False)


def test_fused_choice_records_the_path_of_a_chunk(monkeypatch):
    """The read of a chunk step records ``paged-fused`` with its ``t`` where
    the rule takes it and the gather form's two families where the rule
    names a reason (here: the score tile), under the same mode."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import attention as att
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "interp")
    q, pk, pv, table, pos_rows, _, _ = _pool_fixture(False, t=4)
    obs_dispatch.reset()
    try:
        att.paged_gqa_attention_at(q, pk, pv, jnp.int32(0), table, pos_rows)
        assert obs_dispatch.dispatches() == {"kv_dense/paged-fused": 1}
        obs_dispatch.reset()
        monkeypatch.setattr(att, "_SCORE_TILE_MAX", 4 * 4 * 8 * 8 * 2 - 1)
        att.paged_gqa_attention_at(q, pk, pv, jnp.int32(0), table, pos_rows)
        assert obs_dispatch.dispatches() == {"kv_dense/paged-gather": 1,
                                             "kv_dense/attn-score": 1}
    finally:
        obs_dispatch.reset()


def test_fused_choice_on_a_mesh_stays_gather(monkeypatch):
    """A pallas_call is not partitioned by GSPMD: on a multi-device mesh
    the TPU ladder keeps the gather form (a static choice, no degrade
    under ``auto``; ``on`` says so loudly)."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import attention as att
    from dllama_tpu.parallel.mesh import active_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs_dispatch.reset()
    try:
        with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
            monkeypatch.setenv("DLLAMA_FUSED_ATTN", "auto")
            assert att._fused_choice(1, 4, 2) == (False, False)
            assert obs_dispatch.degraded() is False
            monkeypatch.setenv("DLLAMA_FUSED_ATTN", "on")
            assert att._fused_choice(1, 4, 2) == (False, False)
            assert obs_dispatch.reasons() == {"attn:fused_needs_tpu": 1}
    finally:
        obs_dispatch.reset()


def test_fused_kernel_under_jit():
    """The kernel composes with jit (the engine always calls it inside a
    compiled step) and stays deterministic across calls."""
    q, pk, pv, table, pos_rows, scales, _ = _pool_fixture(False)

    @jax.jit
    def step(q):
        return fused_paged_attention(q, pk, pv, jnp.int32(0), table,
                                     pos_rows, interpret=True)

    a = np.asarray(step(q))
    b = np.asarray(step(q))
    np.testing.assert_array_equal(a, b)
    ref = _rows_ceiling_attention(
        q, paged_gather_layer(pk, jnp.int32(0), table),
        paged_gather_layer(pv, jnp.int32(0), table), pos_rows)
    tol = 1e-2 * max(float(np.abs(np.asarray(ref, np.float32)).max()), 1e-3)
    np.testing.assert_allclose(a.astype(np.float32),
                               np.asarray(ref, np.float32), atol=tol)


# -- a slot's first chunk is copied behind the previous slot's last fold -----
# (PR 64) depths of a slot, in chunks of the walk: 1, 2, 3; "x" exactly
# ``_WALK_PAGES`` live pages (one chunk, none of its pages past ``last``);
# "e" an empty slot (position 0: one page); "z" the table's last position.
# Which buffer a slot begins in is the parity of the chunks before it.
_UNITS = "1121322331"   # every ordered pair of 1, 2, 3 chunks
SLOT_DEPTHS = (
    ["1", "3", "x", "12", "21", "e3", "3e", "x2", "zz"]
    + ["".join(p) + tail for p, tail in zip(
        ("123", "132", "213", "231", "312", "321"),
        ("xe", "ex", "ez", "ze", "ee", "xx"))]
    + ["e" + p + "e" for p in ("123", "321")] + ["1e3e2", "2e1e3"]
    + ["e" + _UNITS + "exz2e", "3x" + _UNITS[::-1] + "e2ze"])


def _depth_fixture(depths, t, f, ps=4):
    """A pool and one slot a letter of ``depths``, whose last query token
    lies on the last position of the page the letter names."""
    from dllama_tpu.ops.attention import _WALK_PAGES as cp, pool_rows
    hkv, g, dh = (4, 2, 64) if f == 2 else (2, 2, 16)
    b, maxp = len(depths), 3 * cp
    last = {"1": cp - 3, "2": 2 * cp - 2, "3": 2 * cp, "x": cp - 1,
            "z": maxp - 1}
    pos = [0 if d == "e" else (last[d] + 1) * ps - t for d in depths]
    assert min(pos) >= 0
    npages = 1 + b * maxp
    rng = np.random.RandomState(len(depths) * 31 + t)
    rows = pool_rows(hkv, dh) if f == 2 else (hkv, dh)
    pk, pv = (jnp.asarray(rng.randn(2, npages, ps, *rows) * 0.3, jnp.bfloat16)
              for _ in range(2))
    table = rng.permutation(np.arange(1, npages)).reshape(b, maxp)
    q = jnp.asarray(rng.randn(b, hkv * g, t, dh) * 0.3, jnp.bfloat16)
    return (q, pk, pv, jnp.asarray(table, jnp.int32),
            jnp.asarray(pos, jnp.int32))


_walk = jax.jit(fused_paged_attention, static_argnames=("interpret",))


@pytest.mark.parametrize("f", [1, 2], ids=["one-head-a-row", "two-a-row"])
@pytest.mark.parametrize("t", [1, 5, 16])
@pytest.mark.parametrize("depths", SLOT_DEPTHS)
def test_a_slot_reads_what_it_reads_alone(depths, t, f):
    """A call over ``B`` slots gives, slot for slot, exactly (bit for bit)
    what ``B`` calls of one slot give: the copy a slot's last fold starts
    for the next slot, and the buffer parity carried across the grid's steps,
    change who copies a chunk and where to, never what is folded."""
    assert len(depths) in (1, 2, 5, 16)
    q, pk, pv, table, pos = _depth_fixture(depths, t, f)
    layer = jnp.int32(1)
    got = np.asarray(_walk(q, pk, pv, layer, table, pos, interpret=True),
                     np.float32)
    assert np.isfinite(got).all()
    for i in range(len(depths)):
        alone = _walk(q[i:i + 1], pk, pv, layer, table[i:i + 1],
                      pos[i:i + 1], interpret=True)
        np.testing.assert_array_equal(got[i], np.asarray(alone[0], np.float32),
                                      err_msg=f"slot {i} of {depths!r}")


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("t,f", [(1, 1), (16, 1), (5, 2)])
def test_every_wait_answers_one_start_and_none_is_left(capfd, dma, t, f):
    """The same read in the TPU interpreter, which keeps the semaphores'
    counts and leaves scratch memory NaN until something writes it:
    ``on_wait`` runs a copy only when its semaphore is waited for, so a fold
    that read a buffer nobody waited on would read NaN; ``eager`` signals at
    the start, so a copy started and never waited for is a count left at the
    kernel's exit, which the interpreter prints."""
    from jax.experimental.pallas import tpu as pltpu
    q, pk, pv, table, pos = _depth_fixture("e2x31", t, f)
    want = fused_paged_attention(q, pk, pv, jnp.int32(1), table, pos,
                                 interpret=True)
    got = fused_paged_attention(
        q, pk, pv, jnp.int32(1), table, pos,
        interpret=pltpu.InterpretParams(dma_execution_mode=dma,
                                        uninitialized_memory="nan",
                                        detect_races=True))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    assert not ipc.races.races_found
    assert "non-zero count" not in capfd.readouterr().out


# -- e2e greedy byte parity: fused vs fallback -----------------------------

def _sched_streams(overlap, kv_dtype, max_new=20):
    eng = make_paged_engine(**({"kv_dtype": kv_dtype} if kv_dtype else {}))
    sched = SlotScheduler(eng, prefill_chunk=8, max_wait_ms=20.0,
                          overlap=overlap)
    out = [None] * len(PROMPTS)

    def go(i):
        t = sched.submit(list(PROMPTS[i]), max_new)
        out[i] = list(t.tokens())

    ths = [threading.Thread(target=go, args=(i,))
           for i in range(len(PROMPTS))]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    sched.close()
    assert all(len(s) == max_new for s in out)
    return out


@pytest.mark.parametrize("kv_dtype", [None, "q8"], ids=["dense", "kv_int8"])
@pytest.mark.parametrize("overlap", [False, True],
                         ids=["sync", "overlap"])
def test_greedy_byte_parity_fused_vs_fallback(monkeypatch, overlap,
                                              kv_dtype):
    """Ragged staggered greedy decode through the paged scheduler: the
    emitted streams with the fused kernel forced on (interpret mode)
    must be byte-identical to the gather fallback, overlap on and off,
    dense and int8 pools."""
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "interp")
    fused = _sched_streams(overlap, kv_dtype)
    monkeypatch.setenv("DLLAMA_FUSED_ATTN", "off")
    fallback = _sched_streams(overlap, kv_dtype)
    assert fused == fallback


# -- fixed-coin sampling parity host vs device -----------------------------

def test_fixed_coin_sampling_parity():
    """For the same uniform coin, sample_on_device picks the same token
    as the host sample_with_coin across the sampling-mode grid —
    greedy, plain multinomial, nucleus, top-k (with ties at the bar),
    and the optional vocab keep-mask."""
    rng = np.random.RandomState(11)
    v = 48
    cases = [(t, p, k) for t in (0.0, 0.4, 1.0)
             for p in (0.0, 0.5, 0.9, 1.0)
             for k in (0, 3, v)]
    n = len(cases)
    logits = (rng.randn(n, v) * 2.0).astype(np.float32)
    logits[:, 7] = logits[:, 3]  # ties through top-k and the stable sort
    coins = rng.rand(n).astype(np.float32)
    temps = np.asarray([c[0] for c in cases], np.float32)
    topps = np.asarray([c[1] for c in cases], np.float32)
    topks = np.asarray([c[2] for c in cases], np.int32)
    mask = np.ones(v, bool)
    mask[::7] = False
    for m in (None, mask):
        host = [sample_with_coin(logits[i], float(coins[i]),
                                 temperature=float(temps[i]),
                                 topp=float(topps[i]), topk=int(topks[i]),
                                 mask=m)
                for i in range(n)]
        dev = sample_on_device(
            jnp.asarray(logits), jnp.asarray(coins), jnp.asarray(temps),
            jnp.asarray(topps), jnp.asarray(topks),
            mask=None if m is None else jnp.asarray(m))
        assert [int(x) for x in np.asarray(dev)] == host, \
            f"device/host divergence (mask={m is not None})"


def test_identity_mask_is_identity():
    """The all-True vocab mask (the grammar seam's identity) changes no
    decision on either path."""
    rng = np.random.RandomState(5)
    v = 32
    logits = (rng.randn(6, v) * 1.5).astype(np.float32)
    coins = rng.rand(6).astype(np.float32)
    temps = np.full(6, 0.8, np.float32)
    topps = np.full(6, 0.9, np.float32)
    topks = np.zeros(6, np.int32)
    ident = np.ones(v, bool)
    no_mask = sample_on_device(jnp.asarray(logits), jnp.asarray(coins),
                               jnp.asarray(temps), jnp.asarray(topps),
                               jnp.asarray(topks))
    with_mask = sample_on_device(jnp.asarray(logits), jnp.asarray(coins),
                                 jnp.asarray(temps), jnp.asarray(topps),
                                 jnp.asarray(topks), mask=jnp.asarray(ident))
    np.testing.assert_array_equal(np.asarray(no_mask), np.asarray(with_mask))
    for i in range(6):
        assert sample_with_coin(
            logits[i], float(coins[i]), temperature=0.8, topp=0.9,
            mask=ident) == int(np.asarray(no_mask)[i])


# -- device RNG key chain: determinism, snapshot, hand-off -----------------

def _sampled_decode(eng, n_steps, b=2):
    """Prefill PROMPTS[:b] rows, then ``n_steps`` sampled pure-decode
    slot_steps feeding each row its own previous sample.  Returns the
    (n_steps, b) emitted ids plus the loop state for continuation."""
    ps = PAGE
    maxp = -(-CFG.seq_len // ps)
    ptab = np.asarray(
        1 + np.arange(b * maxp).reshape(b, maxp), np.int32)
    temps = np.full(b, 0.8, np.float32)
    topps = np.full(b, 0.9, np.float32)
    width = max(len(p) for p in PROMPTS[:b])
    toks = np.zeros((b, width), np.int32)
    n_valid = np.zeros(b, np.int32)
    for i, p in enumerate(PROMPTS[:b]):
        toks[i, :len(p)] = p
        n_valid[i] = len(p)
    pos = np.zeros(b, np.int32)
    first = eng.slot_step(toks, pos, n_valid, temps_np=temps,
                          topps_np=topps, page_tables_np=ptab)
    pos = pos + n_valid
    cur = first[-1]
    out = [cur.copy()]
    for _ in range(n_steps - 1):
        t = eng.slot_step(cur[:, None].astype(np.int32), pos,
                          np.ones(b, np.int32), temps_np=temps,
                          topps_np=topps, page_tables_np=ptab)
        pos = pos + 1
        cur = t[-1]
        out.append(cur.copy())
    return np.stack(out), (cur, pos, ptab, temps, topps)


def _continue_decode(eng, state, n_steps):
    cur, pos, ptab, temps, topps = state
    b = len(cur)
    out = []
    for _ in range(n_steps):
        t = eng.slot_step(cur[:, None].astype(np.int32), pos,
                          np.ones(b, np.int32), temps_np=temps,
                          topps_np=topps, page_tables_np=ptab)
        pos = pos + 1
        cur = t[-1]
        out.append(cur.copy())
    return np.stack(out), (cur, pos, ptab, temps, topps)


def test_sampled_decode_deterministic_across_engines():
    """Two engines built from the same seed thread the same device key
    chain: sampled slot decode emits identical streams — the property
    that makes on-device sampling snapshot/hand-off safe at all."""
    a, _ = _sampled_decode(make_paged_engine(batch=2), 8)
    b, _ = _sampled_decode(make_paged_engine(batch=2), 8)
    np.testing.assert_array_equal(a, b)
    assert len(np.unique(a)) > 1  # actually sampling, not a constant


def test_sampled_stream_survives_snapshot_restore(tmp_path):
    """DLSNAP02 carries the device RNG key beside the host stream: a
    restored engine continues the sampled stream byte-identically to
    the uninterrupted run."""
    eng = make_paged_engine(batch=2)
    head, state = _sampled_decode(eng, 4)
    path = tmp_path / "mid.dlsnap"
    eng.snapshot(path)
    tail_uninterrupted, _ = _continue_decode(eng, state, 5)

    eng2 = make_paged_engine(batch=2)
    eng2.restore(path)
    tail_restored, _ = _continue_decode(eng2, state, 5)
    np.testing.assert_array_equal(tail_uninterrupted, tail_restored)


def test_snapshot_sampling_path_mismatch_rejected(tmp_path, monkeypatch):
    """A snapshot taken on the device sampling path names it in the
    meta; an engine pinned to the host path refuses the restore with
    SnapshotMismatch instead of silently switching coin streams."""
    eng = make_paged_engine(batch=2)
    assert eng.sampling_path == "device"
    _sampled_decode(eng, 2)
    path = tmp_path / "dev.dlsnap"
    eng.snapshot(path)

    monkeypatch.setenv("DLLAMA_SAMPLING_PATH", "host")
    eng2 = make_paged_engine(batch=2)
    assert eng2.sampling_path == "host"
    with pytest.raises(snapfmt.SnapshotMismatch, match="sampling_path"):
        eng2.restore(path)

    monkeypatch.setenv("DLLAMA_SAMPLING_PATH", "device")
    eng3 = make_paged_engine(batch=2)
    eng3.restore(path)  # matching path restores fine


def test_handoff_record_carries_dev_key_and_rejects_mismatch(monkeypatch):
    """DLREQ01 hand-off records export the device RNG key and the
    engine's sampling-path flag; an importer on a different sampling
    path refuses the record before touching any state."""
    monkeypatch.delenv("DLLAMA_SAMPLING_PATH", raising=False)
    sa = SlotScheduler(make_paged_engine(batch=2), prefill_chunk=4,
                       max_wait_ms=20.0, decode_burst=4)
    try:
        with injected("engine.device_step=delay:0.05"):
            t = sa.submit(list(PROMPTS[0]), 30, temperature=0.7)
            it = t.tokens()
            for _ in range(4):
                next(it)
            records = sa.handoff_export_all()
        list(it)
    finally:
        sa.close()
    assert set(records) == {t.rid}
    meta, arrays = snapfmt.loads_request(records[t.rid])
    assert meta["extra"]["sampling_path"] == "device"
    assert "rng_dev_key" in arrays  # the sampled chunk seeded the chain

    monkeypatch.setenv("DLLAMA_SAMPLING_PATH", "host")
    sb = SlotScheduler(make_paged_engine(batch=2), prefill_chunk=4,
                       max_wait_ms=20.0)
    try:
        with pytest.raises(snapfmt.SnapshotMismatch, match="sampling_path"):
            sb.import_request(records[t.rid])
    finally:
        sb.close()

    monkeypatch.setenv("DLLAMA_SAMPLING_PATH", "device")
    sc = SlotScheduler(make_paged_engine(batch=2), prefill_chunk=4,
                       max_wait_ms=20.0)
    try:
        t2, extra = sc.import_request(records[t.rid])
        assert extra["sampling_path"] == "device"
        resumed = list(t2.tokens())
        assert len(meta["extra"]["completion"]) + len(resumed) == 30
    finally:
        sc.close()
