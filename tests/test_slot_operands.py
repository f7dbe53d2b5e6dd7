"""How a slot program's host operands reach the device (PR 55):
``Engine._slot_operands`` packs them into one numpy vector
(``decode_loop.pack_slot_operands``) that the jitted call uploads in its own
argument path and takes apart on the device; nothing is uploaded one
``jnp.asarray`` at a time in front of it.

Held here, on paged and contiguous engines alike: no Python-level upload of a
host operand on a host-fed step, a pipelined step and a verify burst, with the
``engine.h2d`` span where it was; one executable a key whatever dtypes and
strides the caller hands in; and the hand-over is a copy, so the caller may
overwrite every buffer the moment the enqueue returns.  CPU, tiny model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import metrics as obs_metrics, trace as obs_trace
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.decode_loop import (pack_slot_operands,
                                            unpack_slot_operands)
from dllama_tpu.runtime.engine import Engine

CFG = tiny_config(seq_len=64)
PAGE = 4
PAGES_PER_SLOT = CFG.seq_len // PAGE
B = 2
PROMPTS = ([5, 9, 2, 7], [7, 3, 11, 4])
ENGINES = pytest.mark.parametrize("paged", [True, False],
                                  ids=["paged", "contiguous"])


def make_engine(paged: bool) -> Engine:
    pool = dict(kv_pages=B * PAGES_PER_SLOT + 1, kv_page_size=PAGE) \
        if paged else {}
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=B,
                  **pool)


def operands(paged: bool, t: int, pos: int, n_valid: int) -> dict:
    """One step's host operands in the dtypes the programs are traced with;
    slot ``r`` owns pages ``1 + r * PAGES_PER_SLOT ..`` (page 0 is scratch)."""
    ops = dict(pos_rows_np=np.full(B, pos, np.int32),
               n_valid_np=np.full(B, n_valid, np.int32),
               temps_np=np.zeros(B, np.float32),
               topps_np=np.full(B, 0.9, np.float32),
               topks_np=np.zeros(B, np.int32))
    if paged:
        ops["page_tables_np"] = 1 + np.arange(
            B * PAGES_PER_SLOT, dtype=np.int32).reshape(B, PAGES_PER_SLOT)
    return ops


def prompt_tokens() -> np.ndarray:
    return np.array(PROMPTS, np.int32)


def serve(eng: Engine, paged: bool, *, mangle=None, scribble=False):
    """A host-fed prefill step, a host-fed decode step, a pipelined decode
    step and a verify burst over both rows; every sampled id, in order.
    ``mangle`` rewrites a step's operands before the call; ``scribble``
    overwrites every one of them with garbage between the enqueue and the
    wait."""
    mangle = mangle or (lambda tokens, ops: (tokens, ops))
    out = []

    def run(call, tokens, ops, **kw):
        tokens, ops = mangle(tokens, ops)
        handle = call(tokens, **ops, **kw)
        if scribble:
            for a in (tokens, *ops.values()):
                if a is not None:
                    a[...] = 7 if a.ndim > 1 else 63
        return handle

    t = len(PROMPTS[0])
    first = run(eng.slot_step_async, prompt_tokens(), operands(paged, t, 0, t))
    ids = first.wait()
    out.append(ids)
    fed = np.ascontiguousarray(ids[-1][:, None]).astype(np.int32)
    second = run(eng.slot_step_async, fed, operands(paged, 1, t, 1))
    third = run(eng.slot_step_async, None, operands(paged, 1, t + 1, 1),
                feed_dev=second.last_dev)
    out += [second.wait(), third.wait()]
    window = np.zeros((B, 3), np.int32)
    window[:, 0] = out[-1][-1]
    window[:, 1:] = [[9, 4], [2, 8]]          # drafts; what they are is moot
    preds, accepted = run(eng.slot_verify_async, window,
                          operands(paged, 3, t + 2, 3)).wait()
    out += [preds, accepted]
    return [np.asarray(a) for a in out]


def same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module", params=[True, False],
                ids=["paged", "contiguous"])
def plain(request):
    """An untouched run of ``serve`` with its engine (every key compiled)."""
    eng = make_engine(request.param)
    return request.param, eng, serve(eng, request.param)


def test_no_host_operand_is_uploaded_from_python(plain, monkeypatch):
    """``jnp.asarray`` and ``jax.device_put`` are not called while a step is
    enqueued (the parent: 7 / 6 on host arrays a paged step, and one on the
    pipelined step's on-device tokens)."""
    paged, eng, want = plain
    seen = []

    def counting(fn):
        def wrapped(x, *a, **kw):
            seen.append(type(x))
            return fn(x, *a, **kw)
        return wrapped

    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    got = serve(eng, paged)
    monkeypatch.undo()
    same(got, want)
    assert seen == []


@ENGINES
def test_the_h2d_span_stays_in_front_of_the_launch(paged):
    """``engine.h2d`` inside ``engine.slot_enqueue`` and before
    ``engine.launch``, with what it hands over; its counter is the sum of
    its spans."""
    eng = make_engine(paged)
    serve(eng, paged)                # compile every key outside the ring
    obs_trace.clear()
    cells = [obs_metrics.host_ms("h2d", k) for k in
             ("mixed", "decode", "verify")]
    ms0 = [c.received for c in cells]
    serve(eng, paged)
    ring = obs_trace.TRACER.snapshot()
    named = lambda n: [s for s in ring if s["name"] == n]  # noqa: E731
    slot, h2d, launch = (named("engine.slot_enqueue"), named("engine.h2d"),
                         named("engine.launch"))
    assert len(slot) == len(h2d) == len(launch) == 4
    for s, h, la, fed in zip(slot, h2d, launch, [False, False, True, False]):
        assert s["ts"] <= h["ts"] and h["ts"] + h["dur"] <= la["ts"]
        assert la["ts"] + la["dur"] <= s["ts"] + s["dur"]
        assert h["args"]["arrays"] == 1       # the packed operands; no mask
        assert h["args"]["feed_dev"] is fed
        assert la["args"]["fresh"] is False
    # one int32 vector: B x t tokens (a pipelined step keeps its unread
    # column), five operands of B, the flag, the table
    rest = 5 * B + 1 + (B * PAGES_PER_SLOT if paged else 0)
    assert [h["args"]["bytes"] for h in h2d] == [
        4 * (B * t + rest) for t in (len(PROMPTS[0]), 1, 1, 3)]
    grew = sum(c.received - m for c, m in zip(cells, ms0))
    assert grew == pytest.approx(sum(h["dur"] for h in h2d), rel=1e-9)


def test_one_executable_whatever_the_callers_dtypes(plain):
    """int64 positions, float64 temperatures, a strided token block and a
    non-contiguous page-table view run the programs the plain operands
    compiled: the same ids, no new trace, no new key.  A key's program was
    traced once in all: a host-fed and a pipelined step share it."""
    paged, eng, want = plain

    def mangle(tokens, ops):
        ops = dict(ops,
                   pos_rows_np=ops["pos_rows_np"].astype(np.int64),
                   n_valid_np=ops["n_valid_np"].astype(np.int64),
                   temps_np=ops["temps_np"].astype(np.float64),
                   topps_np=ops["topps_np"].astype(np.float64),
                   topks_np=None)
        if paged:
            wide = np.zeros((B, 2 * PAGES_PER_SLOT), np.int64)
            wide[:, ::2] = ops["page_tables_np"]
            ops["page_tables_np"] = wide[:, ::2]
            assert not ops["page_tables_np"].flags.c_contiguous
        if tokens is not None:
            tokens = np.asfortranarray(tokens.astype(np.int64))
        return tokens, ops

    sizes = {k: fn._cache_size() for k, fn in eng._chunk_fns.items()}
    (decode,) = [k for k in sizes if k[0] in ("slot", "slot_paged")
                 and k[1] == 1]
    assert len(sizes) == 3 and sizes[decode] == 1
    compiles0 = obs_metrics.ENGINE_RECOMPILES.value
    same(serve(eng, paged, mangle=mangle), want)
    assert {k: fn._cache_size() for k, fn in eng._chunk_fns.items()} == sizes
    assert obs_metrics.ENGINE_RECOMPILES.value == compiles0


def test_the_hand_over_is_a_copy(plain):
    """Every host operand, the page table included, is overwritten with
    garbage the moment the enqueue returns and before ``wait``: the ids are
    those of the untouched run (the scheduler hands over ``_page_tables``
    itself and rebuilds it for the next step)."""
    paged, eng, want = plain
    same(serve(eng, paged, scribble=True), want)


@pytest.mark.parametrize("t,fed", [(1, False), (1, True), (5, False)],
                         ids=["host-fed", "pipelined", "chunk"])
@ENGINES
def test_the_packed_vector_unpacks_to_its_operands(paged, t, fed):
    """Bit for bit through ``pack_slot_operands`` and back on the device:
    floats by their bits, a fed step's tokens from the device's own."""
    rng = np.random.RandomState(3)
    b, pages = 3, 7
    host = dict(tokens=None if fed else rng.randint(0, 999, (b, t)),
                pos_rows=rng.randint(0, 50, b),
                n_valid=rng.randint(1, t + 1, b).astype(np.int32),
                temps=np.array([0.0, 0.7, 1.3]),
                topps=np.array([0.9, 0.95, 1.0], np.float32),
                topks=np.array([0, 40, 3]),
                page_tables=rng.randint(0, 99, (b, pages)) if paged else None)
    ops = pack_slot_operands(**host)
    assert ops.dtype == np.int32 and ops.ndim == 1
    assert not ops.flags.writeable          # nobody's buffer but the call's
    last = jnp.asarray([11, 12, 13], jnp.int32)
    got = jax.jit(lambda o, f: unpack_slot_operands(o, f, t, paged))(ops, last)
    want = dict(host, tokens=np.asarray(last)[:, None] if fed
                else host["tokens"], page_table=host["page_tables"])
    del want["page_tables"]
    assert set(got) == set(want)
    for name, x in want.items():
        if x is None:
            assert got[name] is None
            continue
        kind = np.float32 if name in ("temps", "topps") else np.int32
        assert got[name].dtype == kind, name
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(x, kind), err_msg=name)


def test_topks_may_be_left_out():
    ops = pack_slot_operands(np.ones((2, 1)), np.zeros(2), np.ones(2),
                             np.zeros(2), np.ones(2))
    got = unpack_slot_operands(jnp.asarray(ops), jnp.zeros(2, jnp.int32), 1,
                               False)
    assert np.asarray(got["topks"]).tolist() == [0, 0]
    assert np.asarray(got["tokens"]).tolist() == [[1], [1]]


@pytest.mark.parametrize("bad", ["pos_rows", "temps", "tokens", "page_tables"])
def test_operands_of_other_lengths_are_refused(bad):
    """A packed operand is found by where it starts, so one row too many
    anywhere is an error on the host, not another operand's values."""
    host = dict(tokens=np.ones((2, 3)), pos_rows=np.zeros(2),
                n_valid=np.ones(2), temps=np.zeros(2), topps=np.ones(2),
                topks=np.zeros(2), page_tables=np.ones((2, 4)))
    pack_slot_operands(**host)
    host[bad] = np.ones(9) if host[bad].ndim > 1 else np.ones(3)
    with pytest.raises(ValueError, match="number of rows"):
        pack_slot_operands(**host)
