"""Tensor-parallel serving tests (the PR-12 tentpole, on 8 forced CPU
host devices).

The slot scheduler has always been proven on tp=1; these tests run the
same serving contracts over a tp=4 mesh and hold them to the house
invariant — greedy output byte-identical to the tp=1 solo run in every
mode:

* **staggered continuous batching** — four greedy requests joining a
  tp=4 scheduler at different times, each byte-identical to its tp=1
  solo decode (the overlapped dispatch pipeline is on by default, so
  device-resident feed rows ride the sharded mesh with no host
  round-trip);
* **radix prefix sharing on a sharded pool** — a repeated system prompt
  binds cached pages on the tp-sharded paged pool, bumps the prefix-hit
  counters, and decodes byte-identically;
* **overlap off** — the non-pipelined dispatch path holds the same
  parity on tp=4;
* **preemption** — an interactive burst preempts a decoding batch slot
  (DLREQ01 park, pages freed), the victim resumes byte-identically, no
  page leaks (``pool.check()``);
* **ledger hygiene** — building a tp>1 engine on a non-TPU backend
  records the ``tp_psum`` degrade (the fused collective-matmul ring is
  TPU-only).

Config note: the suite's usual ``tiny_config`` only shards to tp=2
(n_kv_heads=2); this file widens it to n_kv_heads=4 / hidden_dim=128 so
tp=4 divides every sharded axis (see ``valid_tp_degrees``).
"""

import threading
import time

import jax
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.parallel.sharding import valid_tp_degrees
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.faults import FAULTS
from dllama_tpu.runtime.scheduler import PRIORITY_LEVELS, SlotScheduler

pytestmark = pytest.mark.tp

CFG = tiny_config(hidden_dim=128, n_kv_heads=4, seq_len=64)
PAGE = 4
P1 = [5, 9, 2]
P2 = [7, 3, 11, 4, 6, 1, 8]
P3 = [2, 4, 6]
P4 = [9, 8, 7, 6]
PROMPTS = (P1, P2, P3, P4)
TP = 4


@pytest.fixture(autouse=True)
def _clean():
    FAULTS.clear()
    yield
    FAULTS.clear()


def make_engine(tp, batch=1, **kw):
    if tp > len(jax.devices()):
        pytest.skip(f"needs {tp} devices")
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]),
                  batch=batch, **kw)


def make_paged_engine(tp, batch=2, page=PAGE):
    pages_per_slot = -(-CFG.seq_len // page)
    return make_engine(tp, batch=batch,
                       kv_pages=batch * pages_per_slot + 2,
                       kv_page_size=page)


@pytest.fixture(scope="module")
def solo_refs():
    """Greedy tp=1 solo completions — the parity oracle every tp=4 mode
    must reproduce byte-for-byte."""
    eng = Engine(CFG, init_params(CFG, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    refs = {}
    for p in PROMPTS:
        eng.reset()
        toks = [t for t, _ in eng.generate_stream(
            p, len(p) + 30, temperature=0.0, chunk=5)]
        refs[tuple(p)] = toks[len(p):]
    return refs


def test_config_actually_allows_tp4():
    assert TP in valid_tp_degrees(CFG)


def _staggered(sched, n=10, delays=(0.0, 0.05, 0.2, 0.35)):
    results = {}

    def run(p, delay):
        time.sleep(delay)
        t = sched.submit(p, n)
        results[tuple(p)] = (list(t.tokens()), t.finish)

    threads = [threading.Thread(target=run, args=(p, d))
               for p, d in zip(PROMPTS, delays)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(180)
    return results


def test_tp4_staggered_sched_parity(solo_refs):
    """Continuous batching on a tp=4 mesh, overlap pipeline on (the
    default): staggered greedy joins match tp=1 solo byte-for-byte."""
    eng = make_engine(TP, batch=4)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=30.0)
    try:
        results = _staggered(sched)
        for p in PROMPTS:
            got, finish = results[tuple(p)]
            assert got == solo_refs[tuple(p)][:10], p
            assert finish == "length"
    finally:
        sched.close()


def test_tp4_no_overlap_parity(solo_refs):
    eng = make_engine(TP, batch=4)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=30.0,
                          overlap=False)
    try:
        results = _staggered(sched)
        for p in PROMPTS:
            assert results[tuple(p)][0] == solo_refs[tuple(p)][:10], p
    finally:
        sched.close()


def test_tp4_prefix_radix_reuse_on_sharded_pool(solo_refs):
    """A repeated system prompt on the tp=4 paged pool must take the
    radix fast path (prefix counters bump) and stay byte-identical —
    page gather/scatter on a sharded cache is an addressing change,
    never a numerics change."""
    import numpy as np
    rng = np.random.RandomState(11)
    system = [int(x) for x in rng.randint(1, CFG.vocab_size, 4 * PAGE)]
    prompt = system + [3, 1]

    eng = make_paged_engine(TP, batch=2)
    sched = SlotScheduler(eng, prefill_chunk=4, prefix_reuse=True)
    hits0 = obs_metrics.PREFIX_HITS.value
    reused0 = obs_metrics.PREFIX_TOKENS_REUSED.value
    try:
        t1 = sched.submit(prompt, 8)
        o1 = list(t1.tokens())
        t2 = sched.submit(prompt, 8)
        o2 = list(t2.tokens())
    finally:
        sched.close()
    assert o1 == o2, "prefix-reused decode diverged from the cold run"
    assert obs_metrics.PREFIX_HITS.value > hits0
    assert obs_metrics.PREFIX_TOKENS_REUSED.value - reused0 == 4 * PAGE


def test_tp4_preempt_park_resume_parity(solo_refs):
    """Interactive burst preempts a tp=4 batch slot mid-decode; the
    victim parks (pages freed to the sharded pool), resumes, and
    finishes byte-identical to tp=1 solo; no pages leak."""
    eng = make_paged_engine(TP, batch=2)
    sched = SlotScheduler(eng, prefill_chunk=4, decode_burst=4,
                          preempt=True, preempt_age_ms=0.0,
                          prefix_reuse=False)
    try:
        done: dict = {}

        def run(key, prompt, n, prio):
            t = sched.submit(prompt, n, priority=prio)
            done[key] = (list(t.tokens()), t.finish, t.preempt_count)

        FAULTS.install("engine.device_step=delay:0.05x1000")
        b1 = threading.Thread(target=run, args=(
            "b1", P1, 30, PRIORITY_LEVELS["batch"]))
        b2 = threading.Thread(target=run, args=(
            "b2", P2, 30, PRIORITY_LEVELS["batch"]))
        b1.start()
        b2.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if sched.occupancy()["active"] == 2:
                break
            time.sleep(0.01)
        else:
            raise AssertionError("batch never saturated the slots")
        time.sleep(0.3)
        it = threading.Thread(target=run, args=(
            "it", P3, 6, PRIORITY_LEVELS["interactive"]))
        it.start()
        it.join(120)
        FAULTS.clear()
        b1.join(240)
        b2.join(240)

        assert done["it"][0] == solo_refs[tuple(P3)][:6]
        assert [k for k in ("b1", "b2") if done[k][2] >= 1], \
            f"no ticket recorded a preemption: {done}"
        for k, p in (("b1", P1), ("b2", P2)):
            toks, finish, _ = done[k]
            assert finish == "length", (k, finish)
            assert toks == solo_refs[tuple(p)][:30], \
                f"{k} drifted after park/resume on tp={TP}"
        occ = sched.occupancy()
        assert occ["kv_pages_free"] == occ["kv_pages_total"], occ
        sched.pool.check()
    finally:
        FAULTS.clear()
        sched.close()


def test_tp_engine_on_cpu_records_psum_degrade():
    """Satellite contract: a tp>1 engine off TPU records the
    ``tp_psum`` degrade — counter + degraded flag + warn-once — so a CPU/GPU run can never pass off a
    plain-psum decode as the fused collective number."""
    obs_dispatch.reset()
    try:
        make_engine(2)
        assert obs_dispatch.degraded() is True
        assert obs_dispatch.reasons().get("q40:tp_psum", 0) >= 1
        # tp=1 engines stay clean — no collective, no degrade
        obs_dispatch.reset()
        make_engine(1)
        assert obs_dispatch.reasons().get("q40:tp_psum", 0) == 0
    finally:
        obs_dispatch.reset()


def test_constraint_error_names_valid_degrees():
    """Satellite: every tp rejection tells the operator which degrees
    WOULD work for this model, instead of a bare modulus complaint."""
    from dllama_tpu.parallel.sharding import check_tp_constraint
    bad = 3  # heads 4, kv 4, hidden 128 — 3 divides none of them
    with pytest.raises(ValueError, match=r"valid tp degrees.*\[1, 2, 4\]"):
        check_tp_constraint(CFG, bad)
