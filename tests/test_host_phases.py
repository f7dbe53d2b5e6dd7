"""The host's part of a served step and the start-up, as the program names
them (PR 36): phase spans under ``sched.admit`` / ``sched.enqueue`` /
``engine.slot_enqueue``, the counters ``sched_host_ms{phase,kind}`` that take
the same measurement, the compile cache's counters behind ``setup_s``.

These tests hold the mechanism, not the clock: which spans a step records and
how they nest, that a counter's total IS the sum of its spans' ring durations
(``span(total=)`` measures once), what ``sched.evict`` counts, what the
``jax.monitoring`` listeners feed.  CPU, tiny model.
"""

import os
import subprocess
import sys
import time

import jax
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import metrics as obs_metrics, trace as obs_trace
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.pagepool import PagePool, RadixTree
from dllama_tpu.runtime.scheduler import SlotScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tiny_config(seq_len=64)
PAGE = 4
WORK = ("admit", "evict", "build", "h2d", "launch", "fanout", "verdict")
PHASE_SPAN = {"admit": "sched.admit", "evict": "sched.evict",
              "build": "sched.build", "h2d": "engine.h2d",
              "launch": "engine.launch", "land_wait": "sched.land_wait",
              "fanout": "sched.fanout", "verdict": "sched.verdict"}


def make_paged_engine(batch=2, kv_pages=None):
    pages_per_slot = -(-CFG.seq_len // PAGE)
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=batch,
                  kv_pages=kv_pages or batch * pages_per_slot + 1,
                  kv_page_size=PAGE)


def host_ms() -> dict:
    """``sched_host_ms`` by ``(phase, kind)``, unrounded."""
    with obs_metrics.SCHED_HOST_MS._lock:
        return dict(obs_metrics.SCHED_HOST_MS._children)


def by_phase(cells: dict) -> dict:
    out: dict = {}
    for (phase, _kind), ms in cells.items():
        out[phase] = out.get(phase, 0.0) + ms
    return out


def _inside(inner: dict, outer: dict) -> bool:
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def _run(sched, jobs, sequential=False):
    """Warm the shapes, then serve ``jobs`` with a clean ring; the loop is
    joined before anything is read, so ring and counters are final."""
    try:
        for prompt, n in jobs[:2]:
            list(sched.submit(prompt, n).tokens())   # compile outside
        sched.flush()
        obs_trace.clear()
        ms0, steps0 = host_ms(), obs_metrics.SCHED_STEPS.total
        if sequential:
            for prompt, n in jobs:
                assert len(list(sched.submit(prompt, n).tokens())) == n
        else:
            tickets = [sched.submit(prompt, n) for prompt, n in jobs]
            for t, (_, n) in zip(tickets, jobs):
                assert len(list(t.tokens())) == n
        sched.flush()
    finally:
        sched.close()
    cells = {k: v - ms0.get(k, 0.0) for k, v in host_ms().items()}
    cells = {k: v for k, v in cells.items() if v}
    return {"ring": obs_trace.TRACER.snapshot(), "cells": cells,
            "ms": by_phase(cells),
            "steps": obs_metrics.SCHED_STEPS.total - steps0}


@pytest.fixture(scope="module")
def served():
    """Two requests on two paged slots: mixed steps while they prefill,
    host-fed and pipelined pure-decode steps after."""
    sched = SlotScheduler(make_paged_engine(2), prefill_chunk=4,
                          max_wait_ms=50.0, decode_burst=2)
    return _run(sched, [([5, 9, 2], 12), ([7, 3, 11, 4, 6, 1, 8], 9)])


@pytest.fixture(scope="module")
def evicting():
    """One slot, a pool of 12 pages, requests of 4 pages whose 2 prompt
    pages stay in the prefix tree: from the sixth on, admission evicts."""
    sched = SlotScheduler(make_paged_engine(1, kv_pages=13), prefill_chunk=8)
    jobs = [([10 * i + j for j in range(1, 9)], 8) for i in range(1, 9)]
    return _run(sched, jobs, sequential=True)


def _named(run, name):
    return [s for s in run["ring"] if s["name"] == name]


def test_every_step_has_one_build_and_nests_the_engines_phases(served):
    enq = {s["args"]["seq"]: s for s in _named(served, "sched.enqueue")}
    assert len(enq) == len(_named(served, "sched.enqueue")) == served["steps"]
    assert any(s["args"]["overlapped"] for s in enq.values())      # pipelined
    assert any(not s["args"]["overlapped"] for s in enq.values())  # host-fed
    assert any(s["args"]["prefill_rows"] for s in enq.values())    # mixed
    builds = [s for s in _named(served, "sched.build") if "seq" in s["args"]]
    assert sorted(s["args"]["seq"] for s in builds) == sorted(enq)
    for b in builds:
        e = enq[b["args"]["seq"]]
        assert b["args"]["overlapped"] == e["args"]["overlapped"]
        assert {b["args"][k] for k in ("t", "steps", "rows")} \
            == {e["args"][k] for k in ("t", "steps", "rows")}
        if e["args"]["overlapped"]:   # the plan comes before its enqueue
            assert b["ts"] + b["dur"] <= e["ts"]
        else:
            assert _inside(b, e)
    slot = _named(served, "engine.slot_enqueue")
    h2d, launch = _named(served, "engine.h2d"), _named(served, "engine.launch")
    assert len(slot) == len(h2d) == len(launch) == len(enq)
    for e in enq.values():
        (s,) = [x for x in slot if _inside(x, e)]
        (h,) = [x for x in h2d if _inside(x, s)]
        (la,) = [x for x in launch if _inside(x, s)]
        assert h["ts"] + h["dur"] <= la["ts"]
        # a pipelined step's tokens are on the device; either way the host
        # operands cross as one packed array (PR 55; before: 6 / 7 uploads)
        assert h["args"]["feed_dev"] == e["args"]["overlapped"]
        assert h["args"]["arrays"] == 1
        assert h["args"]["bytes"] > 0 and la["args"]["fresh"] is False


def test_a_plan_that_declines_takes_no_seq(served):
    """While a row is mid-prefill ``_maybe_pipeline`` declines: its
    ``sched.build`` is in the ring without a ``seq`` (nobody enqueues one)
    and its time counts as ``build/round``."""
    declined = [s for s in _named(served, "sched.build")
                if "seq" not in s["args"]]
    assert declined and all(s["args"]["overlapped"] for s in declined)
    assert served["cells"]["build", "round"] == pytest.approx(
        1e3 * sum(s["dur"] for s in declined), rel=1e-9)
    taken = [s["args"]["seq"] for s in _named(served, "sched.build")
             if "seq" in s["args"]]
    assert len(taken) == len(set(taken)) == served["steps"]


@pytest.mark.parametrize("phase", [*WORK, "land_wait"])
def test_a_phases_counter_is_the_sum_of_its_spans(served, evicting, phase):
    """One measurement feeds ring and counter: the counter's growth equals
    the ring durations of the phase's span (``sched.admit``: less the
    ``sched.evict`` inside it)."""
    run = evicting if phase in ("admit", "evict") else served
    spans = _named(run, PHASE_SPAN[phase])
    assert spans, phase
    want = sum(s["dur"] for s in spans)
    if phase == "admit":
        ev = _named(run, "sched.evict")
        assert ev and all(any(_inside(e, a) for a in spans) for e in ev)
        want -= sum(e["dur"] for e in ev)
    assert run["ms"][phase] == pytest.approx(want * 1e3, rel=1e-9, abs=1e-9)


def test_phase_kinds_are_the_steps_kinds(served, evicting):
    """``build`` counts under the kind its shape turns out to have (a
    declined plan under ``round``), the engine's phases under the kind the
    engine is given, the round's head under ``round``."""
    kinds = {phase: {k for p, k in served["cells"] if p == phase}
             for phase in PHASE_SPAN}
    assert kinds["build"] == {"decode", "mixed", "round"}
    for phase in ("h2d", "launch", "fanout", "land_wait"):
        assert kinds[phase] == {"decode", "mixed"}, phase
    assert kinds["verdict"] == {"decode"}
    assert {k for p, k in evicting["cells"] if p in ("admit", "evict")} \
        == {"round"}


def test_admission_evicts_inside_admit(evicting):
    ev = _named(evicting, "sched.evict")
    assert ev and all(e["args"]["asked"] >= 1 for e in ev)
    assert all(e["args"]["freed"] == e["args"]["asked"] for e in ev)
    assert all(e["args"]["visited"] >= e["args"]["asked"] for e in ev)


def test_evict_walks_the_whole_tree_once_a_page():
    """``k`` pages from a tree of ``n`` dead leaves: ``visited`` is the sum
    of the tree's size before each walk, about ``k * n``.  The PR that frees
    pages in one walk re-pins this to about ``n``."""
    n, k = 40, 6
    pool = PagePool(n + 2, 2)
    tree = RadixTree(pool)
    for i in range(n):
        (page,) = pool.alloc(1)
        tree.insert([2 * i + 1, 2 * i + 2], [page])
        pool.decref([page])          # the tree holds the only reference
    obs_trace.clear()
    got0 = obs_metrics.SCHED_HOST_MS.get("evict", "round")
    assert tree.evict(k) == k
    (span,) = [s for s in obs_trace.TRACER.snapshot()
               if s["name"] == "sched.evict"]
    assert span["args"] == {"asked": k, "freed": k,
                            "visited": sum(n - i for i in range(k))}
    assert obs_metrics.SCHED_HOST_MS.get("evict", "round") - got0 \
        == pytest.approx(span["dur"] * 1e3, rel=1e-9)


def test_a_span_less_its_child_keeps_self_time():
    total, less = obs_metrics.host_ms("admit", "test"), \
        obs_metrics.host_ms("evict", "test")
    t0, l0 = total.received, less.received
    obs_trace.clear()
    with obs_trace.span("sched.admit", total=total, less=less):
        with obs_trace.span("sched.evict", total=less):
            time.sleep(0.002)
    inner, outer = obs_trace.TRACER.snapshot()
    assert (inner["name"], outer["name"]) == ("sched.evict", "sched.admit")
    assert less.received - l0 == pytest.approx(inner["dur"], rel=1e-6)
    assert total.received - t0 == pytest.approx(outer["dur"] - inner["dur"],
                                                rel=1e-6, abs=1e-9)


def test_a_block_can_name_its_counter_late():
    cell = obs_metrics.host_ms("build", "test")
    got0 = cell.received
    obs_trace.clear()
    with obs_trace.span("sched.build", seq=3) as args:
        args["t"] = 16
        args.total = cell
    (rec,) = obs_trace.TRACER.snapshot()
    assert rec["args"] == {"seq": 3, "t": 16} and type(rec["args"]) is dict
    assert cell.received - got0 == pytest.approx(rec["dur"], rel=1e-6)


def test_a_span_with_a_total_never_imports_jax():
    code = ("import sys; from dllama_tpu.obs import metrics, trace; "
            "cell = metrics.host_ms('admit', 'round')\n"
            "with trace.span('sched.admit', seq=1, total=cell): pass\n"
            "assert not metrics.watch_compiles(); "
            "assert 'jax' not in sys.modules; "
            "assert cell.received == trace.TRACER.snapshot()[0]['dur'] > 0")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_a_cache_hit_event_bumps_the_counter():
    assert obs_metrics.watch_compiles() and obs_metrics.watch_compiles()
    hits0 = obs_metrics.COMPILE_CACHE_HITS.value
    writes0 = obs_metrics.COMPILE_CACHE_WRITES.value
    secs0 = obs_metrics.COMPILE_CACHE_RETRIEVAL_SECONDS.value
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/some/other/event")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    assert obs_metrics.COMPILE_CACHE_HITS.value == hits0 + 1  # listeners: once
    assert obs_metrics.COMPILE_CACHE_WRITES.value == writes0 + 1
    assert obs_metrics.COMPILE_CACHE_RETRIEVAL_SECONDS.value == secs0 + 0.25


def test_a_fresh_program_is_an_engine_compile_span():
    """One real compile: JAX's listeners see the backend's seconds, and the
    slot program's first call is an ``engine.compile`` span inside its
    ``engine.launch``, carrying what was counted meanwhile."""
    import numpy as np
    eng = make_paged_engine(2)           # the engine installs the listeners
    secs0 = obs_metrics.BACKEND_COMPILE_SECONDS.value
    trace0 = obs_metrics.JAXPR_TRACE_SECONDS.value
    host_ms = lambda phase, kind: obs_metrics.host_ms(  # noqa: E731
        phase, kind).received * 1e3
    compile0 = host_ms("compile", "decode")
    launch0 = host_ms("launch", "decode")
    obs_trace.clear()
    b = 2
    ptab = np.zeros((b, CFG.seq_len // PAGE), np.int32)
    step = lambda: eng.slot_step_async(  # noqa: E731
        np.ones((b, 1), np.int32), np.zeros(b, np.int32),
        np.ones(b, np.int32), temps_np=np.zeros(b, np.float32),
        topps_np=np.ones(b, np.float32), page_tables_np=ptab).wait()
    step()
    grew = obs_metrics.BACKEND_COMPILE_SECONDS.value - secs0
    assert grew > 0 and obs_metrics.JAXPR_TRACE_SECONDS.value > trace0
    ring = obs_trace.TRACER.snapshot()
    (comp,) = [s for s in ring if s["name"] == "engine.compile"]
    (launch,) = [s for s in ring if s["name"] == "engine.launch"]
    assert _inside(comp, launch) and launch["args"]["fresh"] is True
    assert comp["args"]["key"].startswith("('slot_paged', 1, 1, True")
    assert 0 < comp["args"]["backend_s"] <= grew + 1e-6
    assert 0 <= comp["args"]["compiled_s"] <= comp["args"]["backend_s"]
    assert comp["args"]["cache_requests"] >= comp["args"]["cache_hits"] >= 0
    # a launch that compiled is the compile cell's, not the launch cell's
    assert launch["args"]["compiled"] is True
    assert host_ms("compile", "decode") - compile0 == pytest.approx(
        launch["dur"] * 1e3, rel=1e-6)
    assert host_ms("launch", "decode") == launch0
    # the engine knows the program from here on (no ``engine.compile`` span),
    # but ``jax.jit`` may trace it once more for operands placed anew (the
    # cache the first call returned): that launch is a compile too
    step()
    step()
    launches = [s for s in obs_trace.TRACER.snapshot()
                if s["name"] == "engine.launch"]
    assert "compiled" not in launches[-1]["args"]
    assert host_ms("launch", "decode") - launch0 == pytest.approx(
        sum(s["dur"] for s in launches
            if "compiled" not in s["args"]) * 1e3, rel=1e-6)
    assert len([s for s in obs_trace.TRACER.snapshot()
                if s["name"] == "engine.compile"]) == 1


def test_the_load_gauge_outlives_the_ring():
    make_paged_engine(1)
    obs_trace.clear()
    assert obs_metrics.ENGINE_LOAD_SECONDS.get("place") > 0
    assert "place" in obs_metrics.snapshot_json()["engine_load_seconds"]
