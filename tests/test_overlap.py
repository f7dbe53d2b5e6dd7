"""Overlapped dispatch pipeline tests (runtime/scheduler.py two-deep
pipeline + runtime/engine.py ``slot_step_async`` / ``feed_dev``).

The tentpole contracts, each pinned here on CPU with a tiny model:

* **device feedback parity** — an async dispatch chain fed by the
  previous dispatch's on-device last-token row (``feed_dev``, no
  device→host→device round trip) is byte-identical to the synchronous
  host-feedback chain, and the ``fresh`` compile bit reports executable
  reuse honestly;
* **overlap on/off byte parity** — greedy output under ragged staggered
  traffic is identical with the pipeline on and off, including EOS
  stops and cancels (partial output is a prefix of the solo run);
* **flush correctness** — a hand-off export fired mid-pipeline lands
  and discards the in-flight pipelined dispatch before any DLREQ01
  snapshot is taken (zero in-flight observed), and the exported request
  resumes byte-identically on a peer;
* **honest accounting** — host gap hidden behind device compute is
  reported as hidden (timeline ``hidden_host_ms`` + the hidden-gap
  counter), never silently dropped; discarded dispatches are marked and
  counted; the goodput components still telescope (the existing
  test_scheduler.py sum-to-wall test runs with overlap on by default);
* **EMA compile poisoning** — a fresh-compile dispatch's trace+compile
  wall never moves the burst-size EMA;
* **parked wakeups** — an idle scheduler wakes from its parked wait a
  handful of times per second (deadline-derived timeout, 0.5s cap),
  not the old fixed-0.1s poll's ~10/s, while queued-deadline expiry
  stays accurate.
"""

import threading
import time

import numpy as np
import pytest

import jax

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import flight as obs_flight, metrics as obs_metrics
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime import snapshot as snapfmt
from dllama_tpu.runtime.engine import Engine, SlotDispatch
from dllama_tpu.runtime.faults import FAULTS, injected
from dllama_tpu.runtime.scheduler import SlotScheduler

CFG = tiny_config(seq_len=64)
PAGE = 4
P1 = [5, 9, 2]
P2 = [7, 3, 11, 4, 6, 1, 8]
P3 = [2, 4, 6]
P4 = [9, 8, 7, 6]
PROMPTS = (P1, P2, P3, P4)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.clear()
    yield
    FAULTS.clear()


def make_engine(batch=1):
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch)


def make_paged_engine(batch=2, page=PAGE):
    pages_per_slot = -(-CFG.seq_len // page)
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                  batch=batch,
                  kv_pages=batch * pages_per_slot + 1,
                  kv_page_size=page)


@pytest.fixture(scope="module")
def solo_refs():
    """Greedy solo completions per prompt — the parity oracle."""
    eng = make_engine()
    refs = {}
    for p in PROMPTS:
        eng.reset()
        toks = [t for t, _ in eng.generate_stream(
            p, len(p) + 30, temperature=0.0, chunk=5)]
        refs[tuple(p)] = toks[len(p):]
    return refs


# -- engine layer: slot_step_async + device-resident feedback --------------

def test_slot_step_async_feed_parity():
    """The async chain fed by ``last_dev`` must be byte-identical to the
    synchronous host-feedback chain, with no host transfer of the fed
    tokens (``last_dev`` stays a device array)."""
    e_sync, e_async = make_engine(2), make_engine(2)
    b = 2
    tokens = np.zeros((b, 4), np.int32)
    tokens[0, :len(P1)] = P1
    tokens[1, :] = P4
    n_valid = np.array([len(P1), 4], np.int32)
    pos = np.zeros((b,), np.int32)
    temps = np.zeros((b,), np.float32)
    topps = np.full((b,), 0.9, np.float32)

    # sync path: host feedback each burst
    out_sync = [e_sync.slot_step(tokens, pos, n_valid, temps_np=temps,
                                 topps_np=topps, steps=1)]
    pos_s = pos + n_valid
    for _ in range(3):
        fed = out_sync[-1][-1][:, None].astype(np.int32)
        out_sync.append(e_sync.slot_step(fed, pos_s, np.ones((b,), np.int32),
                                         temps_np=temps, topps_np=topps,
                                         steps=4))
        pos_s = pos_s + 4

    # async path: device-resident feedback, land only at the end
    handles = [e_async.slot_step_async(tokens, pos, n_valid, temps_np=temps,
                                       topps_np=topps, steps=1)]
    assert isinstance(handles[0], SlotDispatch)
    assert handles[0].fresh  # first executable for this key
    pos_a = pos + n_valid
    for _ in range(3):
        handles.append(e_async.slot_step_async(
            None, pos_a, np.ones((b,), np.int32), temps_np=temps,
            topps_np=topps, steps=4, feed_dev=handles[-1].last_dev))
        pos_a = pos_a + 4
    # the fed token block never visited the host
    assert all(isinstance(h.last_dev, jax.Array) for h in handles)
    out_async = [h.wait() for h in handles]
    # the decode-burst executable was minted once, then reused
    assert handles[1].fresh and not handles[2].fresh and not handles[3].fresh
    for a, s in zip(out_async, out_sync):
        np.testing.assert_array_equal(a, s)


def test_slot_step_async_feed_dev_validation():
    eng = make_engine(2)
    with pytest.raises(ValueError, match="feed_dev"):
        eng.slot_step_async(np.zeros((2, 1), np.int32), np.zeros((2,), np.int32),
                            np.ones((2,), np.int32),
                            temps_np=np.zeros((2,), np.float32),
                            topps_np=np.full((2,), 0.9, np.float32),
                            feed_dev=jax.numpy.zeros((2,), jax.numpy.int32))
    with pytest.raises(ValueError, match="tokens_np or feed_dev"):
        eng.slot_step_async(None, np.zeros((2,), np.int32),
                            np.ones((2,), np.int32),
                            temps_np=np.zeros((2,), np.float32),
                            topps_np=np.full((2,), 0.9, np.float32))


# -- scheduler: overlap on/off byte parity ---------------------------------

def _run_traffic(sched, solo_refs, *, eos_prompt=None, eos_at=3):
    """Staggered ragged greedy traffic; returns {prompt: (tokens, finish)}.
    ``eos_prompt`` additionally runs one request with an EOS id picked
    from its own solo reference (stop-mid-burst coverage)."""
    results = {}

    def run(p, delay, max_new, eos_ids):
        time.sleep(delay)
        t = sched.submit(p, max_new, eos_ids=eos_ids)
        results[tuple(p)] = (list(t.tokens()), t.finish)

    jobs = [(p, d, 12, ()) for p, d in zip(PROMPTS, (0.0, 0.03, 0.2, 0.4))]
    if eos_prompt is not None:
        ref = solo_refs[tuple(eos_prompt)]
        jobs.append((list(eos_prompt) + [13], 0.1, 25, (ref[eos_at],)))
    threads = [threading.Thread(target=run, args=j) for j in jobs]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    return results


def test_overlap_on_off_greedy_byte_parity(solo_refs):
    """Acceptance: greedy output is byte-identical with the pipeline on
    vs off under ragged staggered traffic, and the on-path actually
    overlapped dispatches."""
    outs = {}
    for overlap in (False, True):
        sched = SlotScheduler(make_engine(4), prefill_chunk=4,
                              max_wait_ms=50.0, decode_burst=6,
                              overlap=overlap)
        try:
            outs[overlap] = _run_traffic(sched, solo_refs)
            if overlap:
                assert sched._n_overlapped > 0, \
                    "steady-state decode never entered the pipeline"
                sched.flush()  # the last round may still be landing
                assert sched._inflight_n == 0 and sched._depth == 0
            else:
                assert sched._n_overlapped == 0
        finally:
            sched.close()
    assert outs[True] == outs[False]
    for p in PROMPTS:
        got, finish = outs[True][tuple(p)]
        assert got == solo_refs[tuple(p)][:12], p
        assert finish == "length"


def test_overlap_eos_stop_parity(solo_refs):
    """A row hitting EOS mid-pipeline retires row-wise; its neighbors'
    output and its own truncation point match the synchronous path."""
    outs = {}
    for overlap in (False, True):
        sched = SlotScheduler(make_engine(4), prefill_chunk=4,
                              max_wait_ms=50.0, decode_burst=6,
                              overlap=overlap)
        try:
            outs[overlap] = _run_traffic(sched, solo_refs, eos_prompt=P2)
        finally:
            sched.close()
    assert outs[True] == outs[False]
    eos_key = tuple(list(P2) + [13])
    got, finish = outs[True][eos_key]
    assert finish == "stop"


def test_overlap_cancel_partial_prefix(solo_refs):
    """Cancel mid-decode with the pipeline live: the partial output is a
    prefix of the solo run (no token from a discarded dispatch leaks)."""
    sched = SlotScheduler(make_engine(4), prefill_chunk=4, decode_burst=6,
                          overlap=True)
    try:
        with injected("engine.device_step=delay:0.02x100000"):
            t = sched.submit(P1, 50)
            got = []
            for tok in t.tokens():
                got.append(tok)
                if len(got) >= 3:
                    t.cancel("aborted")
        assert t.finish == "aborted"
        assert got == solo_refs[tuple(P1)][:len(got)]
        assert 0 < len(got) < 50
        assert sched._inflight_n == 0 and sched._depth == 0
    finally:
        sched.close()


# -- flush correctness ------------------------------------------------------

@pytest.fixture(scope="module")
def paged_solo_ref():
    eng = make_engine(1)
    toks = [t for t, _ in eng.generate_stream(
        P1, len(P1) + 30, temperature=0.0, chunk=5)]
    return toks[len(P1):]


def test_handoff_export_flushes_pipeline(paged_solo_ref):
    """Acceptance: a hand-off export fired mid-pipeline observes zero
    in-flight dispatches at every DLREQ01 snapshot, and the exported
    request resumes byte-identically on a peer scheduler."""
    sa = SlotScheduler(make_paged_engine(), prefill_chunk=4,
                       max_wait_ms=20.0, decode_burst=4, overlap=True)
    sb = SlotScheduler(make_paged_engine(), prefill_chunk=4,
                       max_wait_ms=20.0, decode_burst=4, overlap=True)
    inflight_seen = []
    real_export = sa._export_slot_locked

    def spying_export(slot_idx):
        inflight_seen.append(sa._inflight_n)
        return real_export(slot_idx)

    sa._export_slot_locked = spying_export
    try:
        with injected("engine.device_step=delay:0.05x100000"):
            # a second concurrent stream plus a cancel exercise the
            # cancel-flush path while the export flush runs
            t_bg = sa.submit(P3, 40, temperature=0.0)
            t = sa.submit(P1, 30, temperature=0.0)
            it = t.tokens()
            consumed = [next(it) for _ in range(6)]
            t_bg.cancel("aborted")
            records = sa.handoff_export_all()
        list(it)
        assert t.finish == "handoff"
        assert t.rid in records
        assert inflight_seen and all(n == 0 for n in inflight_seen), \
            inflight_seen
        # the cancel of t_bg lands on the scheduler thread: wait (bounded)
        # for it to go quiet instead of racing its last dispatch
        deadline = time.monotonic() + 20.0
        while (sa._inflight_n or sa._depth) and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sa._inflight_n == 0 and sa._depth == 0

        meta, _ = snapfmt.loads_request(records[t.rid])
        replayed = [int(x) for x in meta["extra"]["completion"]]
        assert replayed[:len(consumed)] == consumed
        t2, _ = sb.import_request(records[t.rid])
        resumed = list(t2.tokens())
        assert t2.finish == "length"
        assert replayed + resumed == paged_solo_ref
    finally:
        sa.close()
        sb.close()


def test_flush_discards_inflight_dispatch():
    """flush() lands-and-discards the pipelined dispatch: the discard
    counter moves, the timeline marks the entry discarded, and greedy
    output is unaffected."""
    sched = SlotScheduler(make_engine(2), prefill_chunk=4, decode_burst=4,
                          overlap=True)
    # warm every executable off the clock (prefill chunk widths + the
    # decode-burst key the pipelined dispatch shares) — CPU compiles
    # take ~1s each and would otherwise stall the timed phase below
    list(sched.submit(P2, 8).tokens())
    obs_flight.TIMELINE.clear()
    before = obs_metrics.SCHED_OVERLAP_DISCARDS.value
    try:
        with injected("engine.device_step=delay:0.05x100000"):
            t = sched.submit(P2, 50)

            def wait_inflight(timeout=20.0):
                """Bounded wait for a pipelined dispatch on the device —
                the condition a discard needs; a fixed sleep missed it
                under loaded xdist workers."""
                deadline = time.monotonic() + timeout
                while sched._inflight_n == 0 and time.monotonic() < deadline:
                    time.sleep(0.002)

            for _ in range(5):
                wait_inflight()
                sched.flush()
                assert sched._inflight_n == 0
            t.cancel("aborted")
            list(t.tokens())
    finally:
        sched.close()
    assert obs_metrics.SCHED_OVERLAP_DISCARDS.value > before, \
        "five flushes against a saturated pipeline never caught a " \
        "pipelined dispatch in flight"
    discarded = [e for e in obs_flight.TIMELINE.snapshot()
                 if e.get("discarded")]
    assert discarded
    for e in discarded:
        assert e["overlapped"] and e["steps"] >= 1
        assert all(s["phase"] == "pad" for s in e["slots"])


# -- honest accounting ------------------------------------------------------

def test_hidden_host_gap_reported_as_hidden(solo_refs):
    """Host gap the pipeline hid behind device compute must show up as
    ``hidden_host_ms`` on overlapped timeline entries and in the hidden
    counter — not vanish, and not pollute the exposed histogram."""
    sched = SlotScheduler(make_engine(2), prefill_chunk=4, decode_burst=4,
                          overlap=True)
    obs_flight.TIMELINE.clear()
    hidden_before = obs_metrics.SCHED_HOST_GAP_HIDDEN_MS.value
    try:
        # device busy 30ms per dispatch, host fanout 5ms per dispatch:
        # the 5ms rides entirely under the in-flight dispatch
        with injected("engine.device_step=delay:0.03x100000,"
                      "sched.host_fanout=delay:0.005x100000"):
            t = sched.submit(P1, 16)
            assert list(t.tokens()) == solo_refs[tuple(P1)][:16]
    finally:
        sched.close()
    entries = obs_flight.TIMELINE.snapshot()
    overlapped = [e for e in entries
                  if e["overlapped"] and not e.get("discarded")]
    assert overlapped, "no dispatch overlapped under steady decode"
    assert any(e["hidden_host_ms"] > 0 for e in overlapped)
    # hidden gap is charged to the hidden counter, and an overlapped
    # entry never double-counts the same ms as exposed host_gap
    assert obs_metrics.SCHED_HOST_GAP_HIDDEN_MS.value > hidden_before
    for e in overlapped:
        if e["hidden_host_ms"] > 0:
            assert e["host_gap_ms"] == 0
    # non-discarded overlapped entries carry live rows, mark the mode
    assert any(s["phase"] == "decode"
               for e in overlapped for s in e["slots"])


def test_overlap_metrics_in_both_formats(solo_refs):
    """Acceptance: pipeline state is exported in the JSON snapshot and
    the Prometheus rendering."""
    sched = SlotScheduler(make_engine(2), prefill_chunk=4, decode_burst=4,
                          overlap=True)
    try:
        t = sched.submit(P3, 12)
        assert list(t.tokens()) == solo_refs[tuple(P3)][:12]
        assert sched._n_overlapped > 0
    finally:
        sched.close()
    js = obs_metrics.snapshot_json()
    for key in ("sched_overlap_ratio", "sched_inflight_depth",
                "sched_host_gap_hidden_ms", "sched_overlap_discards"):
        assert key in js, key
    assert 0 < js["sched_overlap_ratio"] <= 1.0
    assert js["sched_inflight_depth"] == 0  # pipeline drained at close
    prom = obs_metrics.render_prometheus()
    for name in ("dllama_sched_overlap_ratio",
                 "dllama_sched_inflight_depth",
                 "dllama_sched_host_gap_hidden_ms_total",
                 "dllama_sched_overlap_discards_total"):
        assert name in prom, name


# -- EMA compile poisoning (satellite) --------------------------------------

def test_ema_ignores_fresh_compile_wall():
    """A simulated 2s compile wall must not move the burst-size EMA —
    the fresh bit gates the update."""
    sch = SlotScheduler.__new__(SlotScheduler)  # unit: no engine/thread
    sch._step_ms_ema = None
    sch._note_step_time(2000.0, 1, True)       # fresh compile: ignored
    assert sch._step_ms_ema is None
    sch._note_step_time(10.0, 1, False)
    assert sch._step_ms_ema == pytest.approx(10.0)
    sch._note_step_time(2000.0, 4, True)       # warm EMA survives too
    assert sch._step_ms_ema == pytest.approx(10.0)
    sch._note_step_time(20.0, 4, False)        # per-step: 5ms folds in
    assert sch._step_ms_ema == pytest.approx(0.8 * 10.0 + 0.2 * 5.0)


# -- parked wakeups (satellite) ---------------------------------------------

def test_parked_wakeups_bounded_and_deadline_accurate():
    """An idle scheduler must not spin its old fixed-0.1s poll (~12
    wakeups in 1.2s); the deadline-derived timeout caps at 0.5s.  A
    queued deadline still expires promptly while parked."""
    sched = SlotScheduler(make_engine(2), prefill_chunk=4, decode_burst=4)
    try:
        time.sleep(0.1)        # let the loop settle into its parked wait
        sched._park_wakeups = 0
        time.sleep(1.25)
        assert sched._park_wakeups <= 5, sched._park_wakeups
        # deadline accuracy: a queued ticket behind a paused scheduler
        # wakes the parked wait at its own deadline, not 0.5s late
        with sched.exclusive():
            t = sched.submit(P1, 5, deadline=time.monotonic() + 0.3)
            t0 = time.monotonic()
            while t.finish is None and time.monotonic() - t0 < 2.0:
                time.sleep(0.01)
            assert t.finish == "timeout"
            assert time.monotonic() - t0 < 0.6
    finally:
        sched.close()


# -- pipelining behind a queue that cannot be served -------------------------

def test_pipeline_runs_behind_a_full_house(solo_refs):
    """Two slots, four requests: while both slots decode and two tickets
    wait, no boundary can admit anyone, so decode stays pipelined, in
    single steps; the queued tickets are admitted when a budget runs out,
    and every output is the solo run's whatever the overlap setting."""
    outs, seen = {}, []
    for overlap in (False, True):
        sched = SlotScheduler(make_engine(2), prefill_chunk=4,
                              max_wait_ms=50.0, decode_burst=6,
                              overlap=overlap)
        inner = sched._maybe_pipeline

        def spy(cur, inner=inner, sched=sched):
            queued = len(sched._queue)
            nxt = inner(cur)
            seen.append((queued, nxt is not None and nxt.queued > 0,
                         nxt.steps if nxt is not None else 0))
            return nxt

        sched._maybe_pipeline = spy
        try:
            with injected("engine.device_step=delay:0.01x100000"):
                tickets = [sched.submit(p, n) for p, n in
                           zip(PROMPTS, (14, 9, 12, 10))]
                outs[overlap] = [(list(t.tokens()), t.finish)
                                 for t in tickets]
            sched.flush()
            assert sched._inflight_n == 0 and sched._depth == 0
        finally:
            sched.close()
    assert outs[True] == outs[False]
    for p, n, (got, finish) in zip(PROMPTS, (14, 9, 12, 10), outs[True]):
        assert got == solo_refs[tuple(p)][:n], p
        assert finish == "length"
    behind = [s for s in seen if s[1]]
    assert behind, "decode never pipelined behind the waiting tickets"
    assert {s[2] for s in behind} == {1}, "a queued pipeline step is single"


@pytest.mark.parametrize("case,expect", [
    ("full", True), ("free_slot", False), ("queued_cancelled", False),
    ("queued_expired", False), ("may_evict", False), ("evict_off", True)])
def test_queue_must_wait_rule(case, expect):
    """The queue stops the pipeline exactly when the next boundary could
    serve it: a free slot, a queued ticket owed its cancel or timeout, or
    one that may evict a running ticket of a lower class."""
    from types import SimpleNamespace
    from dllama_tpu.runtime.scheduler import Ticket
    sched = SlotScheduler(make_paged_engine(), prefill_chunk=4,
                          preempt=case != "evict_off")
    sched.close()
    mk = lambda prio=1, deadline=None: Ticket(  # noqa: E731
        [1, 2], 8, 0.0, 0.9, (), deadline, priority=prio)
    running = [mk(2 if case in ("may_evict", "evict_off") else 1), mk()]
    for s, t in zip(sched.slots, running):
        s.ticket = t
    if case == "free_slot":
        sched.slots[1].ticket = None
    q = mk(1, time.monotonic() - 1 if case == "queued_expired" else None)
    if case == "queued_cancelled":
        q._cancel = "aborted"
    sched._queue.append(q)
    cur = SimpleNamespace(tickets=dict(enumerate(running)))
    with sched._cond:
        assert sched._queue_must_wait_locked(cur, time.monotonic()) is expect
