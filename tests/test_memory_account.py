"""The memory account (``obs/memory.py``, PR 54): the chip's memory by owner,
the program that set its peak, the host's resident set by phase, and the one
line a failing allocation logs.

CPU, toy model.  The CPU backend has no allocator statistics, so the tests
inject a reader (a fake chip whose ``bytes_in_use`` is the bytes of the
process's live arrays on device 0 above a base, so the engine's real arrays
move it) and a fake ``/proc/self/status`` text, as ``test_host_phases.py``
holds its mechanism and not the clock.  Both fakes count their reads: the
account reads at edges only, and a warm engine reads nothing.
"""

import logging

import jax
import numpy as np
import pytest

from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import memory as obs_memory, metrics as obs_metrics, \
    trace as obs_trace
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler

CFG = tiny_config(seq_len=128)
PAGE = 4
OWNERS = ("found", "params", "cache", "resident_idle", "programs", "limit")
FAMILIES = (obs_metrics.HBM_ACCOUNT_BYTES, obs_metrics.HBM_PEAK_RAISED_BYTES,
            obs_metrics.HBM_PEAK_SET_BY_BYTES)


class FakeChip:
    """``{device: memory_stats()}`` of one device "0": what the arrays born
    since this fake was made hold there above ``base``; a test raises ``peak``
    by hand where a program's temporaries would (they raise the peak and are
    gone).

    The arrays the process already had stand for ``base`` and are held until
    the fake goes: another test file's leftovers (an engine in a reference
    cycle, a scheduler thread on its way out) are otherwise collected whenever
    the collector or that thread gets to it, between two reads of one test, and
    ``found`` then differs from the reading before it or ``programs`` comes out
    negative (seen once in the driver's run of PR 55's tree, on the first test
    of this file, which runs after whatever file its worker had before)."""

    LIMIT = 1 << 34

    def __init__(self, base=1 << 20):
        self.base, self.peak, self.reads = base, 0, 0
        self._held = list(jax.live_arrays())
        self._old = {id(a) for a in self._held}   # held, so no id is reused

    def live(self) -> int:
        dev = jax.devices()[0]
        return self.base + sum(a.nbytes for a in jax.live_arrays()
                               if id(a) not in self._old and dev in a.devices())

    def __call__(self) -> dict:
        self.reads += 1
        in_use = self.live()
        self.peak = max(self.peak, in_use)
        return {"0": {"bytes_in_use": in_use, "peak_bytes_in_use": self.peak,
                      "bytes_limit": self.LIMIT,
                      "largest_free_block_bytes": self.LIMIT - in_use}}


class FakeProc:
    """The text of ``/proc/self/status``; ``rss_kb=None`` stands for no
    ``/proc``, ``hwm_kb=None`` for a kernel that leaves ``VmHWM`` out."""

    def __init__(self, rss_kb=1000, hwm_kb=3000):
        self.rss_kb, self.hwm_kb, self.reads = rss_kb, hwm_kb, 0

    def __call__(self) -> str:
        self.reads += 1
        if self.rss_kb is None:
            return ""
        hwm = "" if self.hwm_kb is None else f"VmHWM:\t{self.hwm_kb:8d} kB\n"
        return (f"Name:\tpython3\nVmPeak:\t 9999 kB\n{hwm}"
                f"VmRSS:\t{self.rss_kb:8d} kB\nThreads:\t4\n")


@pytest.fixture
def fakes(monkeypatch):
    """A fresh account over the two fakes in the process's place; the real one
    is bound to the lazily read families again afterwards."""
    real = obs_memory.ACCOUNT
    chip, proc = FakeChip(), FakeProc()
    for fam in FAMILIES:
        fam.reset()
    monkeypatch.setattr(obs_memory, "ACCOUNT",
                        obs_memory.MemoryAccount(stats=chip, proc=proc))
    # a record must reach caplog's handler on the root logger even after
    # another test's obs.log.configure stopped the propagation
    monkeypatch.setattr(logging.getLogger("dllama"), "propagate", True)
    yield chip, proc
    real.bind()
    for fam in FAMILIES:
        fam.reset()


def host_params(seed=4):
    """Host (numpy) stacks, as ``load_params`` hands them over: nothing on a
    device before the engine places them."""
    return jax.tree.map(np.asarray, init_params(CFG, seed=seed))


def make_engine(params=None, **kw):
    return Engine(CFG, host_params() if params is None else params,
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), **kw)


def make_paged(params, batch=2):
    pages = batch * -(-CFG.seq_len // PAGE) + 1
    return make_engine(params, batch=batch, kv_pages=pages, kv_page_size=PAGE)


def account() -> dict:
    return obs_metrics.HBM_ACCOUNT_BYTES.values()


def plane_bytes(eng) -> int:
    return sum(int(a.nbytes) for a in eng.cache.planes().values())


def reads(chip, proc) -> tuple[int, int]:
    return chip.reads, proc.reads


def test_owners_sum_and_found_is_read_before_the_load(fakes):
    chip, _ = fakes
    params = host_params()
    before = chip.live()                       # nothing of the model is placed
    eng = make_engine(params)
    acc = account()
    assert acc["found"] == before
    # the peak the process already had is the first key of the raisers
    assert obs_metrics.HBM_PEAK_RAISED_BYTES.get("found") == before
    assert acc["limit"] == FakeChip.LIMIT
    assert acc["params"] == obs_metrics.PARAM_BYTES_RESIDENT.get("0") > 0
    assert acc["cache"] == plane_bytes(eng)
    assert "resident_idle" not in acc and "programs" not in acc  # not read yet
    list(eng.generate_stream([1, 2, 3], 20))   # compiles; its close reads idle
    acc = account()
    assert acc["found"] + acc["params"] + acc["cache"] + acc["programs"] \
        == acc["resident_idle"]
    assert acc["found"] == before              # read once a process
    assert acc["programs"] >= 0                # the engine's keys, its outputs


def test_a_second_engine_over_the_same_parameters_adds_its_cache_alone(fakes):
    chat = make_engine()
    found, params = account()["found"], account()["params"]
    batch = make_paged(chat.params)
    acc = account()
    assert (acc["found"], acc["params"]) == (found, params)
    assert acc["cache"] == plane_bytes(chat) + plane_bytes(batch)
    del batch                                  # its pool goes with it
    assert obs_memory.ACCOUNT._cache["0"] == plane_bytes(chat)


def test_peak_raised_names_the_program_that_raised_it_and_no_other(fakes):
    chip, _ = fakes
    eng = make_engine()
    obs_trace.clear()
    obs_metrics.HBM_PEAK_RAISED_BYTES.reset()   # the load phases' own raises
    obs_metrics.HBM_PEAK_SET_BY_BYTES.reset()
    chip.peak = 1 << 30       # the peak stands far above what a launch adds
    step = eng._step

    def spiking(*args):       # the prefill program's temporaries
        chip.peak += 4096
        return step(*args)

    eng._step = spiking
    list(eng.generate_stream([1, 2, 3], 20))   # a fresh prefill, a fresh chunk
    raised = obs_metrics.HBM_PEAK_RAISED_BYTES.values()
    assert list(raised) == [repr(("step", (1, 16), False))]
    assert raised[repr(("step", (1, 16), False))] == 4096
    assert list(obs_metrics.HBM_PEAK_SET_BY_BYTES.values()) == list(raised)
    spans = [s["args"] for s in obs_trace.TRACER.snapshot()
             if s["name"] == "engine.compile"]
    assert len(spans) == 2
    for args in spans:
        assert {"hbm_in_use", "hbm_peak_before", "hbm_peak_after"} <= set(args)
    assert spans[0]["hbm_peak_after"] - spans[0]["hbm_peak_before"] == 4096
    assert spans[1]["hbm_peak_after"] == spans[1]["hbm_peak_before"]


def test_a_warm_engine_reads_nothing(fakes):
    chip, proc = fakes
    chat = make_engine()
    batch = make_paged(chat.params)
    sched = SlotScheduler(batch, prefill_chunk=4, max_wait_ms=5.0,
                          decode_burst=1)
    try:
        prompt = list(range(3, 11))
        for _ in range(2):                     # every shape the checks run
            for n in (65, 12):
                list(chat.generate_stream(prompt, 8 + n, chunk=16))
                chat.reset()
            for n in (16, 5):
                assert len(list(sched.submit(prompt, n).tokens())) == n
            sched.flush()
        assert "resident_idle" in account()
        assert not obs_memory.ACCOUNT.dirty and not obs_memory.ACCOUNT.pending
        warm = reads(chip, proc)

        list(chat.generate_stream(prompt, 8 + 65, chunk=16))  # 64 decode steps
        assert reads(chip, proc) == warm
        chat.reset()
        steps0 = obs_metrics.SCHED_STEPS.total
        assert len(list(sched.submit(prompt, 16).tokens())) == 16
        sched.flush()
        assert obs_metrics.SCHED_STEPS.total - steps0 >= 16   # scheduler steps
        assert reads(chip, proc) == warm
        for _ in range(3):                     # whole requests, parks between
            list(chat.generate_stream(prompt, 8 + 12, chunk=16))
            chat.reset()
            assert len(list(sched.submit(prompt, 5).tokens())) == 5
        sched.flush()
        assert reads(chip, proc) == warm
    finally:
        sched.close()
    # a scrape reads /proc once for "now" and the high water together; the
    # account's own counter of edge reads does not move
    edge = obs_metrics.MEMORY_ACCOUNT_READS.get("host")
    snap = obs_metrics.snapshot_json()
    assert snap["host_rss_bytes"]["now"] == 1000 * 1024
    assert snap["host_rss_peak_bytes"] == 3000 * 1024
    assert obs_metrics.MEMORY_ACCOUNT_READS.get("host") == edge
    assert reads(chip, proc) == (warm[0], warm[1] + 1)   # one /proc read a scrape


def test_host_rss_by_phase_and_on_the_load_spans(fakes, tmp_path):
    from dllama_tpu.io import mfile
    from dllama_tpu.models.params import load_params
    from fixtures import write_tiny_model
    _, proc = fakes
    obs_trace.clear()
    path = str(tmp_path / "tiny.m")
    write_tiny_model(path)
    proc.rss_kb = 2000
    with mfile.MFile(path) as mf:
        cfg, params = load_params(mf)
        proc.rss_kb = 2500
        Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    proc.rss_kb = 2700
    rss = obs_metrics.snapshot_json()["host_rss_bytes"]
    assert rss == {"read": 2000 * 1024, "placed": 2500 * 1024,
                   "ready": 2500 * 1024, "now": 2700 * 1024}
    spans = {s["name"]: s["args"] for s in obs_trace.TRACER.snapshot()}
    assert spans["engine.load_read"]["rss"] == 2000 * 1024
    assert spans["engine.load_place"]["rss"] == 2500 * 1024


def test_exhausted_allocation_logs_one_line_and_raises_unchanged(fakes, caplog):
    eng = make_engine()
    list(eng.generate_stream([1, 2, 3], 6))
    eng.reset()
    boom = RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer: "
                        "Attempting to allocate 1.50G. That was not possible.")

    def refusing(*args):
        raise boom

    eng._step = refusing
    with caplog.at_level(logging.ERROR, logger="dllama"), \
            pytest.raises(RuntimeError) as err:
        eng.prefill(list(range(3, 40)))        # a bucket not yet compiled
    assert err.value is boom
    lines = [r for r in caplog.records if r.getMessage() == "hbm_exhausted"]
    assert len(lines) == 1
    line = lines[0]
    for owner in OWNERS:
        assert isinstance(getattr(line, owner), int), owner
    assert line.found + line.params + line.cache + line.programs \
        == line.resident_idle
    assert line.key == repr(("step", (1, 64), False))
    assert line.peak >= line.in_use > 0
    assert line.largest_free_block == FakeChip.LIMIT - line.in_use
    assert (line.host_rss, line.host_rss_peak) == (1000 * 1024, 3000 * 1024)
    # another error is not an allocation's: no line
    caplog.clear()
    eng._step = lambda *args: (_ for _ in ()).throw(ValueError("shape"))
    with caplog.at_level(logging.ERROR, logger="dllama"), \
            pytest.raises(ValueError):
        eng.prefill(list(range(3, 100)))
    assert not [r for r in caplog.records if r.getMessage() == "hbm_exhausted"]


def test_exhausted_load_says_what_the_process_held(fakes, caplog, monkeypatch):
    from dllama_tpu.parallel import sharding

    def refusing(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                           "allocate 12884901888 bytes.")

    monkeypatch.setattr(sharding, "_place_params", refusing)
    with caplog.at_level(logging.ERROR, logger="dllama"), \
            pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        make_engine()
    lines = [r for r in caplog.records if r.getMessage() == "hbm_exhausted"]
    assert len(lines) == 1
    assert lines[0].found > 0 and lines[0].key is None
    assert lines[0].resident_idle is None      # nothing was idle yet


def test_the_fullest_of_four_devices_is_the_account(fakes):
    def four() -> dict:
        return {str(d): {"bytes_in_use": 100 + use, "peak_bytes_in_use": 900,
                         "bytes_limit": 1000 + d}
                for d, use in enumerate((10, 30, 70, 20))}

    acct = obs_memory.MemoryAccount(stats=four, proc=FakeProc())
    for d in range(4):
        obs_metrics.PARAM_BYTES_RESIDENT.set(str(d), 40 + d)
    try:
        acct.found()
        acct.cache_built({"0": 5, "1": 5, "2": 6, "3": 5})
        acct.idle()
        assert account() == {"found": 170, "params": 42, "cache": 6,
                             "resident_idle": 170, "programs": 170 - 218,
                             "limit": 1002}
    finally:
        obs_metrics.PARAM_BYTES_RESIDENT.reset()


def test_the_cpu_has_an_empty_family_and_no_zeros(fakes):
    # the engine's own reader on this backend: no allocator statistics
    from dllama_tpu.runtime.engine import _device_stats
    proc = FakeProc(rss_kb=None)
    obs_memory.ACCOUNT = obs_memory.MemoryAccount(stats=_device_stats, proc=proc)
    obs_trace.clear()
    eng = make_engine()
    list(eng.generate_stream([1, 2, 3], 12))
    snap = obs_metrics.snapshot_json()
    for key in ("hbm_account_bytes", "hbm_peak_raised_bytes",
                "hbm_peak_set_by_bytes", "host_rss_bytes"):
        assert snap[key] == {}, key
    assert snap["host_rss_peak_bytes"] is None
    text = obs_metrics.render_prometheus()
    assert "# TYPE dllama_hbm_account_bytes gauge" in text
    assert "dllama_hbm_account_bytes{" not in text
    assert "dllama_host_rss_bytes{" not in text
    assert "\ndllama_host_rss_peak_bytes " not in text
    spans = [s["args"] for s in obs_trace.TRACER.snapshot()
             if s["name"] == "engine.compile"]
    assert spans and not any("hbm_in_use" in a for a in spans)


def test_a_status_file_without_vmhwm_takes_getrusages_high_water(fakes):
    obs_memory.ACCOUNT = obs_memory.MemoryAccount(
        stats=None, proc=FakeProc(rss_kb=700, hwm_kb=None),
        maxrss=lambda: 900 * 1024)
    snap = obs_metrics.snapshot_json()
    assert snap["host_rss_peak_bytes"] == 900 * 1024
    assert snap["host_rss_bytes"] == {"now": 700 * 1024}
    # the process's own default reads the real high water, in bytes
    assert obs_memory.read_maxrss() > 10 * 1024 * 1024


def test_parse_proc_status_reads_kilobytes():
    text = FakeProc(rss_kb=123, hwm_kb=456)()
    assert obs_memory.parse_proc_status(text) == {"rss": 123 * 1024,
                                                  "rss_peak": 456 * 1024}
    assert obs_memory.parse_proc_status("") == {}


def test_the_tool_prints_the_account_from_a_metrics_snapshot(fakes):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "memory_account.py")
    spec = importlib.util.spec_from_file_location("memory_account_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    eng = make_engine()
    list(eng.generate_stream([1, 2, 3], 12))
    text = tool.render(obs_metrics.snapshot_json())
    acc = account()
    for owner in OWNERS:
        assert f"{owner:<14}{acc[owner] / 1e9:9.4f}" in text
    assert "the peak was set by: " in text and "load_place" in text
    assert f"{'high water':<14}{3000 * 1024 / 1e9:9.4f}" in text
    assert "device " in text and " ms" in text
    # a program without the account (or the CPU): dashes, never zeros
    assert "found                 -" in tool.render({})


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_the_cache_owner_holds_a_mixers_state_beside_keys_and_values(fakes, paged):
    """A model with a state-space mixer beside attention (Falcon-H1): the
    account's ``cache`` owner is every plane of the engine, the keys and values
    AND the slots' states and rings, and ``kv_cache_bytes{kind}`` names the two:
    ``full`` what a position (or a page) addresses, ``ssm`` what a slot owns."""
    from dllama_tpu.models.config import tiny_falcon_h1
    cfg = tiny_falcon_h1(seq_len=128)
    kw = dict(batch=2, kv_pages=2 * 32 + 1, kv_page_size=PAGE) if paged else {}
    eng = Engine(cfg, jax.tree.map(np.asarray, init_params(cfg, seed=4)),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), **kw)
    acc = account()
    assert acc["cache"] == plane_bytes(eng)
    by_kind = obs_metrics.KV_CACHE_BYTES.json_value()
    planes = eng.cache.planes()
    assert by_kind["full"] == int(planes["k"].nbytes + planes["v"].nbytes)
    assert by_kind["ssm"] == plane_bytes(eng) - by_kind["full"] > 0
    assert by_kind["full"] + by_kind["ssm"] == acc["cache"]
