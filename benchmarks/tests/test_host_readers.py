"""The readers of the host's phases and of the start-up on made-up inputs:
what counts as work and what as slack, the window's delta over its steps, the
idle time by innermost span, the start-up's counters at the window's first
instant; and nothing at all from a program that lacks the names (the parent
of the PR that added them)."""

import importlib.util
import os

import pytest
from conftest import BENCH

SERVED = ("serve_host_ms_per_step", "serve_host_slack_ms_per_step",
          "serve_host_evict_ms_per_step", "serve_host_h2d_ms_per_step",
          "serve_host_launch_ms_per_step")
IDLE = ("serve_idle_enqueue_ms_per_step", "serve_idle_admit_ms_per_step")
SETUP = ("setup_compile_s", "setup_cache_miss_programs", "setup_load_s",
         "setup_trace_s", "setup_cache_miss_s", "setup_cache_write_programs")

BEFORE = {"sched_steps": {"decode": 100, "mixed": 20},
          "sched_host_ms": {"admit/round": 50.0, "h2d/decode": 10.0},
          "compile_cache_requests": 41, "compile_cache_hits": 38,
          "compile_cache_writes": 1, "backend_compile_seconds": 9.5,
          "compile_cache_retrieval_seconds": 2.25, "jaxpr_trace_seconds": 4.5,
          "engine_load_seconds": {"read": 20.5, "place": 3.25}}
AFTER = {"sched_steps": {"decode": 180, "mixed": 40},
         "sched_host_ms": {
             "admit/round": 150.0, "evict/round": 30.0, "build/decode": 40.0,
             "build/mixed": 10.0, "build/round": 5.0, "h2d/decode": 90.0,
             "h2d/mixed": 20.0, "launch/decode": 160.0, "launch/mixed": 40.0,
             "fanout/decode": 50.0, "fanout/mixed": 15.0,
             "verdict/decode": 10.0, "land_wait/decode": 700.0,
             "land_wait/mixed": 300.0, "compile/mixed": 900.0},
         "sched_step_time_ms": {"host_gap": 140.0, "decode": 1e4},
         "sched_host_gap_hidden_ms": 360.0,
         "compile_cache_requests": 41, "compile_cache_hits": 38,
         "backend_compile_seconds": 9.5,
         "engine_load_seconds": {"read": 20.5, "place": 3.25}}
TABLE = {"steps": 50, "spans": {"engine.launch": [1.0], "sched.admit": [1.0]},
         "idle_in_span_s": {"sched.build": 0.010, "engine.h2d": 0.020,
                            "engine.launch": 0.100, "engine.compile": 0.0,
                            "engine.slot_enqueue": 0.005, "sched.enqueue": 0.015,
                            "sched.admit": 0.040, "sched.evict": 0.060,
                            "sched.land_wait": 0.5, "api.emit": 0.3}}
# the parent's program: its steps and spans, none of the new names
PARENT = {"sched_steps": {"decode": 180, "mixed": 40},
          "sched_step_wall_ms": {"decode": 1.0}, "engine_recompiles": 12}
PARENT_TABLE = {"steps": 50, "spans": {"engine.slot_enqueue": [1.0]},
                "idle_in_span_s": {"engine.slot_enqueue": 0.2,
                                   "sched.admit": 0.1}}


def _read(name, ctx, table=None, monkeypatch=None):
    mod = importlib.import_module(name)
    if monkeypatch is not None:
        monkeypatch.setattr(importlib.import_module("_host"), "table",
                            lambda ctx: table)
    return mod.read(ctx)


def test_host_work_is_every_phase_but_the_wait_and_the_compile():
    ctx = {"before": BEFORE, "after": AFTER}
    steps = 100  # 80 decode + 20 mixed landed in the window
    work = (100 + 30 + 55 + 100 + 200 + 65 + 10) / steps
    assert _read("serve_host_ms_per_step", ctx) == pytest.approx(work)
    assert _read("serve_host_slack_ms_per_step", ctx) == pytest.approx(10.0)
    assert _read("serve_host_evict_ms_per_step", ctx) == pytest.approx(0.3)
    assert _read("serve_host_h2d_ms_per_step", ctx) == pytest.approx(1.0)
    assert _read("serve_host_launch_ms_per_step", ctx) == pytest.approx(2.0)


def test_the_tool_prints_the_same_split_by_kind():
    """``benchmarks/tools/host_phases.py`` over a run's two snapshots: each
    cell over the steps of its kind, the readers' sum, the goodput clock."""
    spec = importlib.util.spec_from_file_location(
        "host_phases", os.path.join(BENCH, "tools", "host_phases.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tab = tool.host_table(AFTER, BEFORE)
    assert tab["steps"] == {"decode": 80, "mixed": 20}
    assert tab["ms_per_step"]["h2d/decode"] == pytest.approx(1.0)
    assert tab["ms_per_step"]["h2d/mixed"] == pytest.approx(1.0)
    assert tab["ms_per_step"]["evict/round"] == pytest.approx(0.3)
    assert tab["ms_per_step"]["compile/mixed"] == pytest.approx(45.0)
    assert tab["work_ms_per_step"] == pytest.approx(5.6)
    assert tab["clock_ms_per_step"] == pytest.approx(5.0)
    assert tool.host_table(PARENT) is None   # a program without the family
    whole = tool.host_table(AFTER)           # one snapshot: cumulative
    assert whole["steps"] == {"decode": 180, "mixed": 40}


def test_a_window_without_steps_reads_nothing():
    ctx = {"before": AFTER, "after": AFTER}
    assert all(_read(name, ctx) is None for name in SERVED)


def test_idle_time_by_innermost_span_per_step(monkeypatch):
    ctx = {"before": BEFORE, "after": AFTER}
    assert _read("serve_idle_enqueue_ms_per_step", ctx, TABLE, monkeypatch) \
        == pytest.approx(1e3 * 0.150 / 50)
    assert _read("serve_idle_admit_ms_per_step", ctx, TABLE, monkeypatch) \
        == pytest.approx(1e3 * 0.100 / 50)
    for name in IDLE:   # a rehearsal: no device plane
        assert _read(name, ctx, None, monkeypatch) is None
        assert _read(name, ctx, dict(TABLE, steps=0), monkeypatch) is None


def test_the_start_up_is_read_at_the_windows_first_instant():
    before = dict(BEFORE)
    ctx = {"before": before, "after": dict(AFTER, compile_cache_requests=43,
                                           backend_compile_seconds=12.0)}
    # the backend's seconds hold the cache's look-ups: no term beside them
    assert _read("setup_compile_s", ctx) == 9.5
    assert _read("setup_cache_miss_programs", ctx) == 3
    assert _read("setup_load_s", ctx) == 23.75
    assert _read("setup_trace_s", ctx) == 4.5
    assert _read("setup_cache_miss_s", ctx) == 7.25   # 9.5 less the loads
    assert _read("setup_cache_write_programs", ctx) == 1
    before["engine_load_seconds"] = {"place": 3.25}   # params handed over
    assert _read("setup_load_s", ctx) == 3.25


@pytest.mark.parametrize("name", [*SERVED, *IDLE, *SETUP])
def test_the_parents_program_gives_the_new_readers_nothing(name, monkeypatch):
    ctx = {"before": PARENT, "after": PARENT}
    assert _read(name, ctx, PARENT_TABLE, monkeypatch) is None
