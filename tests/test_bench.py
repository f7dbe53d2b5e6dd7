"""The bench stages' output contract (bench.py).

``python bench.py --attempt <stage>`` prints one JSON line; a chip stage
that finds no TPU fails instead of printing a CPU number under a device
name; the compile cache is placed by the shared helper.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from fixtures import REPO, cpu_env

sys.path.insert(0, REPO)
import bench  # noqa: E402


def test_emit_contract(capfd):
    """One parseable line; backend stripped; extras riding along."""
    bench._emit({"metric": "m", "value": 1.5, "unit": "tok/s",
                 "vs_baseline": None, "backend": "tpu"},
                {"llama3-8b_toks": 88.0})
    out = capfd.readouterr().out
    lines = [l for l in out.splitlines() if l.strip()]
    assert len(lines) == 1
    obj = json.loads(lines[0])
    assert obj["value"] == 1.5 and "backend" not in obj
    assert obj["extras"] == {"llama3-8b_toks": 88.0}


CHIP_STAGES = [
    "llama2-7b", "llama2-7b-b8", "llama2-7b-q8kv", "llama2-7b-q8w",
    "llama2-7b-profile", "llama2-7b-c64", "llama2-7b-long", "llama3-8b",
    "llama2-13b", "tinyllama-1.1b", "llama2-7b-cli", "llama2-7b-prefill",
    "llama2-7b-sched4", "llama2-7b-prefix4", "llama2-7b-pressure4",
    "llama2-7b-overlap4", "llama2-7b-fused4", "llama2-7b-spec4",
    "llama2-7b-tp4sched4"]


@pytest.mark.parametrize("stage", CHIP_STAGES)
def test_chip_stage_refuses_before_any_work(stage, monkeypatch, capfd):
    """Every real-config stage, ``-prefill`` and ``-cli`` included, stops
    at the one guard on top of ``_attempt_body``: nothing is synthesized,
    compiled or printed on the CPU backend."""
    def no_work(*a, **k):
        raise AssertionError("a chip stage started work without a TPU")
    for fn in ("_model_cfg", "_synth_model_files", "_run_cli_bench",
               "_bench_decode", "_bench_prefill", "_bench_sched"):
        monkeypatch.setattr(bench, fn, no_work)
    with pytest.raises(SystemExit, match="chip stage and JAX found no TPU"):
        bench._attempt_body(stage)
    assert capfd.readouterr().out == ""


@pytest.mark.parametrize("stage", ["llama2-7b", "llama2-7b-sched4",
                                   "llama2-7b-prefill", "llama2-7b-cli"])
def test_chip_stage_fails_without_a_tpu(stage):
    """No CPU number is ever printed under a device name: a real-config
    stage on the CPU backend exits non-zero with no result line."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--attempt", stage], env=cpu_env(1),
                       capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "chip stage" in r.stderr


def test_no_orchestrator_left():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=cpu_env(1), capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    assert r.returncode != 0 and r.stdout == ""
    assert "--attempt" in r.stderr


class TestCompileCache:
    def test_env_dir_is_honoured_and_nothing_else_set(self, monkeypatch):
        import jax
        from dllama_tpu import hostenv
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert hostenv.configure_compile_cache() == "/some/dir"
        assert calls == []  # JAX reads the variable itself

    def test_default_is_the_fixed_checkout_path(self, monkeypatch):
        import jax
        from dllama_tpu import hostenv
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        want = os.path.join(REPO, "build", "xla_cache")
        assert hostenv.compile_cache_dir() == want
        assert hostenv.configure_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]


def test_bench_decode_pipelined_schedule_runs():
    """_bench_decode's depth-1 pipelined loop (dispatch chunk i+1 on the
    device-carried token before fetching chunk i) must keep the position
    arithmetic sound end to end — a schedule regression shows up as a
    cache-bounds crash or a nonsense rate."""
    cfg = bench._model_cfg("cpu-tiny").with_(quant_impl="xla")
    ms = bench._bench_decode(cfg, chunk=8, n_chunks=3)
    assert 0 < ms < 10_000
