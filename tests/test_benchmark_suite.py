"""Tier-1's view of the benchmark's own tests (``benchmarks/tests/``).

``pytest tests/`` never collected that directory, so a broken reader, trace
reducer or load generator was invisible to tier-1.  This module loads those
test modules and re-exports their test functions and fixtures, so that they
are collected, run and counted here.  Most are JAX-free and take seconds;
``test_models_program`` and ``test_models_olmoe`` import JAX and
``dllama_tpu`` (the architecture modules against the program's engine, at toy
widths) and take about a minute together.

The benchmark's ``conftest.py`` puts ``benchmarks/`` and
``benchmarks/layer_metrics/`` on ``sys.path``, and its test modules import
``BENCH``/``ROOT`` from a module named ``conftest``; in this directory that
name is ``tests/conftest.py``, so the benchmark's own stands in for it while
its test modules are imported.
"""

import importlib.util
import os
import sys

from _pytest.fixtures import FixtureFunctionDefinition

_BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests")
MODULES = ("test_exaone_moe", "test_host_readers", "test_loadgen", "test_manifest",
           "test_memory_readers", "test_models",
           "test_models_brumby", "test_models_deepseek_v2", "test_models_falcon_h1",
           "test_models_granitemoehybrid", "test_models_lfm2_moe",
           "test_models_olmoe", "test_models_ouro",
           "test_models_program", "test_models_smallthinker", "test_prefill_readers",
           "test_rows_reader",
           "test_tp_readers", "test_traffic",
           "test_xmeta", "test_xplane")


def _load(name: str, stem: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_BENCH_TESTS, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _collect() -> dict:
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = _load("benchmarks_tests_conftest", "conftest")
    try:
        found = {}
        for stem in MODULES:
            mod = _load("benchmarks_tests_" + stem, stem)
            for key, val in vars(mod).items():
                if not (key.startswith("test_") and callable(val)) \
                        and not isinstance(val, FixtureFunctionDefinition):
                    continue
                if key in found:
                    raise ImportError(f"benchmarks/tests defines {key} twice")
                found[key] = val
        return found
    finally:
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours


globals().update(_collect())
