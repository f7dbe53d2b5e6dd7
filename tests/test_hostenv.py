"""The two decisions about JAX's persistent compile cache that every entry
point makes first (``hostenv.configure_compile_cache``): where the cache is,
and that a Pallas kernel is keyed on its program, not on where its Python
stands.  An operator's own JAX variable wins over both.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax

from dllama_tpu import hostenv
from fixtures import REPO, cpu_env

FRAMES_OFF = ("jax_traceback_in_locations_limit", 0)


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


class TestCompileCache:
    def test_env_dir_is_honoured_and_nothing_else_set(self, monkeypatch):
        """With both of JAX's variables set by the operator the helper makes
        no ``jax.config.update`` call at all; with the directory alone, the
        one thing set in code is the frames limit, never a directory."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        monkeypatch.setenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "10")
        calls = _updates(monkeypatch)
        assert hostenv.configure_compile_cache() == "/some/dir"
        assert calls == []  # JAX reads both variables itself
        monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT")
        assert hostenv.configure_compile_cache() == "/some/dir"
        assert calls == [FRAMES_OFF]

    def test_default_is_the_fixed_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", raising=False)
        calls = _updates(monkeypatch)
        want = os.path.join(REPO, "build", "xla_cache")
        assert hostenv.compile_cache_dir() == want
        assert hostenv.configure_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want), FRAMES_OFF]


def test_an_operators_frames_limit_is_left_alone(monkeypatch):
    """``JAX_TRACEBACK_IN_LOCATIONS_LIMIT`` is how a builder gets source
    lines back into one diagnostic trace: the helper, and so ``Engine``,
    which calls it for a caller that is no entry point, does not touch it."""
    monkeypatch.setenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "10")
    calls = _updates(monkeypatch)
    hostenv.kernels_without_frames()
    assert calls == []
    monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT")
    hostenv.kernels_without_frames()
    assert calls == [FRAMES_OFF]


# lowered for the TPU on the CPU backend: nothing loads the TPU's library, so
# a child process may do it (compiles for a described chip stay in
# tests/test_tpu_compile.py)
_LOWER_Q40_MM = """
import re, sys
import jax, jax.numpy as jnp
from dllama_tpu import hostenv
from dllama_tpu.ops import q40
from fixtures import kernel_bodies
if sys.argv[1] == "entry-point":
    hostenv.configure_compile_cache()
s = jax.ShapeDtypeStruct
(body,) = kernel_bodies(jax.jit(lambda x, qp, sc: q40._pallas_matmul(x, qp, sc)).trace(
    s((1, 1024), jnp.bfloat16), s((512, 1024), jnp.uint8),
    s((32, 1024), jnp.uint16)).lower(lowering_platforms=("tpu",)).as_text())
print(len(body), *sorted({m.decode() for m in re.findall(rb"[\\w/.-]+\\.py", body)}))
"""


def test_after_the_entry_points_helper_a_kernel_holds_no_file_name():
    """A fresh process that calls what every entry point calls first and then
    lowers ``q40_mm`` for the TPU serializes a body without a ``.py`` name;
    without the call the body holds this checkout's ``ops/q40.py`` (the
    control: the probe does see frames)."""
    def run(mode):
        env = cpu_env(1)
        env.pop("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", None)
        env["PYTHONPATH"] = os.path.dirname(__file__) + os.pathsep + env["PYTHONPATH"]
        r = subprocess.run([sys.executable, "-c", _LOWER_Q40_MM, mode], env=env,
                           capture_output=True, text=True, timeout=300, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        size, *names = r.stdout.split()
        return int(size), names

    size, names = run("entry-point")
    assert names == [] and size > 1000
    bare_size, bare_names = run("bare")
    assert any(n.endswith("dllama_tpu/ops/q40.py") for n in bare_names)
    assert bare_size > size
