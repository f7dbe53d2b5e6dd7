"""Test harness config.

Tests run on CPU with 8 virtual XLA devices so the multi-chip sharding path
(tensor/sequence parallel over a `jax.sharding.Mesh`) compiles and executes
without TPU hardware — the same trick the driver uses for
``__graft_entry__.dryrun_multichip``.

``JAX_PLATFORMS=cpu`` in the environment selects the CPU backend; the
config update below makes the same choice for a bare ``pytest`` run.  It
must happen before the first backend query.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """A worker runs a dozen files in one process, and every program a file
    compiled stays loaded until the process ends.  With enough of them loaded
    XLA's CPU backend segfaults inside a later compile: ``test_packed_rows``,
    ``test_deepseek_v2`` and ``test_smallthinker`` in one process die in the
    last test of the third (so does the parent of PR 51, which found it when a
    new file moved the files' places among the workers).  So a file's programs
    go when its tests are done."""
    yield
    jax.clear_caches()
