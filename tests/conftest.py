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
