"""Process environment helpers: backend selection for subprocesses and the
placement of JAX's persistent compilation cache.

A JAX backend cannot be re-selected in-process once initialized.  Anything
that needs a CPU mesh of a chosen size — the multichip dryrun, the CLI
tests — spawns a child process whose environment forces CPU *before* JAX
loads.  This is the one shared copy of that recipe.
"""

from __future__ import annotations

import os


def forced_cpu_env(n_devices: int = 1, base: dict | None = None) -> dict:
    """Environment that selects the CPU backend with ``n_devices`` virtual
    XLA devices, regardless of what the parent process's backend is.

    Any pre-existing ``--xla_force_host_platform_device_count`` flag is
    replaced (not merely appended to) so a stale count of 1 cannot shadow
    the requested mesh size.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where this process keeps JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/build/xla_cache``.  The path is part of the cache key, so
    it is never a temp name, pid or timestamp."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, "build", "xla_cache")


def configure_compile_cache() -> str:
    """Called first thing by every entry point (CLI, server, pod, bench
    attempts, chip_smoke children).  With ``JAX_COMPILATION_CACHE_DIR``
    set, JAX reads it itself and no other directory is set in code;
    otherwise point JAX at the fixed default.  Returns the directory."""
    d = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", d)
    return d
