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
    it is never a temp name, pid or timestamp.  The rest of a program's key
    is the program: its HLO without locations and, for a Pallas kernel, the
    serialized Mosaic module, which :func:`kernels_without_frames` keeps free
    of file names and lines.  An edit that leaves a program's jaxpr alone
    leaves its key alone, wherever the Python that traced it stands."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, "build", "xla_cache")


def kernels_without_frames() -> None:
    """Serialize this process's Pallas kernels without Python frames.

    JAX compiles a Mosaic kernel from MLIR bytecode written with debug info,
    and the persistent cache, which strips locations from the HLO around a
    custom call, hashes that payload as it is: by default it holds file
    (the checkout's absolute path), function, line and column of the ten
    innermost frames that traced the kernel, so a line moved in ``ops/`` or
    up the stack in ``runtime/`` re-keyed every step program (PERF.md §6,
    PR 46).  ``jax_traceback_in_locations_limit = 0`` leaves the frames out;
    it has to be set before the process lowers its first kernel.

    Given up by default: the ``source_file`` / ``source_line`` of HLO ops in
    a compiled program's text or a profiler trace.  Kept: everything a
    location carries that is not a frame, so the scope names of
    ``ops/scopes.py`` (an op's ``op_name``, the trace's ``tf_op``: what every
    reader under ``benchmarks/layer_metrics/`` goes by) and the kernels' own
    ``name=``.  An operator's ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT`` (JAX's
    own variable, e.g. 10 for one diagnostic trace with source lines) is
    left alone, like ``JAX_COMPILATION_CACHE_DIR``."""
    if "JAX_TRACEBACK_IN_LOCATIONS_LIMIT" not in os.environ:
        import jax
        jax.config.update("jax_traceback_in_locations_limit", 0)


def configure_compile_cache() -> str:
    """Called first thing by every entry point (CLI, server, pod,
    chip_smoke children): the two decisions about the persistent compile
    cache.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and
    no other directory is set in code; otherwise point JAX at the fixed
    default.  Either way kernels are keyed on their program alone
    (:func:`kernels_without_frames`).  Returns the directory."""
    d = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", d)
    kernels_without_frames()
    return d
