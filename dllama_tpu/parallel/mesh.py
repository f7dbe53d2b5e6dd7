"""Device-mesh construction.

The reference's cluster topology is ``--workers host:port …`` — a TCP star
of 2^n CPU nodes (socket.cpp:160-185).  Here the topology is a
``jax.sharding.Mesh`` over TPU chips on ICI; the CLI keeps the contract as
``--workers tpu:N``.

Axes:
* ``tp`` — tensor parallel: the reference's slice index
  (RowMatmulSlice/ColMatmulSlice, commands.cpp:8-70).
* ``sp`` — sequence parallel (ring attention) for long context; the
  reference has no equivalent (SURVEY §5: its only long-context lever is
  TP's 1/n KV shrink).
* ``dp`` — data parallel over batch; the reference is fixed batch-1.
* ``ep`` — expert parallel: MoE expert stacks sharded over experts (the
  reference replicates all experts on every node and TP-slices them,
  transformer.cpp:299-317 — that layout remains the default here; ep is
  the beyond-reference alternative for models whose expert set outgrows
  one chip).
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np
from jax.sharding import Mesh

# --- active mesh -----------------------------------------------------------
# The transformer's attention dispatch reads this at *trace* time to decide
# whether to run the sequence-parallel shard_map path (ops/sp_attention.py).
# The Engine enters the context around its jitted calls; tracing happens on
# the first call, so the mesh is visible exactly when the decision is made.
_ACTIVE: list[Mesh] = []


@contextlib.contextmanager
def active_mesh(mesh: Mesh):
    _ACTIVE.append(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE.pop()


def get_active_mesh() -> Mesh | None:
    return _ACTIVE[-1] if _ACTIVE else None


def make_mesh(tp: int | None = None, sp: int = 1, dp: int = 1, ep: int = 1,
              devices=None) -> Mesh:
    """Build a (dp, sp, ep, tp) mesh; tp defaults to all remaining devices.

    tp is the innermost axis so tensor-parallel collectives ride the
    fastest ICI links (the scaling-book recipe: put the most
    bandwidth-hungry axis innermost).  The ``ep`` axis always exists
    (size 1 unless requested) so expert PartitionSpecs can mention it
    unconditionally.
    """
    devices = list(devices if devices is not None else jax.devices())
    if tp is None:
        tp = len(devices) // (sp * dp * ep)
        if tp == 0:
            raise ValueError(
                f"mesh sp={sp}×dp={dp}×ep={ep} already exceeds "
                f"{len(devices)} devices")
    n = dp * sp * ep * tp
    if n > len(devices):
        raise ValueError(
            f"mesh {dp}x{sp}x{ep}x{tp} needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(dp, sp, ep, tp)
    return Mesh(arr, axis_names=("dp", "sp", "ep", "tp"))


def parse_workers(workers: str | None, sp: int = 1, dp: int = 1, ep: int = 1,
                  devices=None) -> Mesh:
    """Parse the CLI ``--workers`` value (+ ``--sp``/``--dp``/``--ep``
    degrees) into a mesh.

    ``tpu:N`` → N-way tensor parallel (the BASELINE.json north-star form);
    ``None``/"" → all remaining devices go to tp.  ``sp``/``dp``/``ep`` add
    sequence-parallel (long context), data-parallel (batch), and
    expert-parallel axes — capability beyond the reference, whose only
    option is TP (README.md:7); the total dp·sp·ep·tp must fit the device
    count.  Host:port worker lists are the reference's CPU-cluster
    transport and are intentionally not supported — the transport here is
    XLA collectives.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not workers:
        return make_mesh(sp=sp, dp=dp, ep=ep, devices=devices)
    if workers.startswith("tpu:"):
        n = int(workers.split(":", 1)[1])
        return make_mesh(tp=n, sp=sp, dp=dp, ep=ep, devices=devices)
    raise ValueError(
        f"unsupported --workers value {workers!r}: this framework replaces the "
        "TCP star with a TPU mesh; use 'tpu:N'")
