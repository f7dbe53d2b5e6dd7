"""The rows of a slot step that carry a token, packed.

A slot step at ``t > 1`` gives every slot a row of ``t`` tokens as soon as one
slot is prefilling, and a decoding slot fills one of them: of a mixed step's
``b * t`` rows a few dozen are real (PERF.md section 6, PR 42).  Everything
between two attention calls is row-local, so those regions of a layer (norm
and ``qkv``; ``wo``; norm and the FFN or the experts) run over the valid rows
alone, gathered to the front of a shorter array, and hand their result back in
slot layout, zeros where a slot's row holds no token.  ``rope``, the KV write,
the attention read, the residual adds and the head keep the slot layout.

The row count is chosen INSIDE the program: :func:`plan` finds, from
``n_valid`` alone and on the device, the smallest of :data:`BUCKETS` that holds
the step's valid rows (else ``b * t``: the step as it always ran) and
:func:`over` switches between one body of the region a bucket.  A step's
compile key stays ``(T, steps, greedy)``: how many slots prefill at once is
not part of it, so no traffic compiles a program the warm-up did not.

``t == 1`` (the pure-decode step, every one-stream program), a device mesh and
a step with no bucket under its ``b * t`` have no plan: :func:`over` then calls
the region as it is and the traced program is what it was before packing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.scopes import scope
from ..parallel.mesh import get_active_mesh

# row counts a packed region may run at, where they lie under the step's
# ``b * t``.  Every entry is one more body of the three regions in every
# ``t > 1`` program, traced and lowered by every start: about 0.75 s of a
# served start each (PERF.md section 6, PR 42, where (32, 64, 128) and
# (64, 128) were measured too: ``setup_s`` +5 to +7% against a bound of 10%).
# 64 holds 85% of the mixed steps of Mistral's served cell, and the Q40
# launches cost there 0.92 of what they cost at 16 rows
BUCKETS = (64,)


def buckets(rows: int) -> tuple[int, ...]:
    """The row counts a step of ``rows`` slot rows may run at, ascending; the
    last is ``rows`` itself."""
    return tuple(r for r in BUCKETS if r < rows) + (rows,)


def _packs(b: int, t: int, mesh) -> bool:
    """Static: does a slot step of ``b`` rows of ``t`` tokens pack its rows?"""
    return t > 1 and len(buckets(b * t)) > 1 \
        and (mesh is None or mesh.size <= 1)


def run_rows(valid: int, b: int, t: int, mesh=None) -> int:
    """The rows a step of ``b`` slot rows of ``t`` tokens runs when ``valid``
    of them carry a token: the device's rule (:func:`plan`), mirrored on the
    host for its counters alone."""
    if not _packs(b, t, mesh):
        return b * t
    return next(r for r in buckets(b * t) if valid <= r)


class Packed(NamedTuple):
    """One step's packing, the same for every layer (``n = b * t``)."""
    b: int
    t: int
    src: jax.Array     # (n,) int32: packed row i's place in slot layout (0 past the last)
    inv: jax.Array     # (n,) int32: a slot row's packed place (0 where it holds no token)
    valid: jax.Array   # (n,) bool: the slot row holds a token
    bucket: jax.Array  # () int32: index into ``buckets(n)``


def plan(n_valid: jax.Array, b: int, t: int) -> Packed | None:
    """The packing of a slot step whose row ``r`` holds ``min(n_valid[r], t)``
    tokens, or None where the step runs every row (module docstring)."""
    if not _packs(b, t, get_active_mesh()):
        return None
    n = b * t
    sizes = buckets(n)
    nv = jnp.clip(n_valid.astype(jnp.int32), 0, t)
    ends = jnp.cumsum(nv)
    starts = ends - nv
    i = jnp.arange(n, dtype=jnp.int32)
    # packed row i lies in the slot whose tokens end after it: stable, by slot
    slot = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), b - 1)
    src = jnp.where(i < ends[-1], slot * t + i - starts[slot], 0)
    j = jnp.arange(t, dtype=jnp.int32)
    valid = (j < nv[:, None]).reshape(n)
    inv = jnp.where(valid, (starts[:, None] + j).reshape(n), 0)
    bucket = jnp.sum(ends[-1] > jnp.asarray(sizes[:-1], jnp.int32))
    return Packed(b, t, src.astype(jnp.int32), inv.astype(jnp.int32), valid,
                  bucket.astype(jnp.int32))


def over(packed: Packed | None, name: str, fn, *xs, **kw):
    """``fn(*xs, **kw)`` for a row-local ``fn`` (arrays with leading row axes
    in, an array or a tuple of them with the same leading axes out) over slot
    arrays ``xs`` of leading shape ``(b, t)``.  With a plan, ``fn`` sees the
    valid rows alone, ``(R, ...)`` for the step's bucket ``R``, and the result
    comes back as ``(b, t, ...)`` with zeros in the rows that hold no token
    (the every-row body leaves there what ``fn`` made of them: nothing reads
    it); the switch and its gathers are filed under scope ``name``."""
    if packed is None:
        return fn(*xs, **kw)
    b, t, src, inv, valid, bucket = packed
    n = b * t

    def take(x, idx):
        return x.at[idx].get(mode="promise_in_bounds")

    def body(rows: int):
        def run(*flat):
            if rows == n:  # every row, in place: the step as it ran unpacked
                return fn(*flat, **kw)
            outs = fn(*(take(x, src[:rows]) for x in flat), **kw)
            return jax.tree.map(
                lambda y: jnp.where(valid.reshape((n,) + (1,) * (y.ndim - 1)),
                                    take(y, inv), jnp.zeros((), y.dtype)), outs)
        return run

    with scope(name):
        outs = jax.lax.switch(bucket, [body(r) for r in buckets(n)],
                              *(x.reshape(n, *x.shape[2:]) for x in xs))
        return jax.tree.map(lambda y: y.reshape(b, t, *y.shape[1:]), outs)
