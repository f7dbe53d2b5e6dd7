"""A call's (row, expert) pairs in blocks of rows that share an expert.

``moe_ffn``'s many-row strategy ``grouped`` (``models/transformer.py``): the
``rows x k`` pairs the router made are ordered by expert and every expert's run
is given a whole number of blocks of ``tr`` rows, so that a block is ONE
expert's and one launch of ``q40_mm_grouped`` (``ops/q40.py``) walks the blocks
with each block's plane in a prefetched vector.  An expert nobody chose has no
block, and the blocks a call does not fill cost nothing, so a launch works for
the pairs there are and not for ``experts x rows``.

Everything here is index arithmetic on ``rows x k`` int32 values: a running
count over a one-hot ``(pairs, experts)`` gives every pair its slot, and one
scatter of ``rows x k`` int32 row numbers (unique places) turns that around
into the row of every slot.  The activations are never scattered: a row has
exactly ``k`` pairs, so the way back is a gather by :attr:`Plan.slot` and a
sum over ``k``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Plan(NamedTuple):
    planes: jax.Array  # (M,) the expert of each block; past ``used``: the last
    used: jax.Array    # () blocks that hold rows
    gather: jax.Array  # (M * tr,) the row each slot holds; row 0 where none
    slot: jax.Array    # (rows, k) the slot of each pair (any, where dropped)


def block_rows(rows: int, k: int, experts: int) -> int | None:
    """Rows a block, or None where the call keeps ``all-experts``: 16 rows and
    fewer, which is every pure-decode step.

    A launch unpacks a weight tile once a (block, tile), so its time follows
    the blocks that hold rows, a little more the more rows a block has: the
    best block holds an expert's whole run where routing is even and no more.
    From the mean rows one of the router's ``experts`` gets (``rows * k /
    experts``, held here or not): the power of two from 4/3 of it up, between
    16 and 128 (one activation block of the kernel).  32 at LFM2's 256-row
    bucket (a mean of 16: 1.33 ms a layer against 1.70 at 16 and 1.47 at 64,
    under uniform routing), 64 at SmallThinker's 512-row chunk (48: 1.22
    against 1.75 at 32 and 1.61 at 128) and at OLMoE's 256 rows (32), 16 at
    every 64-row packed step.  No shape a cell runs loses to ``all-experts``:
    the narrowest, DeepSeek-V2's 64-row step (2.4 rows an expert, 145 of 160
    experts hit) reads 5.76 ms against 7.21 and OLMoE's 0.79 against 0.81
    (PERF.md section 6, PR 53: tools/sweep_q40.py --grouped)."""
    if rows <= 16:
        return None
    tr = 16
    while 3 * tr < 4 * rows * k / experts and tr < 128:
        tr *= 2
    return tr


CHUNK = 128  # pairs a chunk of the running count


def blocks(rows: int, k: int, held: int, tr: int) -> int:
    """The static block count ``M``: every pair, and a ragged last block an
    expert at worst."""
    return -(-(rows * k + held * (tr - 1)) // tr)


def plan(idx: jax.Array, held: int, tr: int, keep: jax.Array | None = None) -> Plan:
    """``idx`` ``(rows, k)``: each pair's plane among the ``held``; ``keep``
    ``(rows, k)`` bool: pairs that are this chip's (None: all), the others get
    no slot.

    Tables of ``held`` entries are read through one-hot masks and a sum, not
    indexed: the TPU compiler expands a gather from a small table into a loop
    of its own, six of them a layer doubled a prompt's program (PERF.md
    section 6, PR 53)."""
    n, k = idx.shape
    p, m = n * k, blocks(n, k, held, tr)
    experts = jnp.arange(held, dtype=jnp.int32)
    key = (idx if keep is None else jnp.where(keep, idx, held)).reshape(p)
    # a pair's rank among its expert's pairs: a running count down the one-hot
    # ``(pairs, held)``, in chunks of 128 pairs and then over the chunks (one
    # cumsum down all the pairs is 0.11 ms at 1024 of them, this 0.01)
    pad = -p % CHUNK
    onehot = (jnp.pad(key, (0, pad), constant_values=held)[:, None]
              == experts[None, :])
    inner = jnp.cumsum(onehot.astype(jnp.int32).reshape(-1, CHUNK, held), axis=1)
    ends = jnp.cumsum(inner[:, -1], axis=0)                      # (chunks, held)
    cum = (inner + (ends - inner[:, -1])[:, None]).reshape(p + pad, held)
    counts = ends[-1]
    nb = jax.lax.div(counts + (tr - 1), jnp.int32(tr))          # blocks an expert
    bend = jnp.cumsum(nb)
    bstart, used = bend - nb, bend[-1]
    # a pair's slot: its expert's first block, then its rank; -1: no slot
    slot = jnp.sum(jnp.where(onehot, cum + (bstart * tr)[None, :], 0), axis=1)[:p] - 1
    # a block's expert; the blocks past ``used`` stand on the last one read
    b = jnp.arange(m, dtype=jnp.int32)
    own = (bstart[None, :] <= b[:, None]) & (b[:, None] < bend[None, :])  # (m, held)
    planes = jnp.where(b < used, jnp.sum(jnp.where(own, experts[None, :], 0), axis=1),
                       jnp.max(jnp.where(nb > 0, experts, 0)))
    # a slot's row: each kept pair writes its row where it sits
    rows = np.arange(p, dtype=np.int32) // k
    gather = jnp.zeros((m * tr,), jnp.int32).at[
        jnp.where(slot >= 0, slot, m * tr)].set(rows, mode="drop", unique_indices=True)
    return Plan(planes.astype(jnp.int32), used.astype(jnp.int32), gather,
                jnp.maximum(slot, 0).reshape(n, k))


# ---- how full the blocks were: one value out of a traced program ----------
#
# ``moe_ffn`` sits under a ``lax.scan`` over layers, so what it learns while
# tracing (the blocks a layer used) cannot be handed up as a Python value.
# A caller that wants it opens :func:`collecting`; ``moe_ffn`` calls
# :func:`note`; the layer loops run their scan through :func:`scan`, which
# carries a body's notes out as one more scanned output and sums them.  With
# no collector open ``note`` does nothing and ``scan`` is ``lax.scan``: a
# program that nobody asks is the program it was.

_LOCAL = threading.local()


@contextlib.contextmanager
def collecting():
    """Within it, every grouped layer traced adds ``[pairs, slots]`` to the
    list yielded (int32 ``(2,)`` each: the pairs it placed and the rows of
    the blocks they took)."""
    outer, _LOCAL.notes = getattr(_LOCAL, "notes", None), []
    try:
        yield _LOCAL.notes
    finally:
        _LOCAL.notes = outer


def note(pairs, tr: int, used: jax.Array) -> None:
    """``pairs``: how many pairs the layer placed, or the ``(rows, k)`` mask
    of those it did (counted only where somebody collects)."""
    notes = getattr(_LOCAL, "notes", None)
    if notes is not None:
        notes.append(jnp.stack([jnp.sum(pairs, dtype=jnp.int32), used * tr]))


def total(notes: list) -> jax.Array | None:
    """``[pairs, slots]`` over the layers that noted, None where none did."""
    return sum(notes[1:], notes[0]) if notes else None


def scan(body, carry, xs):
    """``lax.scan(body, carry, xs)`` whose body may :func:`note`."""
    if getattr(_LOCAL, "notes", None) is None:
        return jax.lax.scan(body, carry, xs)

    def noting(c, x):
        with collecting() as notes:
            c, ys = body(c, x)
        return c, (ys, total(notes))

    carry, (ys, noted) = jax.lax.scan(noting, carry, xs)
    if noted is not None:
        _LOCAL.notes.append(noted.sum(0))
    return carry, ys
