"""The gated short convolution (LFM2's ``conv`` layers) and its state: a ring
of positions beside the KV cache.

The operator, for the normed input ``u`` of a layer (hidden ``D``, ``K`` taps)::

    [B, C, X] = W_in u              (D -> 3 D, split in that order)
    z_t       = B_t * X_t
    y_t[c]    = C_t[c] * sum_{j < K} w[c, j] * z_{t - (K - 1) + j}[c]
    Op        = W_out y             (D -> D)

a depthwise causal convolution over ``z``; ``z`` before the sequence's start is
zero.  What a sequence leaves behind in such a layer is not keys and values
but the last ``K - 1`` rows of ``z``, whatever the context's depth.

**The state is addressed by position**, as keys and values are, so that the
engines' one invariant about positions holds for it too (``runtime/engine.py
Engine``, "the pos-rewind invariant"): the plane ``cz`` is ``(Lc, B, 1, R,
D)``, the contiguous cache's and the slot engines' alike (a slot owns its row;
no page id addresses it), ``z`` of position ``p`` in slot ``p % R`` of its
row, written with ``ops/window.py``'s windows.  A call reads the ``K - 1``
rows before its first position under the mask ``floor <= position`` (``floor``
is 0, or a left-padded ragged row's first real position), computes from its
own ``z``, and writes its rows.  Then

* rows past a call's last real token (a prompt's bucket padding, a slot row's
  tail past ``n_valid``, a slot with ``n_valid`` 0) land AHEAD of the live
  position, where the next call overwrites them before anything reads them;
  they displace positions ``R`` behind them, which is harmless while ``R >=
  T + K - 1``;
* a rewind of the position clock by ``w`` (a decode burst past an
  end-of-sequence token, a rejected draft, a stop string) finds the rows it
  needs as long as ``w <= R - (K - 1)`` counted from the highest position
  written: ``Engine`` keeps that account (``conv_state_rewinds``) and refuses
  a deeper one by name;
* a slot's new tenant starts at position 0 and masks whatever its predecessor
  left.

``RING`` is ``R``.  The deepest rewind an engine makes: the one-stream
engine's decode bursts are pipelined, so an end-of-sequence token in the first
place of a burst finds the next burst already written, ``2 * burst - 1``
positions (31 at the default burst of 16; ``Engine`` caps a burst at
``max_burst(RING, K)`` for such a model); a verify block rejects up to
``spec_k`` of ``spec_k + 1 <= 16`` rows; a slot step has at most
``windowed.SLOT_ROWS`` = 16 rows.  64 holds all of them with room.

A call of more rows than ``R - (K - 1)`` (a prefill chunk) writes the ``R - (K
- 1)`` rows that end at its last real token (:func:`written`, the one
statement of that rule; ``n_real``: a bucketed prefill
already has that row's index to pick the logits), so padding never displaces
the prompt's end and the rows read stay clear of the rows written.

Ledger: ``{codec="conv", path="ring"}`` one a compiled call site, with the
call's rows and the ring.  Device time: part ``conv`` of the scopes the
operator passes through (``qkv``, ``kv_write``, ``attn``, ``wo``;
``ops/scopes.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import dispatch as obs_dispatch
from . import window

# positions a row's state ring holds (module docstring)
RING = 64


def max_burst(ring: int, taps: int) -> int:
    """The longest pipelined decode burst whose deepest rewind (``2 * burst -
    1`` positions) still finds the ``taps - 1`` rows before the live position
    in a ring of ``ring``."""
    return (ring - (taps - 1) + 1) // 2


def state_read(cz: jax.Array, layer: jax.Array, pos: jax.Array, taps: int,
               floor: jax.Array | None = None) -> jax.Array:
    """The ``taps - 1`` rows of ``z`` before each row's position ``pos (B,)``,
    ``(B, taps - 1, D)`` oldest first, zeros where the position is before the
    sequence's start (or a ragged row's ``floor``)."""
    b, r = cz.shape[1], cz.shape[3]
    back = pos[:, None] - jnp.arange(taps - 1, 0, -1)[None, :]      # (B, K-1)
    rows = cz.at[layer.astype(jnp.int32), jnp.arange(b)[:, None], 0,
                 back % r].get(mode="promise_in_bounds")            # (B, K-1, D)
    live = back >= (0 if floor is None else floor[:, None])
    return jnp.where(live[..., None], rows, jnp.zeros((), rows.dtype))


def written(n_real, rows: int, ring: int, taps: int):
    """Which of a call's ``rows`` rows enter a ring of ``ring`` positions, as
    ``(first, count)`` from the call's first row: every row of a call that
    leaves the ``taps - 1`` rows it read in place (``rows <= ring - (taps -
    1)``), else the ``ring - (taps - 1)`` rows that end at the last of its
    ``n_real`` rows that hold a token.  The one statement of the rule: the
    device's write (:func:`state_write`, ``n_real`` an array) and the
    one-stream engine's account of what the ring holds (``Engine._state_wrote``,
    ``n_real`` an int) both call it."""
    keep = ring - (taps - 1)
    if rows <= keep:
        return 0, rows
    if isinstance(n_real, int):
        return min(max(n_real - keep, 0), rows - keep), keep
    return jnp.clip(n_real - keep, 0, rows - keep), keep


def state_write(cz: jax.Array, z: jax.Array, layer: jax.Array, pos: jax.Array,
                taps: int, n_real=None) -> jax.Array:
    """Write a call's ``z (B, T, D)`` at positions ``pos[b] .. pos[b] + T - 1``
    of ``cz`` at ``layer``: the rows :func:`written` names (``n_real``: a
    scalar or ``(B,)``; ``None``: all ``T`` are real)."""
    b, t, _ = z.shape
    first, count = written(t if n_real is None else n_real, t, cz.shape[3],
                           taps)
    if count < t:
        first = jnp.broadcast_to(jnp.asarray(first, jnp.int32), (b,))
        z = jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
            row, s, count, axis=0))(z, first)
        pos = pos + first
    return window.ring_write_plane(cz, z[:, None], layer, pos)


def taps(z: jax.Array, carried: jax.Array, w: jax.Array, pos: jax.Array,
         floor: jax.Array | None = None) -> jax.Array:
    """The depthwise causal convolution ``(B, T, D)`` float32 of the call's
    ``z`` after the ``carried (B, K - 1, D)`` rows before it under the taps ``w
    (D, K)``."""
    t, k = z.shape[1], w.shape[-1]
    if floor is not None:  # a ragged row's padding is before its sequence
        at = pos[:, None] + jnp.arange(t)[None, :]
        z = jnp.where((at >= floor[:, None])[..., None], z,
                      jnp.zeros((), z.dtype))
    ext = jnp.concatenate([carried.astype(z.dtype), z], axis=1
                          ).astype(jnp.float32)                    # (B, T+K-1, D)
    wf = w.astype(jnp.float32)
    acc = ext[:, 0:t] * wf[:, 0]
    for j in range(1, k):
        acc = acc + ext[:, j:j + t] * wf[:, j]
    return acc


def taps_and_gate(z: jax.Array, carried: jax.Array, c: jax.Array,
                  w: jax.Array, pos: jax.Array,
                  floor: jax.Array | None = None) -> jax.Array:
    """``y (B, T, D)`` from the call's ``z``, the ``carried (B, K - 1, D)`` rows
    before it, the gate ``c`` and the taps ``w (D, K)``, summed in float32."""
    return (c.astype(jnp.float32) * taps(z, carried, w, pos, floor)
            ).astype(z.dtype)


def record(t: int, ring: int, taps: int) -> None:
    obs_dispatch.record_dispatch("conv", "ring", t=t, ring=ring, taps=taps)
