"""Power retention (Brumby's layers; Buckman, Gelada, Zhang, arXiv:2507.04239)
and its state: a matrix a kv head that no position addresses, kept a block
behind the position clock so that the clock can still be moved back.

The operator, for a query head ``h`` of kv head ``g``, head size ``dh``, degree
2, gate ``log gamma_t^g = logsigmoid(W_g u_t)^g`` (``models/transformer.py
_retention_block`` makes ``q``, ``k`` (normed, rotated), ``v`` and the gate)::

    a_{t,j} = exp(sum_{i=j+1..t} log gamma_i) * (q_t . k_j / sqrt(dh))^2     j <= t
    y_t     = sum_j a_{t,j} v_j / (sum_j a_{t,j} + EPS)

Its recurrent form needs a ``phi`` with ``phi(a) . phi(b) = (a . b / sqrt(dh))^2``
(:func:`phi`: the products of eight blocks of a head, each unordered pair of
blocks once, ``D = 36 (dh / 8)^2``: 9216 at 128, whole lane tiles, where the
element-wise symmetric square has 8256)::

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T      (D x dh)
    z_t = gamma_t z_{t-1} + phi(k_t)            (D)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

**A state that has absorbed a token cannot give it back**, so the engines' one
invariant about positions ("the pos-rewind invariant", ``runtime/engine.py``:
rows ahead of the clock are dead and get overwritten) would not hold for it.
It is made to hold by keeping the newest tokens OUT of the state.  A row (a
sequence of the contiguous cache, a slot of a slot engine) has

* the state ``S``, ``z`` (planes ``rs (L, rows, Hkv, D, dh)`` and ``rz (L,
  rows, Hkv, 1, D)``, float32) of the tokens ``[0, w)``;
* a ring of ``RING = FOLD + REWIND + MAX_ROWS`` recent positions of ``k``,
  ``v`` (``rk``, ``rv (L, rows, Hkv, RING, dh)``) and ``log gamma`` (``rg (L, rows, 1, RING,
  Hkv)``, float32), position ``p`` in slot ``p % RING``, written with
  ``ops/window.py``'s windows, holding ``[w, pos]``;
* its watermark ``w`` (``rw (1, rows, 1, 1, 1)`` int32: a leaf of the cache,
  no operand of a step program), a multiple of ``FOLD``.

A call of ``t <= MAX_ROWS`` rows at position ``pos``, of which the first
``n_real`` hold a token, (1) folds the ring's oldest ``FOLD`` positions into the
state, one MXU product a kv head, once the clock it leaves (``pos + n_real``)
is ``REWIND`` past their end (:func:`watermark`, the one statement of the rule:
the device's fold and the engines' accounts both call it); (2) writes its rows
into the ring; (3) reads: ``phi(q)^T S`` decayed from ``w`` to each query, plus
the attention form over the ring's rows in ``[w, query]``, over the same
quotient.  **The watermark is a function of the clock alone**, not of a call's
width or padding: a token decoded alone (``t`` = 1) and beside prefilling
neighbours (``t`` = 16, fifteen rows of padding) reads the same state and the
same ring rows, so a request's tokens do not depend on its neighbours.  What is
folded lies at least ``REWIND`` positions behind the clock, so rows ahead of
the clock stay rows ahead of the clock: bucket padding and a slot row's tail
past ``n_valid`` (at most ``MAX_ROWS`` rows: the ring's third part), a burst's
overshoot and a rejected draft need nothing special, and a rewind deeper than
that is refused by name (``Engine._state_enter``), as a convolution state's is.
The deepest rewind an engine makes is two pipelined bursts less one position
(:func:`max_burst`); a verify block and a slot step have at most 16 rows.  A
call that starts at position 0 starts a sequence: ``w`` is 0 and a state with
``w == 0`` reads as zero, so a slot's new tenant sees nothing of its
predecessor and nothing is cleared.  A call of more than ``MAX_ROWS`` rows is
refused at trace time: ``ModelConfig.prefill_chunk`` feeds a prompt in calls
that fit.

Ledger: ``{codec="retention", path="state-read"|"block"|"fold"}`` one a
compiled call site, with the call's rows, the ring and ``D``.  Device time:
part ``retention`` of scope ``qkv`` (the gate), parts ``state`` and ``recent``
of ``attn``, ``recent`` and ``fold`` of ``kv_write`` (``ops/scopes.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import dispatch as obs_dispatch
from . import window
from .scopes import part

# positions folded into the state at a time; positions behind the clock that
# always stay in the ring (the deepest rewind); rows of the widest call: what it
# folds lies wholly before its first row (MAX_ROWS <= REWIND), and its padding
# past the clock fits the ring beside the FOLD + REWIND - 1 positions that may
# still wait there
FOLD = 64
REWIND = 32
MAX_ROWS = 32
RING = FOLD + REWIND + MAX_ROWS
assert RING == 2 * FOLD and MAX_ROWS <= REWIND  # _in_order swaps halves
# added to the quotient's sum of scores
EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST
_BLOCKS = 8


def _block(dh: int) -> int:
    return dh // _BLOCKS if dh % _BLOCKS == 0 else 1


def state_dim(dh: int) -> int:
    """``D``: what :func:`phi` makes of a head of ``dh`` values."""
    blk = _block(dh)
    nb = dh // blk
    return nb * (nb + 1) // 2 * blk * blk


@functools.lru_cache(maxsize=None)
def _pairs(dh: int):
    """``(first, second, weight)`` of :func:`phi`'s ``D`` outputs: the two
    places of the head whose product output ``o`` is, as 0/1 matrices ``(dh,
    D)``, and its weight: ``dh^-1/2`` (the ``dh^(1/4)`` of both factors), times
    sqrt(2) where the two blocks differ (the pair stands for its mirror too)."""
    blk = _block(dh)
    nb = dh // blk
    a, b, w = [], [], []
    for i in range(nb):
        for j in range(i, nb):
            for p in range(blk):
                for q in range(blk):
                    a.append(i * blk + p)
                    b.append(j * blk + q)
                    w.append(1.0 if i == j else math.sqrt(2.0))
    first = np.zeros((dh, len(a)), np.float32)
    second = np.zeros((dh, len(a)), np.float32)
    first[a, np.arange(len(a))] = 1.0
    second[b, np.arange(len(a))] = 1.0
    return first, second, np.asarray(w, np.float32) * dh ** -0.5


def phi(x: jax.Array) -> jax.Array:
    """``(..., dh) -> (..., D)`` float32 with ``phi(a) . phi(b) = (a . b / sqrt(
    dh))^2``: the head in ``dh / blk`` blocks of ``blk``, for each pair of
    blocks ``i <= j`` the ``blk x blk`` products of their values, times sqrt(2)
    where ``i < j``.  The two factors of every product are picked by a matmul
    against a 0/1 matrix, which lays the ``D`` outputs along lanes as they are
    made (a reshape of ``blk x blk`` tiles into a row is a relayout of every
    row); exact: one bf16 pass copies a bf16 value, and a float32 input takes
    ``highest``."""
    first, second, weight = _pairs(x.shape[-1])
    if x.dtype == jnp.bfloat16:
        pick = lambda m: jnp.matmul(  # noqa: E731
            x, jnp.asarray(m, jnp.bfloat16), preferred_element_type=jnp.float32)
    else:
        pick = lambda m: jnp.matmul(  # noqa: E731
            x.astype(jnp.float32), jnp.asarray(m), precision=_HI)
    return pick(first) * pick(second) * jnp.asarray(weight)


def watermark(w, clock):
    """The watermark after a call that leaves the position clock at ``clock``
    (the call's first position plus its rows that hold a token): the greatest
    multiple of ``FOLD`` at least ``REWIND`` behind the clock, and never below
    ``w`` (a clock that was moved back folds nothing again).  A function of the
    clock alone.  Ints and numpy arrays on the host (``Engine._state_wrote``,
    ``_note_slot_folds``), traced arrays on the device."""
    need = (clock - REWIND) // FOLD * FOLD
    if isinstance(need, int):
        return max(w, need)
    return (np if isinstance(need, np.ndarray) else jnp).maximum(w, need)


def max_burst() -> int:
    """The longest pipelined decode burst whose deepest rewind (``2 * burst -
    1`` positions behind the clock) stays in the ring."""
    return (REWIND + 1) // 2


def init_planes(layers: int, rows: int, hkv: int, dh: int, dt) -> dict:
    """The planes of ``rows`` rows (module docstring), by field of ``KVCache``."""
    d = state_dim(dh)
    return {
        "rs": jnp.zeros((layers, rows, hkv, d, dh), jnp.float32),
        "rz": jnp.zeros((layers, rows, hkv, 1, d), jnp.float32),
        "rk": jnp.zeros((layers, rows, hkv, RING, dh), dt),
        "rv": jnp.zeros((layers, rows, hkv, RING, dh), dt),
        "rg": jnp.zeros((layers, rows, 1, RING, hkv), jnp.float32),
        "rw": jnp.zeros((1, rows, 1, 1, 1), jnp.int32),
    }


def clock(rw: jax.Array, pos: jax.Array, t: int, n_real=None):
    """``(w, w_new)`` of a call of ``t`` rows at each row's position ``pos
    (B,)``, of which the first ``n_real`` (a scalar or ``(B,)``; ``None``: all)
    hold a token: the watermark the call finds (0 where it starts a sequence)
    and the one it leaves."""
    if t > MAX_ROWS:
        raise ValueError(
            f"a call of {t} rows does not fit a retention layer's ring of "
            f"{RING} recent positions: feed at most {MAX_ROWS} rows a call")
    w = jnp.where(pos == 0, 0, rw.reshape(-1))
    return w, watermark(w, pos + (t if n_real is None else n_real))


def fold(rs, rz, rk, rv, rg, layer, w, w_new, floor=None):
    """Fold positions ``[w, w + FOLD)`` of every row whose watermark moves
    (``w_new > w``) from the ring into the state at ``layer``: ``S <- Gamma S
    + phi(K)^T (d * V)``, ``z <- Gamma z + phi(K)^T d`` with ``d_j`` the decay
    from ``j`` to the block's end and ``Gamma`` the block's whole decay.  A
    loop over the rows that fold (none in most steps), each touching its own
    ``(Hkv, D, dh)`` of the plane in place."""
    hkv, dh = rk.shape[2], rk.shape[4]
    d = rs.shape[3]
    obs_dispatch.record_dispatch("retention", "fold", t=FOLD, ring=RING, D=d)
    need = w_new > w
    order = jnp.argsort(jnp.logical_not(need), stable=True).astype(jnp.int32)
    count = jnp.sum(need.astype(jnp.int32))
    zero = jnp.zeros((), jnp.int32)
    li = layer.astype(jnp.int32)

    def one(carry):
        i, rs, rz = carry
        row = order[i]
        at = w[row]
        s0 = at % RING

        def held(ring, width):
            return jax.lax.dynamic_slice(
                ring, (li, row, zero, s0, zero),
                (1, 1, ring.shape[2], FOLD, width))[0, 0]

        kf, vf = held(rk, dh), held(rv, dh)                     # (Hkv, FOLD, dh)
        lg = held(rg, hkv)[0].T                                 # (Hkv, FOLD)
        live = jnp.ones((FOLD,), bool) if floor is None else (
            at + jnp.arange(FOLD) >= floor[row])
        lg = jnp.where(live, lg, 0.0)
        total = jnp.sum(lg, axis=-1)                            # (Hkv,)
        tail = jnp.exp(total[:, None] - jnp.cumsum(lg, axis=-1)) * live
        fk = phi(kf) * tail[..., None]
        s_add = jnp.einsum("gad,gae->gde", fk, vf.astype(jnp.float32),
                           precision=_HI)
        z_add = jnp.sum(fk, axis=1)                             # (Hkv, D)
        keep = jnp.where(at > 0, jnp.exp(total), 0.0)           # (Hkv,)
        s_old = jax.lax.dynamic_slice(rs, (li, row, zero, zero, zero),
                                      (1, 1, hkv, d, dh))
        z_old = jax.lax.dynamic_slice(rz, (li, row, zero, zero, zero),
                                      (1, 1, hkv, 1, d))
        rs = jax.lax.dynamic_update_slice(
            rs, s_old * keep[None, None, :, None, None] + s_add[None, None],
            (li, row, zero, zero, zero))
        rz = jax.lax.dynamic_update_slice(
            rz, z_old * keep[None, None, :, None, None]
            + z_add[None, None, :, None, :], (li, row, zero, zero, zero))
        return i + 1, rs, rz

    _, rs, rz = jax.lax.while_loop(lambda c: c[0] < count, one, (zero, rs, rz))
    return rs, rz


def write(rk, rv, rg, k, v, lg, layer, pos):
    """A call's ``k``, ``v (B, Hkv, T, dh)`` and ``lg (B, Hkv, T)`` into the
    rings at ``layer``, row ``b`` at positions ``pos[b] .. pos[b] + T - 1``."""
    rk = window.ring_write_plane(rk, k, layer, pos)
    rv = window.ring_write_plane(rv, v, layer, pos)
    rg = window.ring_write_plane(rg, lg.transpose(0, 2, 1)[:, None], layer, pos)
    return rk, rv, rg


def _in_order(x, swap):
    """A row's ring ``(B, ..., RING, ·)`` (ring on axis -2) from slot order to
    position order from its watermark ``base``: ``base`` is a multiple of
    ``FOLD`` and the ring two of them, so the halves are swapped or not."""
    sw = swap.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(sw, jnp.roll(x, FOLD, axis=-2), x)


def read(q, rs, rz, rk, rv, rg, layer, pos, base, floor=None):
    """``y (B, Hq, T, dh)`` for the queries ``q (B, Hq, T, dh)`` at positions
    ``pos[b] + t`` over a row's state (tokens ``[0, base)``) and its ring
    (``[base, query]``; the call's own rows are already written)."""
    b, hq, t, dh = q.shape
    hkv, d = rk.shape[2], rs.shape[3]
    m = hq // hkv
    obs_dispatch.record_dispatch(
        "retention", "state-read" if t == 1 else "block", t=t, ring=RING, D=d)
    li = layer.astype(jnp.int32)
    q5 = q.reshape(b, hkv, m, t, dh)
    swap = base % RING != 0
    at = pos[:, None] + jnp.arange(t)[None, :] - base[:, None]     # (B, T) in ring order
    idx = jnp.arange(RING)
    with part("recent"):
        kr = _in_order(jax.lax.dynamic_index_in_dim(rk, li, 0, False), swap)
        vr = _in_order(jax.lax.dynamic_index_in_dim(rv, li, 0, False), swap)
        lg = _in_order(jax.lax.dynamic_index_in_dim(rg, li, 0, False), swap
                       )[:, 0].transpose(0, 2, 1)                  # (B, Hkv, RING)
        live = idx[None, :] <= at[:, -1:]                          # (B, RING)
        seen = idx[None, None, :] <= at[:, :, None]                # (B, T, RING)
        if floor is not None:
            ok = base[:, None] + idx[None, :] >= floor[:, None]
            live, seen = live & ok, seen & ok[:, None, :]
        cs = jnp.cumsum(jnp.where(live[:, None, :], lg, 0.0), axis=-1)
        gq = jnp.take_along_axis(cs, jnp.broadcast_to(
            at[:, None, :], (b, hkv, t)), axis=-1)                 # (B, Hkv, T)
        qk = jnp.einsum("bgmtd,bgcd->bgmtc", q5.astype(jnp.float32),
                        kr.astype(jnp.float32), precision=_HI) * dh ** -0.5
        decay = jnp.exp(jnp.where(seen[:, None, None], gq[:, :, None, :, None]
                                  - cs[:, :, None, None, :], -jnp.inf))
        a = qk * qk * decay
        num = jnp.einsum("bgmtc,bgcd->bgmtd", a, vr.astype(jnp.float32),
                         precision=_HI)
        den = jnp.sum(a, axis=-1)
    with part("state"):
        fq = phi(q5)                                               # (B, Hkv, m, T, D)
        s = jax.lax.dynamic_index_in_dim(rs, li, 0, False)         # (B, Hkv, D, dh)
        z = jax.lax.dynamic_index_in_dim(rz, li, 0, False)[:, :, 0]
        since = jnp.where((base > 0)[:, None, None], jnp.exp(gq), 0.0)
        since = since[:, :, None, :]                               # (B, Hkv, 1, T)
        num = num + since[..., None] * jnp.einsum(
            "bgmtD,bgDd->bgmtd", fq, s, precision=_HI)
        den = den + since * jnp.einsum("bgmtD,bgD->bgmt", fq, z, precision=_HI)
    y = num / (den[..., None] + EPS)
    return y.reshape(b, hq, t, dh).astype(q.dtype)
