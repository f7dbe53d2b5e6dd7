"""Mamba-2's selective state-space mixer ("SSD"; Falcon-H1's second sequence
mixer, beside grouped-query attention in every block, and the other layers of a
Granite-4.0-H period, ``models/windowed.py``) and its state: a matrix a
head that no position addresses, kept a block behind the position clock by
``ops/retention.py``'s watermark, beside a position-addressed ring of the
convolution's input as ``ops/conv.py``'s.

The operator, for the block's normed input ``u`` (``H`` heads of ``P`` values,
``G`` groups of ``N`` state rows, ``K`` taps; head ``h`` reads group ``g(h) = h
// (H / G)``; ``models/transformer.py _ssm_block`` makes ``z``, ``xBC`` and
``dt`` with their multipliers)::

    xBC_t[c] = silu(sum_{j<K} w[c, j] * xBC_{t-(K-1)+j}[c] + b[c])    zero before the start
    [x | B | C] = xBC        dt_t^h = softplus(dt_t^h + dt_bias^h)     A^h = -exp(A_log^h)
    S_t^h = exp(dt_t^h A^h) S_{t-1}^h + dt_t^h B_t^g (x_t^h)^T         (N x P, float32)
    y_t^h = (C_t^g)^T S_t^h + D^h x_t^h

In the attention form: ``y_t^h = sum_{j<=t} exp(sum_{i=j+1..t} dt_i^h A^h) (C_t^g
. B_j^g) dt_j^h x_j^h + D^h x_t^h``: linear attention with a scalar decay a
head, queries ``C``, keys ``B``, values ``dt * x``.  That is retention's
operator with ``phi`` the identity, no quotient and the gate ``dt * A``, so the
state is kept rewindable the same way and by the same rule, which this module
imports and does not restate: the state ``rs (L, rows, H, N, P)`` float32 holds
the tokens ``[0, w)``; rings of ``retention.RING`` recent positions hold ``B``
(``rk (L, rows, G, RING, N)``), ``x`` (``rv (L, rows, H, RING, P)``; heads of
64 two to a row, ``heads_a_row``) and ``dt``
(``rg (L, rows, 1, RING, H)`` float32) of ``[w, pos]``; ``rw`` is the watermark
``w``; ``retention.clock`` / ``watermark`` say when ``FOLD`` positions leave the
ring for the state, ``REWIND`` behind the clock, so a rewind within ``REWIND``
finds everything addressed by position and a deeper one is refused by name
(``Engine._state_enter``).  ``dt`` is stored and not ``dt * x`` and ``dt * A``:
the ring's bytes are the same, the products are made in float32 on the read,
and **a row whose ``dt`` is 0 neither decays nor feeds the state**, so a row
that holds no token (a ragged batch's left padding, a call's rows past its
``n_real``) is exact by masking its ``dt`` at the write and nothing downstream
knows of padding.  The convolution's input ``xBC`` (after the multipliers,
before the taps) is in ``cz (L, rows, 1, conv.RING, C)``, written and read with
``ops/conv.py``'s ring rule.

A call of ``t <= retention.MAX_ROWS`` rows (1) folds, (2) writes its rows into
the rings, (3) reads: ``C^T S`` decayed from ``w`` to each query (part
``state``: one XLA product over the layer's slice of ``rs``) plus the
attention form over the ring's rows in ``[w, query]`` (part ``recent``).  The
rings' rows are read by ONE launch a layer where the call has one token a row
(:func:`recent_walk`, every pure-decode step of a slot engine on one TPU
device: a slot's LIVE positions alone are copied, 33 to 96 of the ring's 128,
and the weights and the product are made in VMEM; :func:`_read_form` is the
rule) and by XLA ops over the whole ring, the dead positions weighted 0,
everywhere else (:func:`_recent`: a chunk's rows, one row, a mesh, the CPU).
The state is read once a call and written once a fold.  **Five things the
compile for the chip taught** (``tests/test_tpu_compile.py`` holds them at the
published widths): the read takes a layer's slice of ``rs`` and of ``rv`` AS
IT LIES, heads flat and
``x`` in slot order (the small ``C`` is repeated to the heads and the small
weights are rolled to the slots), because a view by groups or a roll of ``x``
between the slice and the product made XLA copy the slice out first (134 MB a
layer, more than twice the product's own time on the chip); the caller orders
the fold before the ring writes with a barrier and writes the convolution's
ring before it reads it, because XLA otherwise kept the old plane alive beside
the new one (the whole ``rv`` or ``cz`` plane copied twice a layer in the mixed
step); a launch that updates a plane in place (``window.ring_put``, a
pure-decode step's write of ``rk``, ``rv`` and ``cz``) asks for nearly all of
VMEM as its scope, because XLA moved a plane that fits there (``rk``, 75 MB)
into VMEM and back around the launch, a layer at a time, whatever memory space
the launch named (pinning the launch's result to HBM aborted the compiler),
and cannot give the launch both that scope and a plane there; and the launch
that reads the rings takes the layer's ``dt`` as a slice XLA cuts for it (half
a megabyte) and not the stacked ``rg``, because the fold's loop wants that
plane with the positions minor and a launch takes an operand with its last
axis minor: XLA then copied all 9.4 MB of it a layer (and Mosaic copies no
part of a row of 32 lanes by hand).

Ledger: ``{codec="ssm", path="state-read"|"block"|"fold"}`` one a compiled call
site, and ``path="recent-walk"`` beside ``state-read`` where the launch reads
the rings.  Device time: part ``ssm`` of scopes ``qkv`` (``W_in``, the ``dt``
projection, softplus) and ``wo`` (the gate, the grouped norm, ``W_out``), parts
``conv`` / ``state`` / ``recent`` of ``attn`` and ``conv`` / ``recent`` /
``fold`` of ``kv_write`` (``ops/scopes.py``): attention's own ops in the same
block keep the bare scopes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import dispatch as obs_dispatch
from ..parallel.mesh import get_active_mesh
from . import conv, window
from .retention import FOLD, REWIND, RING, _in_order
from .scopes import part

_HI = jax.lax.Precision.HIGHEST
_LANES = 128
# A decoded token's recent rows (:func:`recent_walk`): the positions one copy
# carries, a sublane tile of a bfloat16 ring; the most positions ``[w, query]``
# holds (the query is at most ``FOLD + REWIND - 1`` past its watermark); the
# slots a call has from which the launch reads them (:func:`_read_form`).
WALK = 16
LIVE = FOLD + REWIND
WALK_MIN_ROWS = 2
assert FOLD % WALK == 0 and LIVE % WALK == 0


def heads_a_row(h: int, p: int) -> int:
    """Heads of ``x`` a row of the ring ``rv`` holds: 2 where a head is half a
    lane row (``P`` = 64: Granite) and the heads pair up, else 1.  A plane
    whose minor axis is 64 wide is laid out by the TPU compiler with the axis
    before it, the ring's 128 positions, along the lanes: one position's write
    is then a store a value (8192 a slot a layer), and the pure-decode step
    spent 0.7 ms a layer in them (PERF.md section 6, PR 65).  Two heads side by
    side fill the lanes, and a position is a row again.  Heads narrower still
    keep a head a row: no served model has them."""
    return 2 if 2 * p == _LANES and h % 2 == 0 else 1


def _paired(x, rows: int):
    """``x (B, H, T, P)`` as the ring holds it, ``(B, rows, T, H / rows * P)``:
    the heads that share a row side by side."""
    b, h, t, p = x.shape
    if rows == h:
        return x
    return x.reshape(b, rows, h // rows, t, p).transpose(0, 1, 3, 2, 4).reshape(
        b, rows, t, -1)


def init_planes(cfg, rows: int, dt, layers: int | None = None) -> dict:
    """The mixer's planes of ``rows`` rows (module docstring), by field of
    ``KVCache``, ``layers`` deep: as many as the model has mixer layers
    (``cfg.n_ssm_layers``: every block of Falcon-H1; a period's other layers of
    Granite, whose attention layers keep keys and values in planes of their
    own depth).  ``None``: every layer of ``cfg``."""
    L = cfg.n_layers if layers is None else layers
    h, g = cfg.ssm_heads, cfg.ssm_groups
    f = heads_a_row(h, cfg.ssm_head_dim)
    return {
        "rs": jnp.zeros((L, rows, h, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
        "rk": jnp.zeros((L, rows, g, RING, cfg.ssm_state), dt),
        "rv": jnp.zeros((L, rows, h // f, RING, f * cfg.ssm_head_dim), dt),
        "rg": jnp.zeros((L, rows, 1, RING, h), jnp.float32),
        "rw": jnp.zeros((1, rows, 1, 1, 1), jnp.int32),
        "cz": jnp.zeros((L, rows, 1, conv.RING, cfg.ssm_channels), dt),
    }


def live_dt(dt, pos, floor=None, n_real=None):
    """``dt (B, T, H)`` with 0 in the rows that hold no token: past the call's
    ``n_real`` rows, before a ragged row's ``floor``."""
    t = dt.shape[1]
    j = jnp.arange(t)[None, :]
    live = jnp.ones((1, t), bool)
    if n_real is not None:
        live = live & (j < jnp.reshape(n_real, (-1, 1)))
    if floor is not None:
        live = live & (pos[:, None] + j >= floor[:, None])
    return jnp.where(live[..., None], dt, 0.0)


def fold(rs, rk, rv, rg, a, layer, w, w_new):
    """Fold positions ``[w, w + FOLD)`` of every row whose watermark moves from
    the rings into the state at ``layer``: ``S <- Gamma S + sum_j d_j dt_j B_j
    x_j^T`` with ``d_j`` the decay from ``j`` to the block's end and ``Gamma``
    the block's whole decay; ``a (H,)`` is the layer's ``A``.  A loop over the
    rows that fold (none in most steps), each touching its own ``(H, N, P)`` of
    the plane in place."""
    h, n, p = rs.shape[2:]
    g = rk.shape[2]
    obs_dispatch.record_dispatch("ssm", "fold", t=FOLD, ring=RING, N=n)
    need = w_new > w
    order = jnp.argsort(jnp.logical_not(need), stable=True).astype(jnp.int32)
    count = jnp.sum(need.astype(jnp.int32))
    zero = jnp.zeros((), jnp.int32)
    li = layer.astype(jnp.int32)

    def one(carry):
        i, rs = carry
        row = order[i]
        at = w[row]
        s0 = at % RING

        def held(ring):
            return jax.lax.dynamic_slice(
                ring, (li, row, zero, s0, zero),
                (1, 1, ring.shape[2], FOLD, ring.shape[4]))[0, 0]

        bf = held(rk).astype(jnp.float32)                       # (G, FOLD, N)
        xf = held(rv).astype(jnp.float32)                       # (H, FOLD, P)
        dt = held(rg)[0].T                                      # (H, FOLD)
        la = dt * a[:, None]
        total = jnp.sum(la, axis=-1)                            # (H,)
        coef = jnp.exp(total[:, None] - jnp.cumsum(la, axis=-1)) * dt
        f = h // xf.shape[0]
        if f == 1:
            s_add = jnp.einsum("gmjn,gmjp->gmnp", bf[:, None] * coef.reshape(
                g, h // g, FOLD, 1), xf.reshape(g, h // g, FOLD, p), precision=_HI)
        else:
            # the ring's rows hold f heads side by side (``heads_a_row``): each
            # head's weighted B against the whole row, its own P columns kept;
            # the slice of the ring is taken as it lies (splitting its rows by
            # head made XLA lay the whole plane the other way round and copy it
            # in and out of every step)
            bw = (jnp.repeat(bf, h // g, axis=0) * coef[..., None]).reshape(
                h // f, f, FOLD, n)
            s_add = jnp.stack([jnp.einsum(
                "rjn,rjq->rnq", bw[:, k], xf, precision=_HI)[
                    ..., k * p:(k + 1) * p] for k in range(f)], axis=1)
        keep = jnp.where(at > 0, jnp.exp(total), 0.0)           # (H,)
        s_old = jax.lax.dynamic_slice(rs, (li, row, zero, zero, zero),
                                      (1, 1, h, n, p))
        return i + 1, jax.lax.dynamic_update_slice(
            rs, s_old * keep[None, None, :, None, None]
            + s_add.reshape(1, 1, h, n, p), (li, row, zero, zero, zero))

    return jax.lax.while_loop(lambda c: c[0] < count, one, (zero, rs))[1]


def write(rk, rv, rg, b, x, dt, layer, pos):
    """A call's ``b (B, G, T, N)``, ``x (B, H, T, P)`` and ``dt (B, T, H)`` into
    the rings at ``layer``, row ``r`` at positions ``pos[r] .. pos[r] + T - 1``."""
    rk = window.ring_write_plane(rk, b, layer, pos)
    rv = window.ring_write_plane(rv, _paired(x, rv.shape[2]), layer, pos)
    rg = window.ring_write_plane(rg, dt[:, None], layer, pos)
    return rk, rv, rg


def _read_form(rv_shape, rows: int, t: int) -> str:
    """How a call reads its rings' rows, from static facts only, the same
    inside and outside a trace (as ``window._put_form``).  ``recent-walk``
    (:func:`recent_walk`: one launch a layer over the live positions) for a
    call of ONE token a row of at least ``WALK_MIN_ROWS`` rows (every
    pure-decode step of a slot engine; no cell serves a mixer one stream at a
    time) on one TPU device (a ``pallas_call`` is not partitioned by GSPMD)
    whose ``x`` ring's rows fill whole lanes (heads of 128, or heads of 64 two
    to a row, :func:`heads_a_row`: Mosaic copies no part of a 128-lane row);
    ``xla`` everything else: the block form of a chunk, a mesh, the CPU, a toy."""
    mesh = get_active_mesh()
    if t != 1 or rows < WALK_MIN_ROWS or jax.default_backend() != "tpu" or (
            mesh is not None and mesh.size > 1) or rv_shape[4] % _LANES:
        return "xla"
    return "recent-walk"


def _walk_kernel(layer_ref, pos_ref, base_ref, c_ref, a_ref, rk_ref, rv_ref,
                 g_ref, y_ref, gq_ref, xbuf, bbuf, wbuf, acc, sem, *, f: int):
    """One grid step, one slot: wait for the slot's live chunks of ``x``
    (``xbuf``) and ``B`` (``bbuf``), which the step before started (the first
    step its own), start the next slot's into the other buffer, then make the
    weights of the live positions (``wbuf``; the slot's ``dt``, ``g_ref``, is a
    block the pipeline brings) and fold ``x`` under them a chunk at a time
    (``acc``).  The buffers hold the positions IN ORDER from the watermark:
    chunk ``k`` of a buffer is ring slots ``(base + k * WALK) % RING ..``, the
    watermark a multiple of ``FOLD`` and ``FOLD`` of ``WALK``, so no chunk
    wraps.  Past the slot's last chunk a buffer holds what an earlier slot left
    or nothing this launch wrote, and past the live count inside it what the
    ring holds there: every such value is SELECTED away, never multiplied by
    0."""
    b = pl.program_id(0)
    li = layer_ref[0]
    g = bbuf.shape[1]
    rows, _, width = xbuf.shape[1:]
    h = g_ref.shape[1]

    def span(r):
        # live positions ``[base, pos]`` (1 .. LIVE), the ring slot of the
        # first (0 or FOLD) and the chunks that hold them; scalar arithmetic
        # through ``jax.lax`` (a ``jnp`` function is a ``jit`` of its own)
        n = jax.lax.max(jax.lax.min(pos_ref[r] - base_ref[r] + 1, LIVE), 1)
        s0 = jax.lax.rem(jax.lax.div(base_ref[r], FOLD), 2) * FOLD
        return n, s0, jax.lax.div(n + (WALK - 1), WALK)

    def chunk(r, s0, slot, k):
        src = pl.multiple_of(jax.lax.rem(s0 + k * WALK, RING), WALK)
        dst = pl.multiple_of(k * WALK, WALK)
        return (pltpu.make_async_copy(rv_ref.at[li, r, :, pl.ds(src, WALK), :],
                                      xbuf.at[slot, :, pl.ds(dst, WALK), :],
                                      sem.at[slot]),
                pltpu.make_async_copy(rk_ref.at[li, r, :, pl.ds(src, WALK), :],
                                      bbuf.at[slot, :, pl.ds(dst, WALK), :],
                                      sem.at[slot]))

    def each(r, slot, do):
        _, s0, nc = span(r)

        def one(k, carry):
            for copy in chunk(r, s0, slot, k):
                do(copy)
            return carry

        jax.lax.fori_loop(0, nc, one, 0)

    slot = jax.lax.rem(b, 2)

    @pl.when(b == 0)
    def _prime():
        each(b, slot, lambda copy: copy.start())

    @pl.when(b + 1 < pl.num_programs(0))
    def _ahead():
        each(b + 1, 1 - slot, lambda copy: copy.start())

    each(b, slot, lambda copy: copy.wait())
    n, s0, nc = span(b)

    # the weights of the live positions, positions on the sublanes and heads on
    # the lanes as ``dt`` lies: w[c, h] = exp(sum_{c < i < n} dt_i A) dt_c (C . B_c)
    at = jax.lax.broadcasted_iota(jnp.int32, (LIVE, h), 0)
    live = at < n
    dt = jnp.concatenate([
        g_ref[pl.ds(pl.multiple_of(s0, FOLD), FOLD), :],
        g_ref[pl.ds(pl.multiple_of(FOLD - s0, FOLD), REWIND), :]], axis=0)
    dt = jnp.where(live, dt, 0.0)
    la = dt * a_ref[...]
    # the decay from each position to the query: the sum of ``la`` over the
    # positions AFTER it, a log-step scan down the sublanes
    after = jnp.where(at + 1 < LIVE, pltpu.roll(la, LIVE - 1, 0), 0.0)
    step = 1
    while step < LIVE:
        after = after + jnp.where(at + step < LIVE,
                                  pltpu.roll(after, LIVE - step, 0), 0.0)
        step *= 2
    gq_ref[0] = after[:1] + la[:1]
    cq = c_ref[0]                                                   # (G, N)
    lane = jax.lax.broadcasted_iota(jnp.int32, (LIVE, h), 1)
    cb = None
    for i in range(g):
        col = jnp.sum(bbuf[slot, i].astype(jnp.float32) * cq[i:i + 1, :],
                      axis=1, keepdims=True)                        # (LIVE, 1)
        cb = col if cb is None else jnp.where(lane >= i * (h // g), col, cb)
    wbuf[:, pl.ds(0, h)] = jnp.where(live, jnp.exp(after) * dt * cb, 0.0)

    # y[h, :] = sum_c w[c, h] x[h, c, :] on the VPU, a chunk of positions at a
    # time over every row of the ring, each lane under the weight of its own
    # head: a row of one head splats that head's lane of the weights (a static
    # lane pattern), a row of ``f`` heads side by side gathers theirs (ONE
    # gather where two splats and a select cost a tenth more on the chip; the
    # gather for a row of one head a quarter more than its splat, and the rows
    # as a loop's index, eight a trip, 1.6 times the rows unrolled: PERF.md
    # section 6, PR 67)
    own = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (WALK, width), 1),
                      width // f)
    acc[...] = jnp.zeros_like(acc)

    def fold(k, carry):
        lo = pl.multiple_of(k * WALK, WALK)
        wk = wbuf[pl.ds(lo, WALK), :]                               # (WALK, lanes)
        # every chunk under the mask of the last: the selects ride in slots the
        # splats leave idle, and a second body for the whole chunks was half of
        # what every program's lowering paid for the launch
        ok = jax.lax.broadcasted_iota(jnp.int32, (WALK, width), 0) < n - lo
        for r in range(rows):
            x = xbuf[slot, r, pl.ds(lo, WALK), :].astype(jnp.float32)
            x = jax.lax.select(ok, x, jnp.zeros_like(x))
            if f == 1:
                wr = wk[:, r:r + 1]
            else:   # the row's heads lie in one block of ``width`` lanes of wk
                at0 = r * f // width * width
                wr = jnp.take_along_axis(wk[:, at0:at0 + width], own + (r * f - at0),
                                         axis=1, mode="promise_in_bounds")
            prod = x * wr
            acc[r] += prod[:WALK // 2] + prod[WALK // 2:]
        return carry

    jax.lax.fori_loop(0, nc, fold, 0)
    y_ref[0] = jnp.sum(acc[...], axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def recent_walk(c, rk, rv, rg, a, layer, pos, base, interpret: bool = False):
    """The part ``recent`` of :func:`read` for a call of ONE token a row, as
    ONE launch over the slots: ``(y (B, H, 1, P), gq (B, H, 1))`` float32, the
    attention form over the ring's rows ``[base, pos]`` and the query's
    log-decay since the watermark.  Per slot the launch copies the LIVE
    positions alone, ``pos - base + 1`` of the ring's ``RING`` rounded up to
    chunks of ``WALK``, of ``x`` and ``B``, the next slot's while this one is
    folded (the layer's ``dt`` comes as a slice XLA cuts, a slot's block at a
    time: the module docstring has why), and makes the weights and the product
    in VMEM, float32 throughout: the decay is a suffix sum from the query, the
    sum over positions runs in another order than :func:`_recent`'s product
    (``tests/test_ssm_read.py``: 1e-6).  A ``jit`` of its own, so that the
    kernel, whose body is unrolled over the ring's rows, is traced ONCE a
    process and not at every site of every program: 0.35 s a trace at
    Granite's 64 rows, and its cell's six programs held enough sites for 20 s
    of every start (``engine_compile_seconds`` 47 -> 69 s; PERF.md section 6,
    PR 67)."""
    b, g, _, n = c.shape
    rows, width = rv.shape[2], rv.shape[4]
    h = rg.shape[4]
    f = h // rows
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    y, gq = pl.pallas_call(
        functools.partial(_walk_kernel, f=f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[vmem((1, g, n), lambda i, *_: (i, 0, 0)),
                      vmem((1, h), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      vmem((None, RING, h), lambda i, *_: (i, 0, 0))],
            out_specs=[vmem((1, rows, width), lambda i, *_: (i, 0, 0)),
                       vmem((1, 1, h), lambda i, *_: (i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((2, rows, LIVE, width), rv.dtype),
                            pltpu.VMEM((2, g, LIVE, n), rk.dtype),
                            pltpu.VMEM((LIVE, -(-h // width) * width), jnp.float32),
                            pltpu.VMEM((rows, WALK // 2, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((b, rows, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_recent_walk",
    )(jnp.atleast_1d(layer).astype(jnp.int32), pos.astype(jnp.int32),
      base.astype(jnp.int32), c[:, :, 0].astype(jnp.float32),
      a.astype(jnp.float32)[None], rk, rv,
      jax.lax.dynamic_index_in_dim(rg, layer.astype(jnp.int32), 0, False)[:, 0])
    return y.reshape(b, h, 1, width // f), gq.reshape(b, h, 1)


def read(c, rs, rk, rv, rg, a, layer, pos, base):
    """``y (B, H, T, P)`` float32 (without the ``D x`` term) for the queries ``c
    (B, G, T, N)`` at positions ``pos[b] + t`` over a row's state (tokens ``[0,
    base)``) and its rings (``[base, query]``; the call's own rows are already
    written)."""
    b, g, t, n = c.shape
    h = rs.shape[2]
    m = h // g
    obs_dispatch.record_dispatch(
        "ssm", "state-read" if t == 1 else "block", t=t, ring=RING, N=n)
    form = _read_form(rv.shape, b, t)
    li = layer.astype(jnp.int32)
    cf = c.astype(jnp.float32)
    with part("recent"):
        if form == "recent-walk":
            obs_dispatch.record_dispatch("ssm", form, rows=b, ring=RING, N=n)
            y, gq = recent_walk(c, rk, rv, rg, a, li, pos, base)
        else:
            y, gq = _recent(cf, rk, rv, rg, a, li, pos, base)
    with part("state"):
        s = jax.lax.dynamic_index_in_dim(rs, li, 0, False)         # (B, H, N, P)
        since = jnp.where((base > 0)[:, None, None], jnp.exp(gq), 0.0)
        # a head at a time against its group's C, the state taken as it lies: a
        # view of it by groups stood between the layer's slice and the product,
        # and XLA then copied the slice out first (134 MB a layer at the
        # published widths, twice the product's own time)
        y = y + since[..., None] * jnp.einsum(
            "bhtn,bhnp->bhtp", jnp.repeat(cf, m, axis=1), s, precision=_HI)
    return y


def _recent(cf, rk, rv, rg, a, li, pos, base):
    """The rings' rows of :func:`read` in XLA ops, the whole ring read and the
    dead positions weighted 0: ``(y (B, H, T, P), gq (B, H, T))`` for the
    float32 queries ``cf`` at layer ``li``."""
    b, g, t, n = cf.shape
    h, f = rg.shape[4], rg.shape[4] // rv.shape[2]
    m, p = h // g, rv.shape[4] // f
    swap = base % RING != 0
    at = pos[:, None] + jnp.arange(t)[None, :] - base[:, None]     # (B, T) in ring order
    idx = jnp.arange(RING)
    # B and dt in position order from the watermark (small planes: rolled)
    br = _in_order(jax.lax.dynamic_index_in_dim(rk, li, 0, False), swap)
    dt = _in_order(jax.lax.dynamic_index_in_dim(rg, li, 0, False), swap
                   )[:, 0].transpose(0, 2, 1)                  # (B, H, RING)
    live = idx[None, :] <= at[:, -1:]                          # (B, RING)
    seen = idx[None, None, :] <= at[:, :, None]                # (B, T, RING)
    dt = jnp.where(live[:, None, :], dt, 0.0)
    cs = jnp.cumsum(dt * a[None, :, None], axis=-1)
    gq = jnp.take_along_axis(cs, jnp.broadcast_to(
        at[:, None, :], (b, h, t)), axis=-1)                   # (B, H, T)
    cb = jnp.einsum("bgtn,bgcn->bgtc", cf, br.astype(jnp.float32),
                    precision=_HI)                             # (B, G, T, RING)
    decay = jnp.exp(jnp.where(seen[:, None], gq[..., None]
                              - cs[:, :, None, :], -jnp.inf))  # (B, H, T, RING)
    w = decay * dt[:, :, None, :] * jnp.repeat(cb, m, axis=1)
    xr = jax.lax.dynamic_index_in_dim(rv, li, 0, False)        # (B, H, RING, P)
    # x stays in slot order as it lies and the weights are rolled to meet it
    # (RING is two FOLDs and the watermark a multiple of FOLD, so the same
    # swap of halves goes either way): a ring of x is 128 times a row of
    # weights, and the layer's slice feeds the product uncopied
    w = _in_order(w[..., None], swap)[..., 0]
    if f == 1:
        y = jnp.einsum("bhtc,bhcp->bhtp", w, xr.astype(jnp.float32),
                       precision=_HI)
    else:
        # a row of the ring holds f heads side by side (``heads_a_row``):
        # each of them against the whole row, as the one-head form above
        # (ONE product over both, two rows of weights a row of the ring,
        # made XLA lay the ring along the lanes again and copy the plane
        # to do it: 0.6 GB of temporaries), its own P columns kept
        wf, xf = w.reshape(b, h // f, f, t, RING), xr.astype(jnp.float32)
        y = jnp.stack([jnp.einsum(
            "bgtc,bgcq->bgtq", wf[:, :, k], xf, precision=_HI)[
                ..., k * p:(k + 1) * p] for k in range(f)],
            axis=2).reshape(b, h, t, p)
    return y, gq
