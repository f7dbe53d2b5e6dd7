"""Fused Q80 dequantize-matmul (reference weight-ftype dispatch parity).

The reference's production matmul dispatches on the WEIGHT file type —
F32/F16/Q40/Q80 all have first-class kernels (funcs.cpp:414-455; Q80:
matmulQ80, funcs.cpp:268-285).  Round ≤3 only gave Q40 the packed fused
path; Q80-weight `.m` files dequantized to dense bf16 at load, paying 2
B/weight of HBM per decode step instead of the stored 1.0625 B/weight.
This module closes that gap the TPU way, mirroring ops/q40.py:

* ``Q8Tensor`` — int8 value plane ``(..., padded_n, d)`` + f16-bit scale
  plane ``(..., padded_n/32, d)``, input-dim-major so a (tile_n, tile_d)
  tile is contiguous per output column, same as the Q40 planes;
* a Pallas kernel that widens int8 → f32, applies the per-block scale
  (the file codec's math, quants.py:162-171), rounds the product to bf16
  for the MXU — one more round than the codec's f32 dequant, the same
  policy as the q40 kernel — and accumulates reduction tiles in
  VMEM; q8.dequantize applies the identical round so kernel and XLA
  emulation agree bit-for-bit;
* a layer-stacked form with the layer index as scalar prefetch, so
  the ``lax.scan`` over layers DMAs tiles straight from the stacked HBM
  buffer (no per-layer slice materialization — see q40._pallas_matmul_stacked);
* XLA-emulation fallback (`impl="xla"`): bit-identical dequant + dot,
  GSPMD-partitionable — the path multi-device meshes take (Q80 is not
  the production format; its mesh story is correctness, not the custom
  kernel; q40.py carries the sharded fast path).

Shares q40's padding contract (``padded_n``; padded scales are zero) and
its f16-bit scale decode (no f16 in the Mosaic dialect).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import quants
from .q40 import (PALLAS_MAX_ROWS, QLayerView, _f16_bits_to_f32, _pad_x,
                  _smap_mesh, _tile_n_legal, _tiles, padded_n)

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Q8Tensor:
    """A Q80 tensor of logical shape ``(..., n, d)``, packed for the MXU.

    Field names match ``q40.QTensor`` so ``q40.QLayerView`` (select /
    flat_planes / sliced) works unchanged over stacked Q8 planes."""

    qpacked: jax.Array          # int8   (..., padded_n, d)
    scales: jax.Array           # uint16 (..., padded_n/32, d) — f16 bits
    logical_nd: tuple[int, int] = field(metadata=dict(static=True))

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.qpacked.shape[:-2]) + self.logical_nd

    @property
    def dtype(self):
        return jnp.bfloat16


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------

def alloc_value_plane(lead: tuple, np_: int, d: int) -> np.ndarray:
    """Q80 stores one int8 row per input position (q40 twin packs 2/byte)."""
    return np.zeros((*lead, np_, d), np.int8)


Tensor = Q8Tensor  # codec-generic alias (q40.Tensor = QTensor)


def pack_planes_np(qvals: np.ndarray, scales: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray, tuple[int, int]]:
    """int8 values ``(..., n, d)`` + f16 scales ``(..., n/32, d)`` →
    padded host planes (zero pad values AND scales: the padded region
    contributes exactly 0 to every dot)."""
    *lead, n, d = qvals.shape
    np_ = padded_n(n)
    q = np.asarray(qvals, np.int8)
    s = np.asarray(scales, np.float16)
    if np_ != n:
        q = np.concatenate([q, np.zeros((*lead, np_ - n, d), np.int8)], axis=-2)
        s = np.concatenate(
            [s, np.zeros((*lead, (np_ - n) // 32, d), np.float16)], axis=-2)
    return q, s, (n, d)


def quantize(w: np.ndarray) -> Q8Tensor:
    """Quantize a float array ``(..., n, d)`` along the input axis with the
    file codec's math (delta = absmax/127; round half away from zero like
    the reference's roundf — quants.round_half_away / writer.py:58-77)."""
    w = np.asarray(w, np.float32)
    *lead, n, d = w.shape
    if n % quants.BLOCK_SIZE:
        raise ValueError(f"input dim {n} not divisible by {quants.BLOCK_SIZE}")
    g = w.reshape(*lead, n // 32, 32, d)
    deltas = np.abs(g).max(axis=-2) / 127.0
    inv = np.where(deltas != 0, np.divide(1.0, deltas, where=deltas != 0), 0.0)
    q = quants.round_half_away(g * inv[..., None, :]) \
        .astype(np.int8).reshape(*lead, n, d)
    with np.errstate(over="ignore"):  # overflow becomes inf → caught below
        sc = deltas.astype(np.float16)
    if not np.isfinite(sc).all():
        raise ValueError("Q80 scale overflowed f16 — values too large to pack")
    qv, s, nd = pack_planes_np(q, sc)
    return Q8Tensor(jnp.asarray(qv), jnp.asarray(s.view(np.uint16)), nd)


def repack_file_bytes_into(raw: np.ndarray, d: int, n: int,
                           qv2: np.ndarray, sc2: np.ndarray, col: int = 0) -> None:
    """One (d, n) tensor's `.m` Q80 bytes → preallocated runtime planes
    (``qv2`` int8 (padded_n, ld), ``sc2`` f16 (padded_n/32, ld)) at output
    column ``col`` — a pure byte transpose (BlockQ80, quants.hpp:22-25);
    native single pass (csrc q80_repack) when built, numpy otherwise."""
    from ..native import have_native_q80, q80_repack_into

    nb = n // 32
    if have_native_q80():
        q80_repack_into(raw, d, n, qv2, sc2, col)
        return
    blocks = np.asarray(raw, np.uint8).reshape(d, nb, quants.Q80_BLOCK_BYTES)
    sc2[:nb, col:col + d] = (
        np.ascontiguousarray(blocks[:, :, :2]).view(np.float16).reshape(d, nb).T)
    vals = np.ascontiguousarray(blocks[:, :, 2:]).view(np.int8)  # (d, nb, 32)
    qv2[:nb * 32, col:col + d] = np.moveaxis(vals, 0, 2).reshape(nb * 32, d)


def pack_file_groups(groups: list[list[tuple[np.ndarray, int, int]]],
                     stacked: bool = True) -> Q8Tensor:
    """Layer-stacked Q8Tensor straight from `.m` file bytes (the Q80 twin
    of q40.pack_file_groups; same fused-group and inf/NaN-scale rules, and
    like it the leaves stay host numpy arrays until ``place_params``)."""
    n = groups[0][0][2]
    d_total = sum(g[1] for g in groups[0])
    L = len(groups)
    np_ = padded_n(n)
    qv = np.zeros((L, np_, d_total), np.int8)
    sc = np.zeros((L, np_ // 32, d_total), np.float16)
    for l, group in enumerate(groups):
        col = 0
        for raw, d, gn in group:
            if gn != n:
                raise ValueError(f"fused group mixes input dims {gn} != {n}")
            repack_file_bytes_into(raw, d, n, qv[l], sc[l], col)
            col += d
    if not np.isfinite(sc).all():
        raise ValueError(
            "Q80 scale plane contains inf/NaN f16 scales — corrupt or "
            "overflowed .m tensor (delta exceeded f16 range at conversion)")
    scu = sc.view(np.uint16)
    if not stacked:
        if L != 1:
            raise ValueError("stacked=False needs exactly one group")
        return Q8Tensor(qv[0], scu[0], (n, d_total))
    return Q8Tensor(qv, scu, (n, d_total))


# ---------------------------------------------------------------------------
# Dequantize (XLA path — also the numerics oracle for the kernel)
# ---------------------------------------------------------------------------

def dequantize(qt: Q8Tensor, dtype=jnp.bfloat16) -> jax.Array:
    """Padded planes → dense logical (..., n, d); one bf16 round of v·s,
    matching the kernel and the file codec."""
    qv, s = qt.qpacked, qt.scales
    *lead, np_, d = qv.shape
    n, _ = qt.logical_nd
    s32 = _f16_bits_to_f32(s)
    v = qv.astype(jnp.float32).reshape(*lead, np_ // 32, 32, d)
    w = (v * s32[..., :, None, :]).astype(dtype).reshape(*lead, np_, d)
    return w[..., :n, :]


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _q8_kernel(x_ref, qv_ref, s_ref, o_ref, acc_ref, *, nsteps):
    i = pl.program_id(1)
    vi = qv_ref[:]                                  # (tn, td) int8
    sc = s_ref[:]
    if vi.ndim == 3:                                # stacked: (1, tn, td) block
        vi, sc = vi[0], sc[0]
    tn, td = vi.shape
    nb = tn // 32
    s32 = _f16_bits_to_f32(sc)                      # (nb, td)
    # int8 → f32 via int32 (no direct small-int→float casts in Mosaic),
    # per-block scale, one bf16 round — the file codec's dequant exactly
    v32 = vi.astype(jnp.int32).astype(jnp.float32).reshape(nb, 32, td)
    w = (v32 * s32[:, None, :]).astype(jnp.bfloat16).reshape(tn, td)
    part = jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = part

    @pl.when(i > 0)
    def _():
        acc_ref[:] = acc_ref[:] + part

    @pl.when(i == nsteps - 1)
    def _():
        o_ref[:] = acc_ref[:]


def _stacked_q8_kernel(lidx_ref, x_ref, qv_ref, s_ref, o_ref, acc_ref, *, nsteps):
    del lidx_ref  # consumed by the index_maps
    _q8_kernel(x_ref, qv_ref, s_ref, o_ref, acc_ref, nsteps=nsteps)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_matmul(x: jax.Array, qv: jax.Array, s: jax.Array,
                   interpret: bool = False) -> jax.Array:
    t, n = x.shape
    d = qv.shape[-1]
    tile_n, tile_d = _tiles(n, d)
    grid = (pl.cdiv(d, tile_d), n // tile_n)
    return pl.pallas_call(
        functools.partial(_q8_kernel, nsteps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, tile_n), lambda j, i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, tile_d), lambda j, i: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n // 32, tile_d), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((t, tile_d), lambda j, i: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, tile_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="q8_mm",
    )(x.astype(jnp.bfloat16), qv, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_matmul_stacked(x: jax.Array, qv: jax.Array, s: jax.Array,
                           layer: jax.Array, interpret: bool = False) -> jax.Array:
    """Layer-indexed Q80 matmul over stacked planes (scalar-prefetch index
    into the (L, n, d) HBM buffer — see q40._pallas_matmul_stacked)."""
    t, n = x.shape
    d = qv.shape[-1]
    tile_n, tile_d = _tiles(n, d)
    grid = (pl.cdiv(d, tile_d), n // tile_n)
    return pl.pallas_call(
        functools.partial(_stacked_q8_kernel, nsteps=grid[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((t, tile_n), lambda j, i, l: (0, i)),
                pl.BlockSpec((1, tile_n, tile_d), lambda j, i, l: (l[0], i, j)),
                pl.BlockSpec((1, tile_n // 32, tile_d), lambda j, i, l: (l[0], i, j)),
            ],
            out_specs=pl.BlockSpec((t, tile_d), lambda j, i, l: (0, j)),
            scratch_shapes=[pltpu.VMEM((t, tile_d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="q8_mm_stacked",
    )(layer.reshape(1).astype(jnp.int32), x.astype(jnp.bfloat16), qv, s)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def matmul(x: jax.Array, qt: Q8Tensor | QLayerView, impl: str = "auto",
           out_dtype=None, kind: str | None = None) -> jax.Array:
    """``x @ dequantize(qt)`` with f32 accumulation (Q80 weights).

    Single-device: fused Pallas kernel.  On a multi-device
    mesh or off-TPU: the GSPMD-partitionable XLA emulation (see module
    docstring) — ``kind`` is accepted for call-site symmetry with q40.mm
    but only the XLA path runs there, so it is unused.
    """
    del kind  # only the auto-sharded XLA path runs on meshes
    n, d = qt.logical_nd
    lead = x.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    out_dtype = out_dtype or x.dtype
    is_view = isinstance(qt, QLayerView)

    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        np_ = (qt.qt if is_view else qt).qpacked.shape[-2]
        # static rule only (q40._auto_pallas rationale): a lowering or
        # runtime failure of the chosen kernel raises
        impl = "pallas" if (on_tpu and rows <= PALLAS_MAX_ROWS
                            and _smap_mesh() is None
                            and _tile_n_legal(
                                np_, _tiles(np_, d)[0])) \
            else "xla"

    from ..obs import dispatch as obs_dispatch
    if impl in ("pallas", "pallas_interpret") and _smap_mesh() is None:
        interp = impl == "pallas_interpret"
        obs_dispatch.record_dispatch("q8", "pallas-fused", rows=rows,
                                     layout="row-major")
        if is_view:
            qv3, s3 = qt.flat_planes()
            np_ = qv3.shape[-2]
            x2 = _pad_x(x.reshape(rows, n), n, np_)
            out = _pallas_matmul_stacked(x2, qv3, s3, qt.layer, interpret=interp)
        else:
            np_ = qt.qpacked.shape[-2]
            x2 = _pad_x(x.reshape(rows, n), n, np_)
            out = _pallas_matmul(x2, qt.qpacked, qt.scales, interpret=interp)
        return out.reshape(*lead, d).astype(out_dtype)

    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown q8 matmul impl {impl!r} "
                         "(expected auto | xla | pallas | pallas_interpret)")
    if impl != "xla" and _smap_mesh() is not None:
        # Q80 has no shard_map kernel path: a forced-pallas request on a
        # mesh degrades to the GSPMD XLA emulation (see module docstring)
        obs_dispatch.record_degrade(
            "q8", "mesh_xla", warn_key=qt.logical_nd,
            shape=qt.logical_nd, impl=impl)
    # XLA path (meshes, CPU, prefill row counts)
    obs_dispatch.record_dispatch("q8", "xla-dequant", rows=rows)
    base = qt.sliced() if is_view else qt
    w = dequantize(base, dtype=jnp.bfloat16)
    return jnp.dot(x.astype(jnp.bfloat16), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)
