"""Hardware sweep of the fused Q40 kernel: tile pairs, and rows x row block.

Times the kernel on one chip at the matmul shapes of the benchmark's
configurations (Mistral-7B, OLMoE-1B-7B, DeepSeek-V2, a tp=4 shard of
Yi-34B; under ``--body`` also K-EXAONE's, LFM2's, Brumby's, Ouro's and
Falcon-H1's): the flat and stacked
forms, and the experts form (``q40_mm_experts``) at each model's expert
count.  Every measurement happens *inside one jitted ``lax.scan``* cycling
the layer index, exactly like the decode loop runs the kernel: a host-side
dispatch loop measures host dispatch latency, not kernel time.  Tile pairs
go through the kernels' ``tiles=`` keyword and row blocks through
``row_block=``, so one process times them all; the program's own choices are
``q40._tiles`` (marked ``"rule": true`` in the records) and
``q40._row_block``.

``--body`` times the kernel at the rule's tiles and the code's own row
block, 1 to 512 rows, Mistral's matmuls and the experts form of OLMoE,
DeepSeek-V2 and one chip's share of K-EXAONE.  Run on two checkouts it
compares two bodies, as PR 41 did (one dot against the activation as the
caller holds it, for two against its nibble halves): at 64 repetitions a
one-row figure repeated to 1-2%, at the 128 it takes to 0.5%; an op that XLA
keeps inside the scan is timed with the launch (PERF.md §6, PR 41).

``--body`` takes a fourth argument, the bodies to time (default ``rule``).
``rule`` is the kernel as the program runs it (``q40._body``: one row
contracts the raw nibbles a quantization block at a time, 2 to
``q40.SLICED_MAX_ROWS`` rows a 128-row slice of the tile at a time by the same
algebra (PR 62), more rows the dequantized tile in one dot; since PR 58 the
few-row operand is made of the packed tile's 32-bit words,
``q40._nibbles_as``).  ``dot`` is the dot at every row count, which at one row
is the body every program ran up to PR 48 and at 2 to 16 rows up to PR 60
(≈5.5 VPU ops a weight).  ``sliced`` is the sliced body at 2 to 32 rows
whatever the constant says: ``--body <shapes> 2,4,8,16,32 sliced,dot`` is the
table that set it (the shapes of the eight served cells are in BODY_SHAPES;
PERF.md §6, PR 62: −24 to −32% a launch at 2 to 8 rows, −14 to −24% at 16,
+0.2 to +3.6% at 32), and ``sliced-from-1`` the same from ONE row (the block
padded to eight: what the one-row launch would pay for sharing the body).
``half-dot`` is the dot body with the dot over HALF of the tile
(the lo nibble planes; the hi planes are unpacked as ever and kept alive by a
float32 sum, ≈0.75 op a weight more) and ``dot-twice`` is it with the same VPU
work and the dot issued TWICE: they answered *do the MXU's 128 x 128 tile loads
bound the body at few rows?* (no: PERF.md §6, PR 50).  ``vpu`` is PR 50's
one-row algebra up to 4 rows with the inner sums on the VPU (no dot), which PR
49 would have shipped.  (``grouped``, PR 50's body with a block-diagonal left
operand of ``tile_n / 32`` rows a row at 2 to 4 rows, left the tool in PR 62:
the sliced body is its few-row form, −26 to −29% where it read −12% to +22%.)
The one-row operand's other forms (PERF.md §6, PR 58's
Step 0; ops a weight on the tile, and the kernel's final VLIW bundles a 1024 x
1024 tile compiled for the v5e): ``nibbles`` is PR 50's (extend, mask or shift,
int -> f32 -> bf16: ≈3.5-4, 1846 bundles); ``bytes`` the ROADMAP's first form
(extend, ``0x41804180 | (b & 0xF) << 3 | (b & 0xF0) << 15``: ≈3, 1583);
``rule`` the shipped words (bitcast to uint32, four times shift / and / or:
1.5, plus the relayout Mosaic puts in front of the bitcast, 1196);
``words128`` the words with ``0x4300 | v`` = 128 + v (1.375, 1151; its sums 17
times the nibbles' read 7e-7 of the reference where 16 + v reads 9e-8);
``touch`` no body at all (the scales decoded, eight rows looked at, 301): what
a launch costs when the pipeline's DMA and its grid steps are all there is, at
one row and at the sliced body's rows.
All of them are patched into the loaded module for the run (``q40._body``,
``q40._contract_dot``, ``q40._contract_grouped``, ``q40._contract_sliced``,
``q40._nibbles_as``, ``q40._words_bf16``); the program has no switch for them.

``--check`` runs the rule's body at ``--body``'s shapes on the chip and prints
its largest difference from ``x @ dequantize(qt, float32)`` as a share of the
largest output: the few-row bodies read 1e-7, the dot body the bf16 round of a
weight, 1.5e-3 (ROADMAP D17).

``--chosen`` times a decoded row's routed experts at SmallThinker's shapes
(6 of 64) and LFM2's (4 of 64): one launch over the chosen planes
(``q40_mm_chosen``) against one launch each of ``q40_mm_stacked``, which is
what ``moe_ffn`` ran at one row
before and still runs on a mesh.  An iteration of the scan carries ops of
its own (the index vector, the slice and sum that keep the result alive; for
the loop also six index slices and a stack): 27 us read here where the
cell's trace reads 19-21 a launch, 67 where it reads 6 x 4.3
(PERF.md §6, PR 39).  The order of the two forms is the tool's to say, a
launch's time the trace's.

``--grouped`` times one layer's routed experts as ``moe_ffn`` runs them (router,
the experts' three launches, the weighted sum; scope ``moe`` without a shared
expert) at the prompt and mixed-step rows of the five expert configurations:
``all-experts`` (``q40_mm_experts`` over every expert, then a masked sum) against
``grouped`` (``models/grouping.py``: the pairs sorted by expert into blocks of
``tr`` rows, ``q40_mm_grouped`` over the blocks that hold rows) at ``tr`` 16 to
128 (``rule`` marks the one ``grouping.block_rows`` takes).  The router's logits are handed in, so the routing
is the tool's: ``uniform`` (k experts a row drawn evenly) and, with ``--routing
DIR``, ``seeded`` (the cell's own: ``tools/experts_hit.py --dump DIR`` wrote what
each layer of the benchmark's seeded weights chose for a prompt).  ``launch``
records time the gate matrix's launch alone.  PERF.md section 6, PR 53.

Usage: python tools/sweep_q40.py --tiles [ds_gate,yi_wo]  # tile pairs at 1, 16, 256 rows
       python tools/sweep_q40.py --grouped [lfm2,olmoe [--routing chiprun_out/routing]]
       python tools/sweep_q40.py --rows [head,w13]        # rows x row block
       python tools/sweep_q40.py --body [w2,ds_down [16,32,64 [rule,dot,dot-twice,vpu]]]  # the body at the rule's tiles, 1 to 512 rows or the rows given
       python tools/sweep_q40.py --body w13,w2,yi_kv,st_down 1 rule,nibbles,bytes,words128,touch  # the one-row operand's forms
       python tools/sweep_q40.py --body kx_gate,kx_down,w13,fh_w2 2,8,16,32 sliced,dot  # the table behind q40.SLICED_MAX_ROWS
       python tools/sweep_q40.py --chosen [st_gate]       # one launch a row's experts, or one each
       python tools/sweep_q40.py --check [kx_gate,w13 [2,16,33]]  # the rule's body against the float32 reference
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from typing import NamedTuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Shape(NamedTuple):
    name: str
    n: int                  # input dim as stored (a tp shard's local rows)
    d: int
    layers: int             # 0: a flat 2-D weight (the head)
    experts: int = 0        # > 0: the experts form, this many a layer
    x_per_expert: bool = False   # one activation block an expert (down)
    chosen: int = 0         # the experts a decoded row reads (--chosen, --body)
    tiles: tuple = ()       # (tile_n, tile_d) pairs to time beside the rule's


# Mistral-7B's five matmuls: the shapes of --rows, and the first of --tiles
MISTRAL = [Shape("qkv", 4096, 6144, 32), Shape("wo", 4096, 4096, 32),
           Shape("w13", 4096, 28672, 32), Shape("w2", 14336, 4096, 32),
           Shape("head", 4096, 32768, 0)]
LAYERS = 32
# PR 28's pairs, kept for Mistral.  A (tn/2, td) tile of a row-major (n/2, d)
# plane is td contiguous bytes per row, so td sets the HBM burst length.  A
# partial-axis tile_n below 256 is illegal (q40._tile_n_legal).
WIDE = ((512, 2048), (1024, 2048))
SHAPES = [s._replace(tiles=WIDE) for s in MISTRAL] + [
    # OLMoE-1B-7B: 64 experts of 1024, hidden 2048
    Shape("olmoe_gate", 2048, 1024, 4, 64, tiles=((2048, 512),)),
    Shape("olmoe_down", 1024, 2048, 4, 64, True, tiles=((1024, 512),)),
    Shape("olmoe_head", 2048, 50304, 0),
    # DeepSeek-V2: 160 experts of 1536, hidden 5120; the fused down-projections
    # from x (1536 + 576), q's up-projection.  *_pad: what the 1024 ladder of
    # PRs 28-33 ran (down and wq_b stored with 2048 input columns)
    Shape("ds_gate", 5120, 1536, 2, 160,
          tiles=((1024, 1024), (1024, 768), (2560, 384), (512, 1536))),
    Shape("ds_down", 1536, 5120, 2, 160, True,
          tiles=((768, 1024), (768, 1280), (1536, 512), (512, 1024), (256, 1024))),
    Shape("ds_down_pad", 2048, 5120, 2, 160, True),
    Shape("ds_wqkv_a", 5120, 2112, 4, tiles=((1024, 1024), (1024, 768))),
    Shape("ds_wq_b", 1536, 24576, 4, tiles=((768, 1024), (1536, 512))),
    Shape("ds_wq_b_pad", 2048, 24576, 4),
    # Yi-34B a tp=4 shard: q and k / v (row), wo (col: 7168 / 4 = 7 x 256
    # rows), and Mistral's w2 at tp=4 (3584 = 7 x 512, no cell)
    Shape("yi_q", 7168, 1792, 8, tiles=((1024, 1024), (1792, 512))),
    Shape("yi_kv", 7168, 256, 8, tiles=((1024, 256), (3584, 256))),
    Shape("yi_wo", 1792, 7168, 8, tiles=((256, 1024), (1792, 256), (256, 4096))),
    Shape("yi_w13", 7168, 5120, 8),
    Shape("yi_w2", 5120, 7168, 8),
    Shape("w2_tp4", 3584, 4096, 8, tiles=((512, 1024), (3584, 256))),
]
# A decoded row's chosen experts (--chosen, --body): SmallThinker-21B-A3B, 64
# experts of 768, hidden 2560, 6 a row; LFM2-24B-A2B, 64 of 1536, hidden 2048, 4
CHOSEN_SHAPES = [Shape("st_gate", 2560, 768, 4, 64, chosen=6),
                 Shape("st_down", 768, 2560, 4, 64, True, chosen=6),
                 Shape("lfm2_gate", 2048, 1536, 4, 64, chosen=4),
                 Shape("lfm2_down", 1536, 2048, 4, 64, True, chosen=4)]
TILE_ROWS = (1, 16, 256)
# K-EXAONE-236B-A23B, one chip's share: 16 held experts of 2048, hidden 6144
BODY_SHAPES = MISTRAL + [s for s in SHAPES if s.name.startswith("yi_")
                         or s.experts and "pad" not in s.name] + [
    Shape("kx_gate", 6144, 2048, 2, 16), Shape("kx_down", 2048, 6144, 2, 16, True),
    # LFM2-24B-A2B's short-convolution projections (hidden 2048; in: B, C, x)
    Shape("lfm2_conv_in", 2048, 6144, 4), Shape("lfm2_conv_out", 2048, 2048, 4),
    # the served cells' other matmuls (PR 62: the table that set
    # q40.SLICED_MAX_ROWS).  K-EXAONE's dense shapes (16 rows a step): q | k | v,
    # wo, the shared expert, the leading dense layer, the head's share
    Shape("kx_qkv", 6144, 10240, 4), Shape("kx_wo", 8192, 6144, 4),
    Shape("kx_shared13", 6144, 4096, 4), Shape("kx_w13", 6144, 36864, 2),
    Shape("kx_w2", 18432, 6144, 2), Shape("kx_head", 6144, 19200, 0),
    # LFM2's 64 experts, all of them walked by a 16-row step
    Shape("lfm2_gate_all", 2048, 1536, 2, 64),
    Shape("lfm2_down_all", 1536, 2048, 2, 64, True),
    # Brumby-14B (8 rows a step)
    Shape("br_qkv", 5120, 7168, 4), Shape("br_wo", 5120, 5120, 4),
    Shape("br_w13", 5120, 34816, 2), Shape("br_w2", 17408, 5120, 2),
    Shape("br_head", 5120, 151936, 0),
    # Ouro-2.6B (8 rows); 5632 is stored as 6144 (q40.padded_n)
    Shape("ouro_qkv", 2048, 6144, 4), Shape("ouro_wo", 2048, 2048, 4),
    Shape("ouro_w13", 2048, 11264, 4), Shape("ouro_w2", 6144, 2048, 4),
    Shape("ouro_head", 2048, 49152, 0),
    # Falcon-H1-34B (32 rows): the mixer's in-projection, the MLP, the head
    Shape("fh_in", 5120, 9216, 4), Shape("fh_w13", 5120, 43008, 2),
    Shape("fh_w2", 21504, 5120, 2), Shape("fh_head", 5120, 261120, 0)]
BODY_ROWS = (1, 16, 128, 256, 512)
# (rows, row block): None is the code's own choice (one block of every row
# up to 128, q40._row_block above), "xla" the dequantize-then-dot path
ROWS_CONFIGS = [(64, None), (128, None), (64, 64), (128, 128), (256, 256),
                (256, "xla"), (512, 256), (512, 512), (1024, 256),
                (1024, 512), (1024, 1024), (2048, None)]


def _q40():
    sys.path.insert(0, HERE)
    from dllama_tpu.ops import q40
    return q40


def _sweep(shapes, configs_of, only: set | None, reps: int, out_name: str):
    """Time each shape under each of ``configs_of(shape)`` inside a jitted
    scan over the layer index, as the model runs it.  A config is ``(tag,
    rows, kw)``: ``tag`` names it in the record, ``kw`` holds the kernel's
    keywords (None: the dequantize-then-dot XLA path).  A ``tag`` with a
    ``form`` reads the shape's ``chosen`` traced planes of the layer's
    experts: ``chosen`` in one launch, ``stacked-loop`` in one launch each.
    One JSON line per measurement; all of them to ``chiprun_out/<out_name>``."""
    import jax
    import jax.numpy as jnp

    q40 = _q40()
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    key = jax.random.key(0)
    results = []
    for sh in shapes:
        if only and sh.name not in only:
            continue
        n, d, E = sh.n, sh.d, sh.experts
        L = max(sh.layers, 1)
        # one plane's random bits, repeated: what a call reads lies at its own
        # addresses, and the generator's 32-bit counters for a whole stack
        # (0.8 G elements at 160 experts) do not fit in HBM
        planes = L * max(E, 1)
        qp = jnp.tile(jax.random.bits(key, (1, n // 2, d), jnp.uint8),
                      (planes, 1, 1))
        sc = jnp.tile(jax.lax.bitcast_convert_type(
            jax.random.uniform(key, (1, n // 32, d), jnp.float16) * 0.01,
            jnp.uint16), (planes, 1, 1))
        for tag, rows, kw in configs_of(sh):
            form = tag.get("form")
            read = sh.chosen if form else max(E, 1)  # planes a call reads
            xshape = ((read,) if sh.x_per_expert else ()) + (rows, n)
            x = jax.random.normal(key, xshape, jnp.bfloat16)

            def one(x, qp, sc, i):
                if form:
                    picks = (i + 11 * jnp.arange(sh.chosen)) % E  # distinct, traced
                    if form == "chosen":
                        return q40._pallas_matmul_experts(
                            x, qp, sc, i % L, experts=E, chosen=picks, **kw)
                    return jnp.stack([q40._pallas_matmul_stacked(
                        x[j] if sh.x_per_expert else x, qp, sc,
                        (i % L) * E + picks[j], **kw) for j in range(sh.chosen)])
                if not sh.layers:
                    # no layer index to vary: vary x, or XLA hoists the one
                    # call out of the scan
                    x = x + (i % 2).astype(x.dtype)
                if kw is None:
                    w = q40.QLayerView(q40.QTensor(qp, sc, (n, d)), i % L)
                    return q40.matmul(x, w, impl="xla", out_dtype=jnp.float32)
                if E:
                    return q40._pallas_matmul_experts(x, qp, sc, i % L,
                                                      experts=E, **kw)
                if sh.layers:
                    return q40._pallas_matmul_stacked(x, qp, sc, i % L, **kw)
                return q40._pallas_matmul(x, qp[0], sc[0], **kw)

            @jax.jit
            def run(x, qp, sc):
                def body(acc, i):
                    o = one(x, qp, sc, i)
                    # a kernel is opaque and runs whole whatever is read of
                    # it; XLA would push a slice into its dot, so read all
                    return acc + (o if kw is None else o[..., :8, :128]).sum(), None
                return jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))[0]

            rec = {"shape": sh.name, "n": n, "d": d, "rows": rows, **tag}
            if E:
                rec["experts"] = E
            try:
                float(run(x, qp, sc))  # compile + warm-up
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    float(run(x, qp, sc))
                    best = min(best, (time.perf_counter() - t0) * 1000 / reps)
                # packed + scales, of every plane a call reads
                nbytes = read * ((n // 2) * d + (n // 32) * d * 2)
                rec.update(ms=round(best, 4),
                           GBps=round(nbytes / best / 1e6, 1),
                           tflops=round(2 * read * rows * n * d / best / 1e9, 1),
                           us_per_row=round(best * 1000 / rows, 3))
            except Exception as e:  # noqa: BLE001 — a form Mosaic refuses is a result
                rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(rec), flush=True)
            results.append(rec)
            # a loaded executable keeps its temporaries (0.8 GB at 160 experts
            # x 256 rows): without this the sweep runs out of HBM
            del run
            jax.clear_caches()
        del qp, sc, x
        stats = jax.devices()[0].memory_stats() or {}
        print(f"{sh.name}: {stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use",
              file=sys.stderr)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", out_name), "w") as f:
        json.dump(results, f, indent=1)
    return results


def measure_tiles(only: set | None = None, reps: int = 32) -> list[dict]:
    """Tile pairs at 1, 16 and 256 rows (the decode shape, the served
    pure-decode step, the mixed step), through ``tiles=``: the rule's own pair
    first, then the shape's candidates.  For Mistral, per tile pair, the five
    matmuls at one row summed into a decode token's matmul time."""
    q40 = _q40()

    def configs_of(sh):
        rule = q40._tiles(sh.n, sh.d)
        pairs = [rule] + [t for t in sh.tiles if t != rule]
        return [({"tiles": list(t), "rule": t == rule}, rows, {"tiles": t})
                for rows in TILE_ROWS for t in pairs]

    results = _sweep(SHAPES, configs_of, only, reps, "sweep_tiles.json")
    if not only:
        names = [s.name for s in MISTRAL]
        for t in [q40._tiles(4096, 4096), *WIDE]:
            ms = [r.get("ms") for r in results if r["tiles"] == list(t)
                  and r["rows"] == 1 and r["shape"] in names]
            if None not in ms:  # MISTRAL order: four a layer, then the head
                print(json.dumps({"tiles": list(t), "matmul_ms_per_token":
                                  round(sum(ms[:-1]) * LAYERS + ms[-1], 3)}))
    return results


def measure_rows(only: set | None = None, reps: int = 16,
                 layers: int = 4) -> list[dict]:
    """Rows x row block, through ``row_block=``, against the XLA path."""
    configs = [({"block": block}, rows,
                None if block == "xla" else {"row_block": block})
               for rows, block in ROWS_CONFIGS]
    return _sweep([s._replace(layers=min(s.layers, layers)) for s in MISTRAL],
                  lambda sh: configs, only, reps, "sweep_rows.json")


def _half_dot(x_ref, vi, s32):
    """The dot body with the dot over the lo nibble planes alone."""
    import jax.numpy as jnp

    lo, hi = _q40()._dequant_bf16(vi, s32)  # both planes unpacked, as ever
    nb, _, td = lo.shape
    part = jnp.dot(x_ref[:, :16 * nb], lo.reshape(16 * nb, td),
                   preferred_element_type=jnp.float32)
    # the hi plane stays alive in a sum nothing can equal: adds 0
    alive = hi.astype(jnp.float32).reshape(2 * nb, 8, td).sum(axis=0)
    return part + jnp.where(alive[:1] == 1.2345e30, 1.0, 0.0)


def _dot_twice(x_ref, vi, s32):
    """The dot body with its dot issued twice on the same unpacked tile."""
    import jax.numpy as jnp

    nb, td = s32.shape
    lo, hi = _q40()._dequant_bf16(vi, s32)
    w = jnp.concatenate([lo, hi], axis=1).reshape(32 * nb, td)
    x = x_ref[:]
    again = (x.astype(jnp.float32) * 0.5).astype(jnp.bfloat16)
    return (jnp.dot(x, w, preferred_element_type=jnp.float32)
            + jnp.dot(again, w, preferred_element_type=jnp.float32))


def _contract_vpu(x_ref, qp, s32):
    """The grouped algebra with the inner sums on the VPU (the form not
    shipped): ``x_lo * lo + x_hi * hi == x_lo * byte + (x_hi - 16 * x_lo) *
    hi``, so a weight costs half an unpack, half a shift, one conversion, one
    multiply and one add on float32, and the activation row comes onto the
    sublanes by one transpose.  ``q40._partial_rows`` sublanes a row, like the
    shipped body."""
    import jax.numpy as jnp

    nb, td = s32.shape
    vi = qp.astype(jnp.int32)
    lanes = lambda v: jnp.concatenate([v] * (td // 128), axis=-1)  # noqa: E731
    byte = vi.astype(jnp.float32).reshape(nb, 16, td)
    hi = (vi >> 4).astype(jnp.float32).reshape(nb, 16, td)
    parts = []
    for r in range(x_ref.shape[0]):
        row = x_ref[r:r + 1, :].astype(jnp.float32)
        xb = jnp.broadcast_to(row, (128, row.shape[-1])).T.reshape(nb, 32, 128)
        xlo, xhi = xb[:, :16], xb[:, 16:]
        p = byte * lanes(xlo) + hi * lanes(xhi - 16.0 * xlo)
        bias = 8.0 * xb.reshape(nb, 4, 8, 128).sum(axis=1)
        part = ((p[:, :8] + p[:, 8:] - lanes(bias)) * s32[:, None, :]).sum(axis=0)
        parts.append(part if _q40()._partial_rows(32 * nb) == 8
                     else part.sum(axis=0, keepdims=True))
    return jnp.concatenate(parts, axis=0)


def _words128_bf16(qp):
    """``q40._words_bf16`` with ``0x4300 | v`` = 128 + v in each half: no shift
    for the first pair, 11 ops for 8 weights, sums 17 times the nibbles'."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    tn, td = 2 * qp.shape[0], qp.shape[1]
    w = pltpu.bitcast(qp, jnp.uint32)
    pieces = [pltpu.bitcast(
        ((w >> at if at else w) & jnp.uint32(0x000F000F)) | jnp.uint32(0x43004300),
        jnp.bfloat16).reshape(tn // 64, 16, td) for at in (0, 4, 8, 12)]
    return jnp.concatenate(pieces, axis=1).reshape(tn, td), 136.0


def _bytes(x_ref, qp, s32):
    """Form B (ROADMAP S2(3)(iii) as first written): the tile extended to
    int32 and ``0x41804180 | (b & 0xF) << 3 | (b & 0xF0) << 15``, one word of
    two bf16 ``16 + v`` a byte in five integer ops and no conversion; row ``2
    p + h`` of the operand holds nibble ``h`` of packed row ``p``."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    b = qp.astype(jnp.int32)
    w = pltpu.bitcast(((b & 0xF) << 3) | ((b & 0xF0) << 15) | 0x41804180,
                      jnp.bfloat16)
    return _q40()._block_sums(
        x_ref, w, 24.0,
        (lambda j: (j & ~31) | ((j & 1) << 4) | ((j >> 1) & 15), lambda j: j >> 5), s32)


def _touch(x_ref, qp, s32):
    """No body at all: the scales decoded, eight rows of the tile looked at.
    What a launch costs when the pipeline's DMA and its steps are all there
    is: the floor of every body at the rule's tiles."""
    import jax.numpy as jnp

    k = _q40()._partial_rows(32 * s32.shape[0])
    return s32[:k] + qp[:32].astype(jnp.int32).astype(jnp.float32).sum(axis=0, keepdims=True)


def _touch_rows(x_ref, qp, s32):
    """:func:`_touch` for the sliced body's block of rows."""
    import jax.numpy as jnp

    return jnp.broadcast_to(_touch(x_ref, qp, s32)[:1], (x_ref.shape[0], qp.shape[1]))


def _few_rows(rows: int, tile_n: int) -> str:
    return "grouped" if rows <= 4 else "dot"


def _sliced_from_1(rows: int, tile_n: int) -> str:
    """The sliced body from ONE row (its block padded to eight: what the
    one-row launch would pay if :func:`q40._contract_sliced` served it) to 32
    whatever ``q40.SLICED_MAX_ROWS`` says."""
    return "sliced" if rows <= 32 and tile_n % 128 == 0 else "dot"


def _sliced_to_32(rows: int, tile_n: int) -> str:
    """``q40._body`` with the sliced body's edge at 32 rows: the table that
    set ``q40.SLICED_MAX_ROWS``, re-run."""
    return "grouped" if rows == 1 else _sliced_from_1(rows, tile_n)


def _always_dot(rows: int, tile_n: int) -> str:
    return "dot"


# what each of --body's bodies patches into the loaded q40 module
BODIES = {
    "rule": {},
    "dot": dict(_body=_always_dot),
    "sliced": dict(_body=_sliced_to_32),
    "sliced-from-1": dict(_body=_sliced_from_1),
    "half-dot": dict(_body=_always_dot, _contract_dot=_half_dot),
    "dot-twice": dict(_body=_always_dot, _contract_dot=_dot_twice),
    "vpu": dict(_body=_few_rows, _contract_grouped=_contract_vpu),
    "nibbles": dict(_nibbles_as=lambda tile_n: "nibbles"),
    "words128": dict(_words_bf16=_words128_bf16),
    "bytes": dict(_contract_grouped=_bytes),
    "touch": dict(_contract_grouped=_touch, _contract_sliced=_touch_rows),
}


@contextlib.contextmanager
def _body_as(name: str):
    """While entered, the loaded kernel runs body ``name`` of BODIES (see the
    module docstring); ``rule`` changes nothing."""
    import jax

    if name not in BODIES:
        sys.exit(f"unknown body {name!r}: one of {', '.join(BODIES)}")
    q40 = _q40()
    saved = {k: getattr(q40, k) for k in BODIES[name]}
    for k, v in BODIES[name].items():
        setattr(q40, k, v)
    jax.clear_caches()
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(q40, k, v)
        jax.clear_caches()


def measure_body(only: set | None = None, reps: int = 128,
                 rows: tuple = BODY_ROWS, bodies: tuple = ("rule",)) -> list[dict]:
    """The kernel at the rule's tiles and the code's own row block: Mistral's
    five matmuls and the experts form of the three expert models at 1, 16,
    128, 256 and 512 rows (or at ``rows``: the packed mixed step runs 64, PR
    42), and a row's chosen experts at SmallThinker's and LFM2's shapes up to
    4 rows, under each of ``bodies``.  Run on two checkouts, ``rule`` compares two
    bodies."""
    results = []
    for name in bodies:
        tag = {"body": name}
        with _body_as(name):
            results += _sweep(
                [s._replace(layers=min(s.layers, 4)) for s in BODY_SHAPES],
                lambda sh: [(tag, r, {}) for r in rows],
                only, reps, f"sweep_body.{name}.json")
            few = [r for r in rows if r <= 4]
            if few and (only is None or only & {s.name for s in CHOSEN_SHAPES}):
                results += _sweep(
                    CHOSEN_SHAPES,
                    lambda sh: [({**tag, "form": "chosen", "chosen": sh.chosen}, r, {})
                                for r in few],
                    only, reps, f"sweep_body.{name}.chosen.json")
    return results


class Geometry(NamedTuple):
    name: str               # the configuration it is the expert layer of
    dim: int
    width: int              # one expert's
    experts: int            # the router's outputs
    k: int
    rows: tuple             # a prompt's bucket or chunk, a packed mixed step
    held: int = 0           # planes on this chip, where fewer than ``experts``


GROUPED = [Geometry("lfm2-24b-a2b", 2048, 1536, 64, 4, (256, 128)),
           Geometry("smallthinker-21b-a3b", 2560, 768, 64, 6, (512,)),
           Geometry("olmoe-1b-7b", 2048, 1024, 64, 8, (64, 256)),
           Geometry("deepseek-v2", 5120, 1536, 160, 6, (64, 256)),
           Geometry("k-exaone-236b-a23b", 6144, 2048, 128, 8, (256,), held=16)]
GROUPED_TR = (16, 32, 64, 128)
_IMPL = "pallas"            # a rehearsal off the chip sets "pallas_interpret"


def _routing_logits(idx, experts: int):
    """Router logits under which a plain softmax top-k chooses ``idx`` ``(...,
    rows, k)``, in its order."""
    import numpy as np
    k = idx.shape[-1]
    logits = np.zeros(idx.shape[:-1] + (experts,), np.float32)
    np.put_along_axis(logits, idx, 10.0 - np.arange(k, dtype=np.float32), axis=-1)
    return logits


def measure_grouped(only: set | None = None, reps: int = 32,
                    routing: str | None = None) -> list[dict]:
    """One layer's routed experts under both many-row strategies (module
    docstring).  A record: ``form`` (``all-experts`` | ``grouped``), ``what``
    (``layer``: router to weighted sum; ``launch``: the gate launch alone),
    ``routing``, ``tr``, ``blocks`` (static), ``blocks_used`` and ``fill_pct``
    (mean over the layers timed), ``ms``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    q40 = _q40()
    from dllama_tpu.io import mfile
    from dllama_tpu.models import grouping
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import tiny_config
    if _IMPL == "pallas" and jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    key = jax.random.key(0)
    rule = grouping.block_rows
    L = 2
    results = []
    for geo in GROUPED:
        if only and geo.name not in only and geo.name.split("-")[0] not in only:
            continue
        E, held = geo.experts, geo.held or geo.experts
        cfg = tiny_config(arch=mfile.ARCH_OLMOE, dim=geo.dim, hidden_dim=geo.width,
                          n_experts=E, n_active_experts=geo.k, dtype=jnp.bfloat16,
                          experts_held=geo.held, first_expert=geo.held,
                          quant_impl=_IMPL)

        def stack(n, d):
            qp = jnp.tile(jax.random.bits(key, (1, 1, n // 2, d), jnp.uint8),
                          (L, held, 1, 1))
            sc = jnp.tile(jax.lax.bitcast_convert_type(
                jax.random.uniform(key, (1, 1, n // 32, d), jnp.float16) * 0.01,
                jnp.uint16), (L, held, 1, 1))
            return q40.QTensor(qp, sc, (n, d))

        stacks = {"gate": stack(geo.dim, geo.width), "up": stack(geo.dim, geo.width),
                  "down": stack(geo.width, geo.dim)}
        seeded = None
        path = routing and os.path.join(routing, geo.name + ".npy")
        if path and os.path.exists(path):
            seeded = np.load(path)                      # (layers, positions, k)
        for rows in geo.rows:
            rng = np.random.default_rng(rows)
            routings = {"uniform": np.stack([
                np.stack([rng.permutation(E)[:geo.k] for _ in range(rows)])
                for _ in range(L)])}
            if seeded is not None and seeded.shape[1] >= rows:
                routings["seeded"] = seeded[:, :rows]
            x = jax.random.normal(key, (rows, geo.dim), jnp.bfloat16)
            for want in (None, *GROUPED_TR):
                for rname, idx in routings.items():
                    if want is None and rname != "uniform":
                        continue  # all-experts does not read the routing
                    logits = jnp.asarray(_routing_logits(idx, E))
                    own = (idx >= cfg.first_expert) & (idx < cfg.first_expert + held)
                    loc = np.where(own, idx - cfg.first_expert, held)
                    used = np.mean([sum(-(-c // want) for c in np.bincount(
                        l.ravel(), minlength=held + 1)[:held]) for l in loc]) if want else 0
                    for what in ("layer", "launch"):
                        if what == "launch" and rname != "uniform":
                            continue
                        rec = {"config": geo.name, "rows": rows, "k": geo.k,
                               "held": held, "what": what, "routing": rname,
                               "form": "grouped" if want else "all-experts"}
                        if want:
                            m = grouping.blocks(rows, geo.k, held, want)
                            rec.update(tr=want, rule=want == rule(rows, geo.k, E), blocks=m,
                                       blocks_used=float(used),
                                       fill_pct=round(100 * own.sum() / len(idx)
                                                      / (used * want), 1))

                        def one(x, logits, qts, i):
                            lp = {n: q40.QLayerView(qt, i % L) for n, qt in qts.items()}
                            lg = jax.lax.dynamic_index_in_dim(
                                logits, i % logits.shape[0], keepdims=False)
                            if what == "layer":
                                return tf.moe_ffn(x, lp, cfg, lg)
                            if not want:
                                return q40.matmul_experts(x, lp["gate"], held, _IMPL)
                            top = jax.lax.top_k(lg, geo.k)[1] - cfg.first_expert
                            here = (top >= 0) & (top < held)
                            gp = grouping.plan(jnp.where(here, top, 0), held, want, here)
                            xg = x[gp.gather].reshape(m, want, geo.dim)
                            return q40.matmul_experts(xg, lp["gate"], held, _IMPL,
                                                      chosen=gp.planes, used=gp.used)

                        @jax.jit
                        def run(x, logits, qts):
                            def body(acc, i):
                                o = one(x + acc.astype(x.dtype) * 0, logits, qts, i)
                                return acc + o.astype(jnp.float32).sum() * 1e-9, None
                            return jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))[0]

                        grouping.block_rows = lambda *a, _w=want: _w
                        try:
                            float(run(x, logits, stacks))
                            best = float("inf")
                            for _ in range(3):
                                t0 = time.perf_counter()
                                float(run(x, logits, stacks))
                                best = min(best, (time.perf_counter() - t0) * 1000 / reps)
                            rec["ms"] = round(best, 4)
                        except Exception as e:  # noqa: BLE001 — a refusal is a result
                            rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                        finally:
                            grouping.block_rows = rule
                        print(json.dumps(rec), flush=True)
                        results.append(rec)
                        del run
                        jax.clear_caches()
        del stacks
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "sweep_grouped.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


def check_values(only: set | None = None, rows: tuple = (2, 8, 16, 32)) -> list[dict]:
    """The kernel's values on the chip at ``--body``'s shapes, one layer of
    each, as the rule runs it: the largest difference from ``x @
    dequantize(qt, float32)`` (summed in float64 on the host: a float32
    product on the chip, at the highest precision, is itself 1e-7 to 3e-7
    away) as a share of the largest output.  The bodies that round no weight (one row,
    and 2 to ``q40.SLICED_MAX_ROWS``) read 1e-7; the dot body 1.5e-3, the bf16
    round of a weight (ROADMAP D17)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    q40 = _q40()
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    key = jax.random.key(1)
    results = []
    for sh in BODY_SHAPES:
        if only and sh.name not in only:
            continue
        n, d, E = sh.n, sh.d, min(sh.experts, 4)
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, n + d), 3)
        qp = jax.random.bits(k1, (max(E, 1), n // 2, d), jnp.uint8)
        sc = jax.lax.bitcast_convert_type(
            (0.004 + 0.008 * jax.random.uniform(k2, (max(E, 1), n // 32, d))
             ).astype(jnp.float16), jnp.uint16)
        dense = np.asarray(q40.dequantize(q40.QTensor(qp, sc, (n, d))), np.float64)
        for r in rows:
            x = jax.random.normal(
                k3, ((E,) if sh.x_per_expert else ()) + (r, n), jnp.bfloat16)
            if E:
                got = q40._pallas_matmul_experts(x, qp, sc, jnp.int32(0), experts=E)
            else:
                got = q40._pallas_matmul_stacked(x, qp, sc, jnp.int32(0))[None]
            ref = np.einsum("...rn,end->erd" if not sh.x_per_expert else "ern,end->erd",
                            np.asarray(x, np.float64), dense)
            rec = {"shape": sh.name, "n": n, "d": d, "rows": r,
                   "body": q40._body(r, q40._tiles(n, d)[0]),
                   "rel_err": float(np.abs(np.asarray(got, np.float64) - ref).max()
                                    / np.abs(ref).max())}
            print(json.dumps(rec), flush=True)
            results.append(rec)
        del qp, sc, dense
    return results


def measure_chosen(only: set | None = None, reps: int = 256) -> list[dict]:
    """One decoded row's chosen routed experts: one launch over their planes
    against one launch each, at the rule's tiles (``ms`` is all of them)."""
    return _sweep(CHOSEN_SHAPES,
                  lambda sh: [({"form": form, "chosen": sh.chosen}, 1, {})
                              for form in ("stacked-loop", "chosen")],
                  only, reps, "sweep_chosen.json")


def main():
    modes = {"--tiles": measure_tiles, "--rows": measure_rows,
             "--body": measure_body, "--chosen": measure_chosen,
             "--grouped": measure_grouped, "--check": check_values}
    if len(sys.argv) < 2 or sys.argv[1] not in modes:
        sys.exit(__doc__)
    kw = {}
    if "--routing" in sys.argv:
        at = sys.argv.index("--routing")
        kw["routing"] = sys.argv[at + 1]
        del sys.argv[at:at + 2]
    if sys.argv[1] in ("--body", "--check") and len(sys.argv) > 3:
        kw["rows"] = tuple(int(r) for r in sys.argv[3].split(","))
    if sys.argv[1] == "--body" and len(sys.argv) > 4:
        kw["bodies"] = tuple(sys.argv[4].split(","))
    modes[sys.argv[1]](set(sys.argv[2].split(",")) if len(sys.argv) > 2
                       else None, **kw)


if __name__ == "__main__":
    main()
