"""Hardware sweep of the fused Q40 kernel: tile pairs, and rows x row block.

Times the kernel on Mistral-7B's five matmul shapes on one chip.  Every
measurement happens *inside one jitted ``lax.scan``* cycling the layer
index, exactly like the decode loop runs the kernel: a host-side dispatch
loop measures host dispatch latency, not kernel time.  Tile pairs go through
the kernels' ``tiles=`` keyword and row blocks through ``row_block=``, so one
process times them all; the program's own choices are ``q40._tiles`` and
``q40._row_block``.

Usage: python tools/sweep_q40.py --tiles [head,w13]  # tile pairs at one row
       python tools/sweep_q40.py --rows [head,w13]   # rows x row block
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Mistral-7B's five matmuls: (name, n_in, d_out, stacked)
SHAPES = [("qkv", 4096, 6144, True), ("wo", 4096, 4096, True),
          ("w13", 4096, 28672, True), ("w2", 14336, 4096, True),
          ("head", 4096, 32768, False)]
LAYERS = 32
# (tile_n, tile_d); the first is the program's own (q40.TILE_N, q40.TILE_D).
# A (tn/2, td) tile of a row-major (n/2, d) plane is td contiguous bytes per
# row, so td sets the HBM burst length.  tile_n below 256 is illegal (the
# scales block needs tn/32 >= 8 sublanes).
TILE_CONFIGS = [(1024, 1024), (512, 2048), (256, 4096), (512, 4096),
                (256, 2048), (1024, 2048), (512, 1024)]
# (rows, row block): None is the code's own choice (one block of every row
# up to 128, q40._row_block above), "xla" the dequantize-then-dot path
ROWS_CONFIGS = [(64, None), (128, None), (64, 64), (128, 128), (256, 256),
                (256, "xla"), (512, 256), (512, 512), (1024, 256),
                (1024, 512), (1024, 1024), (2048, None)]


def _sweep(configs, only: set | None, reps: int, layers: int, out_name: str):
    """Time each shape under each config inside a jitted scan over the layer
    index, as the model runs it.  A config is ``(tag, rows, kw)``: ``tag``
    names it in the record, ``kw`` holds the kernel's keywords (None: the
    dequantize-then-dot XLA path).
    One JSON line per measurement; all of them to ``chiprun_out/<out_name>``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    from dllama_tpu.ops import q40

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    rng = np.random.RandomState(0)
    results = []
    for name, n, d, stacked in SHAPES:
        if only and name not in only:
            continue
        L = layers if stacked else 1
        qp = jnp.asarray(rng.randint(0, 256, (L, n // 2, d), dtype=np.uint8))
        sc = jnp.asarray((rng.rand(L, n // 32, d).astype(np.float16)
                          * 0.01).view(np.uint16))
        for tag, rows, kw in configs:
            x = jnp.asarray(rng.randn(rows, n).astype(np.float32), jnp.bfloat16)

            def one(x, qp, sc, i):
                if not stacked:
                    # no layer index to vary: vary x, or XLA hoists the one
                    # call out of the scan
                    x = x + (i % 2).astype(x.dtype)
                if kw is None:
                    w = q40.QLayerView(q40.QTensor(qp, sc, (n, d)), i % L)
                    return q40.matmul(x, w, impl="xla", out_dtype=jnp.float32)
                if stacked:
                    return q40._pallas_matmul_stacked(x, qp, sc, i % L, **kw)
                return q40._pallas_matmul(x, qp[0], sc[0], **kw)

            @jax.jit
            def run(x, qp, sc):
                def body(acc, i):
                    o = one(x, qp, sc, i)
                    # a kernel is opaque and runs whole whatever is read of
                    # it; XLA would push a slice into its dot, so read all
                    return acc + (o if kw is None else o[:8, :128]).sum(), None
                return jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))[0]

            rec = {"shape": name, "n": n, "d": d, "rows": rows, **tag}
            try:
                float(run(x, qp, sc))  # compile + warm-up
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    float(run(x, qp, sc))
                    best = min(best, (time.perf_counter() - t0) * 1000 / reps)
                nbytes = (n // 2) * d + (n // 32) * d * 2  # packed + scales
                rec.update(ms=round(best, 4),
                           GBps=round(nbytes / best / 1e6, 1),
                           tflops=round(2 * rows * n * d / best / 1e9, 1),
                           us_per_row=round(best * 1000 / rows, 3))
            except Exception as e:  # noqa: BLE001 — a form Mosaic refuses is a result
                rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(rec), flush=True)
            results.append(rec)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", out_name), "w") as f:
        json.dump(results, f, indent=1)
    return results


def measure_tiles(only: set | None = None, reps: int = 32) -> list[dict]:
    """Tile pairs at one row (the decode shape), through ``tiles=``; per tile
    pair, the five matmuls summed into a decode token's matmul time."""
    results = _sweep([({"tiles": list(t)}, 1, {"tiles": t})
                      for t in TILE_CONFIGS],
                     only, reps, LAYERS, "sweep_tiles.json")
    if not only:
        for t in TILE_CONFIGS:
            ms = [r.get("ms") for r in results if r["tiles"] == list(t)]
            if None not in ms:  # SHAPES order: four a layer, then the head
                print(json.dumps({"tiles": list(t), "matmul_ms_per_token":
                                  round(sum(ms[:-1]) * LAYERS + ms[-1], 3)}))
    return results


def measure_rows(only: set | None = None, reps: int = 16,
                 layers: int = 4) -> list[dict]:
    """Rows x row block, through ``row_block=``, against the XLA path."""
    return _sweep([({"block": block}, rows,
                    None if block == "xla" else {"row_block": block})
                   for rows, block in ROWS_CONFIGS],
                  only, reps, layers, "sweep_rows.json")


def main():
    modes = {"--tiles": measure_tiles, "--rows": measure_rows}
    if len(sys.argv) < 2 or sys.argv[1] not in modes:
        sys.exit(__doc__)
    modes[sys.argv[1]](set(sys.argv[2].split(",")) if len(sys.argv) > 2
                       else None)


if __name__ == "__main__":
    main()
