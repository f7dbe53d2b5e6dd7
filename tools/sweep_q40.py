"""Hardware sweep of the fused Q40 kernel: variants × tile sizes.

Times the layer-stacked kernel (the decode hot path) on the llama2-7B
matmul shapes for each (variant, tile_n, tile_d) configuration — each in a
fresh subprocess because TILE_N governs the packed storage layout — and
prints effective HBM bandwidth + a projected decode ms/token so the
winning config can be made the default with evidence (VERDICT r02 Next #2).

Measurement happens *inside one jitted ``lax.scan``* cycling the layer
index, exactly like the decode loop runs the kernel: a host-side dispatch
loop (the first version of this tool) measures host dispatch latency,
not kernel time — same-config repeat runs varied ±30% where the scan
timing is stable to a few percent and matches the xplane per-op numbers.

Usage: python tools/sweep_q40.py            # sweep and rank
       python tools/sweep_q40.py --one folded 1024 2048   # single config
       python tools/sweep_q40.py --rows [head,w13]  # rows x row block, Mistral-7B shapes
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def shapes():
    """Representative llama2-7B matmuls (stacked over 32 layers), as
    (name, n_in, d_out, stacked_layers): wo is the narrow-output extreme
    (632 GB/s in the r3 xplane), w13 the wide-output extreme (354 GB/s),
    wqkv in between — enough to rank configs while keeping per-config
    compile time inside the subprocess timeout.
    Projections scale w13's rate onto w2 (similar width class) and wqkv's
    onto wcls."""
    return [
        ("wqkv", 4096, 12288, 32),
        ("wo", 4096, 4096, 32),
        ("w13", 4096, 22016, 32),
    ]

# (variant, tile_n, tile_d).  Wide tile_d configs probe DMA contiguity:
# a (tn/2, td) tile of a row-major (n/2, d) plane is td contiguous bytes
# per row, so td sets the HBM burst length (w13's d=22016 at td=1024 is
# 1 KB bursts on a 22 KB stride).  tile_n below 256 is illegal (the
# scales block spec needs tn/32 ≥ 8 sublanes).
CONFIGS = [
    ("classic", 1024, 1024), ("fma", 1024, 1024), ("folded", 1024, 1024),
    # exact is Mosaic-legal by construction since the r04 transposed-
    # operand rework (q40.py _q40_kernel) — measure it on hardware
    ("exact", 1024, 1024),
    ("classic", 512, 2048), ("folded", 512, 2048), ("exact", 512, 2048),
    # tile-contiguous layout probe (one sequential DMA per grid step; a
    # wide-shape win here graduates the layout into the pack path)
    ("blocked", 1024, 1024), ("blocked", 512, 2048),
    ("classic", 256, 4096), ("folded", 256, 4096),
    ("classic", 512, 4096),
    ("classic", 256, 2048),
    ("classic", 1024, 2048),
    ("classic", 512, 1024),
]


def blocked_stacked_matmul(x, qp_blk, sc_blk, layer, tn, td, dp,
                           interpret=False):
    """Layer-indexed fused matmul over TILE-CONTIGUOUS packed storage —
    thin wrapper over the production kernel (ops/q40.py
    _pallas_matmul_blocked / BlockedQTensor, docs/PERF.md lever #1b); the
    probe and the deployed path are the same code by construction."""
    from dllama_tpu.ops import q40
    del tn, td, dp  # implied by the blocked plane shapes
    return q40._pallas_matmul_blocked(x, qp_blk, sc_blk, layer,
                                      interpret=interpret)


def block_pack(qp, sc, tn, td):
    """Re-block row-major packed planes (L, n2, d) / (L, nb, d) into the
    tile-contiguous layout (production transform: q40.to_blocked).
    Returns host numpy arrays + the padded width dp."""
    import numpy as np

    from dllama_tpu.ops import q40

    bqt = q40.to_blocked(
        q40.QTensor(qp, sc, (qp.shape[1] * 2, qp.shape[2])), tn, td)
    return (np.asarray(bqt.qpacked), np.asarray(bqt.scales),
            bqt.qpacked.shape[2] * bqt.tiles[1])  # to_blocked may clamp td


def measure_one(variant: str, reps: int = 32, only: set | None = None) -> dict:
    """Time the stacked kernel on the 7B shapes (or the ``only`` subset —
    a single-shape run is one compile)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, HERE)
    from dllama_tpu.ops import q40

    if jax.default_backend() == "cpu":
        print(json.dumps({"error": "no TPU"}))
        return {}
    rng = np.random.RandomState(0)
    out = {"variant": variant, "tile_n": q40.TILE_N, "tile_d": q40.TILE_D,
           "shapes": {}}
    total_ms = 0.0
    total_bytes = 0
    for name, n, d, L in shapes():
        if only and name not in only:
            continue
        nb = n // 32
        x = jnp.asarray(rng.randn(1, n).astype(np.float32), jnp.bfloat16)
        tn, td = q40.TILE_N, q40.TILE_D
        if variant == "blocked":
            # tile-contiguous layout probe: bytes are bytes, so random
            # blocked planes time identically to a real repack
            dp = -(-d // td) * td
            qp = jnp.asarray(rng.randint(
                0, 256, (L, (n // 2) // (tn // 2), dp // td, tn // 2, td),
                dtype=np.uint8))
            sc = jnp.asarray(rng.randint(
                0, 2 ** 14, (L, nb // (tn // 32), dp // td, tn // 32, td),
                dtype=np.uint16))
        else:
            qp = jnp.asarray(rng.randint(0, 256, (L, n // 2, d), dtype=np.uint8))
            sc = jnp.asarray((rng.rand(L, nb, d).astype(np.float16) * 0.01).view(np.uint16))

        # one compiled scan = `reps` serialized kernel calls cycling the
        # layer index (scalar-prefetch path), exactly like decode's layer
        # scan; the accumulator consumes each output so none is dead code
        @jax.jit
        def run(x, qp, sc):
            def body(acc, i):
                if variant == "blocked":
                    o = blocked_stacked_matmul(x, qp, sc, i % L, tn, td, dp)
                else:
                    o = q40._pallas_matmul_stacked(x, qp, sc, i % L,
                                                   variant=variant)
                return acc + o.sum(), None
            return jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))[0]

        float(run(x, qp, sc))  # compile + warmup (float() fetches: blocks)
        t0 = time.perf_counter()
        float(run(x, qp, sc))
        ms = (time.perf_counter() - t0) * 1000 / reps
        d_eff = dp if variant == "blocked" else d  # blocked pads d to td
        nbytes = (n // 2) * d_eff + nb * d_eff * 2  # packed + f16-bit scales per layer
        gbps = nbytes / ms / 1e6
        out["shapes"][name] = {"ms": round(ms, 4), "GBps": round(gbps, 1)}
        total_ms += ms * L
        total_bytes += nbytes * L
    if not only:
        # unmeasured 7B shapes, projected at a measured peer's rate; the
        # rate class tracks *output width d* (= DMA row stride,
        # docs/PERF.md): w2 (d=4096) matches wo's class, wcls (d=32000)
        # extrapolates wqkv/w13's
        per_w = 0.5 + 2 / 32  # packed + f16-bit scale bytes per weight
        for nbytes, peer in ((int(11264 * 4096 * per_w) * 32, "wo"),
                             (int(4096 * 32000 * per_w), "w13")):
            gbps = out["shapes"][peer]["GBps"]
            total_ms += nbytes / gbps / 1e6
            total_bytes += nbytes
        out["proj_matmul_ms_per_token"] = round(total_ms, 3)
        out["proj_matmul_GBps"] = round(total_bytes / total_ms / 1e6, 1)
    print(json.dumps(out))
    return out


# Mistral-7B's five matmuls: (name, n_in, d_out, stacked)
ROWS_SHAPES = [("qkv", 4096, 6144, True), ("wo", 4096, 4096, True),
               ("w13", 4096, 28672, True), ("w2", 14336, 4096, True),
               ("head", 4096, 32768, False)]
# (rows, row block): None is the code's own choice (one block of every row
# up to 128, q40._row_block above), "xla" the dequantize-then-dot path
ROWS_CONFIGS = [(64, None), (128, None), (64, 64), (128, 128), (256, 256),
                (256, "xla"), (512, 256), (512, 512), (1024, 256),
                (1024, 512), (1024, 1024), (2048, None)]


def measure_rows(only: set | None = None, reps: int = 16,
                 layers: int = 4) -> list[dict]:
    """Time the fused kernel by row count and row block on one chip, inside
    a jitted scan over the layer index as the model runs it.  One JSON line
    per (shape, rows, row block); all of them to chiprun_out/sweep_rows.json."""
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
    for name, n, d, stacked in ROWS_SHAPES:
        if only and name not in only:
            continue
        L = layers if stacked else 1
        qp = jnp.asarray(rng.randint(0, 256, (L, n // 2, d), dtype=np.uint8))
        sc = jnp.asarray((rng.rand(L, n // 32, d).astype(np.float16)
                          * 0.01).view(np.uint16))
        qt = q40.QTensor(qp, sc, (n, d))
        for rows, block in ROWS_CONFIGS:
            x = jnp.asarray(rng.randn(rows, n).astype(np.float32), jnp.bfloat16)

            def one(x, qp, sc, i, block=block):
                if not stacked:
                    # no layer index to vary: vary x, or XLA hoists the one
                    # call out of the scan
                    x = x + (i % 2).astype(x.dtype)
                if block == "xla":
                    w = q40.QLayerView(q40.QTensor(qp, sc, (n, d)), i % L)
                    return q40.matmul(x, w, impl="xla", out_dtype=jnp.float32)
                if stacked:
                    return q40._pallas_matmul_stacked(x, qp, sc, i % L,
                                                      row_block=block)
                return q40._pallas_matmul(x, qp[0], sc[0], row_block=block)

            @jax.jit
            def run(x, qp, sc):
                def body(acc, i):
                    o = one(x, qp, sc, i)
                    # a kernel is opaque and runs whole whatever is read of
                    # it; XLA would push a slice into its dot, so read all
                    return acc + (o if block == "xla" else o[:8, :128]).sum(), None
                return jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))[0]

            rec = {"shape": name, "n": n, "d": d, "rows": rows, "block": block}
            try:
                float(run(x, qt.qpacked, qt.scales))  # compile + warm-up
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    float(run(x, qt.qpacked, qt.scales))
                    best = min(best, (time.perf_counter() - t0) * 1000 / reps)
                rec.update(ms=round(best, 4),
                           tflops=round(2 * rows * n * d / best / 1e9, 1),
                           us_per_row=round(best * 1000 / rows, 3))
            except Exception as e:  # noqa: BLE001 — a form Mosaic refuses is a result
                rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(rec), flush=True)
            results.append(rec)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "sweep_rows.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--rows":
        measure_rows(set(sys.argv[2].split(",")) if len(sys.argv) > 2 else None)
        return
    # a deployed width-rule table would silently override the tiles under
    # test (every swept config would measure the rule's tiles and the sweep
    # could never contradict the current rules) — the sweep measures the
    # explicit DLLAMA_Q40_TILE_N/TILE_D ladder only
    os.environ.pop("DLLAMA_Q40_TILES_JSON", None)
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        argv = sys.argv[2:]
        only = None
        if "--shapes" in argv:
            i = argv.index("--shapes")
            only = set(argv[i + 1].split(","))
            argv = argv[:i] + argv[i + 2:]
        if len(argv) > 2:
            # tiles must be in the env before the q40 import inside
            # measure_one reads them
            os.environ["DLLAMA_Q40_TILE_N"] = argv[1]
            os.environ["DLLAMA_Q40_TILE_D"] = argv[2]
        measure_one(argv[0], only=only)
        return
    results = []
    for variant, tn, td in CONFIGS:
        env = dict(os.environ)
        env["DLLAMA_Q40_TILE_N"] = str(tn)
        env["DLLAMA_Q40_TILE_D"] = str(td)
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", variant],
                stdout=subprocess.PIPE, env=env, cwd=HERE, timeout=420)
        except subprocess.TimeoutExpired:
            print(f"{variant} tn={tn} td={td}: TIMEOUT", file=sys.stderr)
            continue
        if r.returncode != 0:
            print(f"{variant} tn={tn} td={td}: rc={r.returncode}", file=sys.stderr)
            continue
        try:
            out = json.loads(r.stdout.decode().strip().splitlines()[-1])
        except Exception:
            print(f"{variant} tn={tn} td={td}: unparseable", file=sys.stderr)
            continue
        if "error" in out:
            print(f"{variant} tn={tn} td={td}: {out['error']}", file=sys.stderr)
            continue
        results.append(out)
        print(f"{variant:8s} tn={tn:<5d} td={td:<5d} "
              f"matmuls {out['proj_matmul_ms_per_token']:7.2f} ms/tok "
              f"@ {out['proj_matmul_GBps']:6.1f} GB/s", file=sys.stderr)
    results.sort(key=lambda r: r["proj_matmul_ms_per_token"])
    print("\n=== ranked ===", file=sys.stderr)
    for r in results[:6]:
        print(f"{r['variant']:8s} tn={r['tile_n']:<5d} td={r['tile_d']:<5d} "
              f"{r['proj_matmul_ms_per_token']:7.2f} ms/tok "
              f"{r['proj_matmul_GBps']:6.1f} GB/s", file=sys.stderr)


if __name__ == "__main__":
    main()
