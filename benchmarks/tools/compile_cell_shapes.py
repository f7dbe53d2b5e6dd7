#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide, as a scratch script: compile a
configuration's per-chip Q40 matmuls at tensor-parallel degree ``--tp`` for a
*described* v5e:2x2 (no chip attached), through the program's own sharded
dispatch (``ops/q40.py _sharded_matmul``: per-shard Pallas kernel, and for
column-sharded weights the reduce that follows it).

A compile, not a run: it says that Mosaic and the partitioner accept the
shapes and what each chip must hold, and nothing about results or times.
``JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_cell_shapes.py
--config benchmarks/configs/yi-34b.json --tp 4``
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--rows", type=int, default=1)
    a = ap.parse_args()
    with open(a.config) as f:
        cfg = json.load(f)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from dllama_tpu.ops import q40

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:a.tp]).reshape(1, 1, 1, a.tp),
                ("dp", "sp", "ep", "tp"))
    jax.default_backend = lambda: "tpu"  # the program asks; this script answers
    dim, hid, voc = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["head_dim"] * cfg["num_key_value_heads"]
    # (name, n_in, d_out, kind, stacked over layers)
    mats = [("wq", dim, dim, "row", True), ("wk/wv", dim, kv, "row", True),
            ("wo", dim, dim, "col", True), ("w1/w3", dim, hid, "row", True),
            ("w2", hid, dim, "col", True), ("wcls", dim, voc, "row", False)]
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    ok = True
    for name, n, d, kind, stacked in mats:
        np_ = q40.padded_n(n)
        lead = (2,) if stacked else ()
        if kind == "row":
            wspec = P(None, None, "tp") if stacked else P(None, "tp")
            xspec = P(None, None)
        else:
            wspec = P(None, "tp", None) if stacked else P("tp", None)
            xspec = P(None, "tp")
        x = jax.ShapeDtypeStruct((a.rows, np_), jnp.bfloat16, sharding=sh(xspec))
        qp = jax.ShapeDtypeStruct((*lead, np_ // 2, d), jnp.uint8, sharding=sh(wspec))
        sc = jax.ShapeDtypeStruct((*lead, np_ // 32, d), jnp.uint16, sharding=sh(wspec))
        args = [x, qp, sc]
        if stacked:
            args.append(jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P())))

        def f(x, qp, sc, *layer, kind=kind):
            return q40._sharded_matmul(x, qp, sc, layer[0] if layer else None,
                                       kind, mesh, False)

        t0 = time.time()
        try:
            compiled = jax.jit(f).lower(*args).compile()
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            print(json.dumps({
                "matrix": name, "n_in": n, "d_out": d, "kind": kind, "tp": a.tp,
                "compiled": True, "pallas_kernel": "tpu_custom_call" in text,
                "xla_all_reduce": "all-reduce" in text,
                "argument_bytes_per_chip": mem.argument_size_in_bytes,
                "temp_bytes_per_chip": mem.temp_size_in_bytes,
                "seconds": round(time.time() - t0, 1)}))
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the result
            ok = False
            print(json.dumps({"matrix": name, "n_in": n, "d_out": d,
                              "kind": kind, "compiled": False,
                              "error": str(e)[:600]}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
