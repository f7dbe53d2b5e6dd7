"""Hardware sweep of the contiguous-cache attention: KV block width of the
live walk at prefill rows.

Times ``ops.attention`` on one chip at Mistral-7B's attention shapes (32
query and 8 KV heads of 128, 32 layers, a 32k bf16 cache) for a few
``(t, pos)`` points.  Every measurement happens *inside one jitted
``fori_loop``* over the layer index with the blocks sliced from the stacked
cache, exactly like the prefill program runs it.  ``auto`` is the program's
own choice through ``gqa_attention_at`` (so the tool also runs on a tree
without the walk: ``--repo <parent checkout>``); the widths go through
``live_gqa_attention``'s ``block=``.  The program's width is
``attention._kv_chunk(s)`` for every ``t`` (PERF.md §6, PR 29 has the table
this tool gave); judge a candidate in the cell, not here.

Usage: python tools/sweep_attn.py [--repo DIR] [--blocks 256,512,1024]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HQ, HKV, DH, LAYERS, S = 32, 8, 128, 32, 32768
POINTS = [(256, 0), (128, 0), (64, 0), (256, 16384), (16, 300), (1, 300)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=HERE)
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="control flow only, on any backend, at 2 layers")
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.repo))
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops import attention as att

    if not a.rehearse and jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    layers = 2 if a.rehearse else LAYERS
    widths = [None] + ([int(b) for b in a.blocks.split(",") if b]
                       if hasattr(att, "live_gqa_attention") else [])
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (layers, 1, HKV, S, DH)
    ck = jax.random.normal(kk, shape, jnp.bfloat16)
    cv = jax.random.normal(kv, shape, jnp.bfloat16)
    results = []
    for t, pos in POINTS:
        q = jax.random.normal(kq, (1, HQ, t, DH), jnp.bfloat16)
        for block in widths:
            # the caches are arguments: closed over, 4 GB of them would be
            # baked into the program as constants
            def one(layer, q_, pos_, ck_, cv_):
                if block is None:
                    return att.gqa_attention_at(q_, ck_, cv_, layer, pos_, t)
                return att.live_gqa_attention(q_, ck_, cv_, pos_, layer=layer,
                                              block=block)

            @jax.jit
            def run(q_, pos_, ck_, cv_):
                # each layer's queries depend on the last layer's output,
                # as in the model: nothing is hoisted or run side by side
                return jax.lax.fori_loop(
                    0, layers, lambda i, acc: one(
                        i, q_ + (acc * 0).astype(q_.dtype), pos_, ck_, cv_)
                    .astype(jnp.float32), jnp.zeros(q_.shape, jnp.float32))

            p = jnp.int32(pos)
            jax.block_until_ready(run(q, p, ck, cv))
            times = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, p, ck, cv))
                times.append(time.perf_counter() - t0)
            rec = {"t": t, "pos": pos, "block": block or "auto",
                   "ms_32_layers": round(1e3 * sorted(times)[len(times) // 2], 3),
                   "min_ms": round(1e3 * min(times), 3), "repo": a.repo}
            results.append(rec)
            print(json.dumps(rec), flush=True)
    out = os.path.join(HERE, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sweep_attn.jsonl"), "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)


if __name__ == "__main__":
    main()
