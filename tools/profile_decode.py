"""Diagnostic: per-op device-time breakdown of a decode step on real HW.

Drives the same zero-weight Q40 decode chunk the bench times (bench.py),
traces it with ``jax.profiler``, and prints the top HLO ops by total device
time plus the compute/collective split — the recorded-fact bottleneck
analysis VERDICT r02 asked for (the reference's analogous attribution is
its per-task-type wall accounting, utils.cpp:189-192).

Usage: python tools/profile_decode.py [model] [--top N]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", nargs="?", default="llama2-7b")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--chunk", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _model_cfg, _zero_q40_params
    from dllama_tpu.models.transformer import init_kv_cache
    from dllama_tpu.runtime.decode_loop import decode_chunk

    print(f"backend: {jax.default_backend()} {jax.devices()}", file=sys.stderr)
    impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    cfg = _model_cfg(args.model).with_(quant_impl=impl)
    params = _zero_q40_params(cfg)
    cache = init_kv_cache(cfg, batch=1)
    chunk = args.chunk

    fn = jax.jit(
        lambda p, c, tok, pos, k: decode_chunk(
            p, cfg, c, tok, pos, k, steps=chunk, temperature=0.0, topp=0.9),
        donate_argnums=(1,))
    tok = jnp.zeros((1,), jnp.int32)
    key = jax.random.PRNGKey(0)

    t0 = time.perf_counter()
    toks, cache, tok, _, _ = fn(params, cache, tok, jnp.int32(0), key)
    np.asarray(toks)
    print(f"compile+warmup {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    t0 = time.perf_counter()
    toks, cache, tok, _, _ = fn(params, cache, tok, jnp.int32(chunk), key)
    np.asarray(toks)
    wall_ms = (time.perf_counter() - t0) * 1000
    print(f"untraced chunk: {wall_ms:.1f} ms = {wall_ms / chunk:.2f} ms/token "
          f"({1000 * chunk / wall_ms:.1f} tok/s)")

    from dllama_tpu.runtime.profiling import traced_op_times

    state = {"cache": cache, "tok": tok, "pos": 2 * chunk}

    def traced_step():
        toks, state["cache"], state["tok"], _, _ = fn(
            params, state["cache"], state["tok"], jnp.int32(state["pos"]), key)
        state["pos"] += chunk
        np.asarray(toks)

    times = traced_op_times(traced_step, steps=1)
    if times is None:
        print("no xplane tooling/trace available", file=sys.stderr)
        return

    total = sum(times.values())
    print(f"\ndevice op time: {total:.1f} ms over {chunk} steps "
          f"= {total / chunk:.2f} ms/token")
    print(f"{'ms':>9}  {'%':>5}  op")
    for name, ms in sorted(times.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"{ms:9.2f}  {100 * ms / total:5.1f}  {name}")


if __name__ == "__main__":
    main()
