#!/usr/bin/env python3
"""Record the small scoped xplane kept in ``benchmarks/fixtures/`` (run on the
chip, by hand): one program with two named scopes (``w13`` around a Pallas
kernel named ``q40_mm``, ``attn`` around an XLA fusion), launched three times
under ``sched.enqueue`` / ``sched.land_wait`` annotations with arguments, a
20 ms sleep inside a ``sched.idle`` annotation after each launch and one 10 ms
sleep under no annotation at the end.  Writes the trace and what the host saw
(``expected.json``) to ``--out``."""

import argparse
import glob
import json
import os
import shutil
import sys
import time

LAUNCHES, IDLE_S, BARE_S = 3, 0.02, 0.01


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.profiler import TraceAnnotation

    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def step(x):
        with jax.named_scope("w13"):
            y = pl.pallas_call(
                double_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=jax.default_backend() != "tpu", name="q40_mm")(x)
        with jax.named_scope("attn"):
            return jnp.tanh(y @ y).sum()

    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready(step(x))
    os.makedirs(a.out, exist_ok=True)
    tmp = os.path.join(a.out, "trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    t0 = time.time()
    for seq in range(LAUNCHES):
        with TraceAnnotation("sched.enqueue", seq=seq, rows=2, rids="a;b"):
            r = step(x)
        with TraceAnnotation("sched.land_wait", seq=seq):
            jax.block_until_ready(r)
        with TraceAnnotation("sched.idle", timeout=IDLE_S):
            time.sleep(IDLE_S)
    time.sleep(BARE_S)
    jax.block_until_ready(step(x))  # closes the bare gap inside the devices' span
    wall = time.time() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    name = "scoped-1chip.xplane.pb"
    shutil.copy(src, os.path.join(a.out, name))
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(a.out, "scoped-1chip.expected.json"), "w") as f:
        json.dump({"chips": 1, "launches": LAUNCHES + 1, "annotated": LAUNCHES,
                   "sleeps_s": IDLE_S, "bare_s": BARE_S, "host_wall_s": wall,
                   "kind": jax.devices()[0].device_kind,
                   "bytes": os.path.getsize(os.path.join(a.out, name))}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
