#!/usr/bin/env python3
"""Record the small xplane kept in ``benchmarks/fixtures/`` (run on the chip,
by hand): a few launches of one Pallas kernel and one XLA fusion with idle
gaps between them and, on several chips, one ``psum``.  Writes the trace and
what the host saw (``expected.json``) to ``--out``."""

import argparse
import glob
import json
import os
import shutil
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.sharding import PartitionSpec as P

    n = len(jax.devices())

    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def pallas_double(x):
        return pl.pallas_call(double_kernel, out_shape=jax.ShapeDtypeStruct(
            x.shape, x.dtype), interpret=jax.default_backend() != "tpu")(x)

    @jax.jit
    def fusion(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((512, 512), jnp.float32)
    steps = [pallas_double, fusion]
    if n > 1:
        mesh = jax.make_mesh((n,), ("tp",))

        @jax.jit
        def reduce_all(v):
            return jax.shard_map(lambda s: jax.lax.psum(s, "tp"), mesh=mesh,
                                 in_specs=P("tp"), out_specs=P())(v)

        v = jnp.ones((n * 8, 1024), jnp.float32)
        steps.append(lambda _x: reduce_all(v))
    for f in steps:
        jax.block_until_ready(f(x))
    os.makedirs(a.out, exist_ok=True)
    tmp = os.path.join(a.out, "trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    t0 = time.time()
    for _ in range(3):
        for f in steps:
            jax.block_until_ready(f(x))
        time.sleep(0.02)
    wall = time.time() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    name = f"tiny-{n}chip.xplane.pb"
    shutil.copy(src, os.path.join(a.out, name))
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(a.out, f"tiny-{n}chip.expected.json"), "w") as f:
        json.dump({"chips": n, "launches_of_each": 3, "sleeps_s": 0.02,
                   "host_wall_s": wall, "kind": jax.devices()[0].device_kind,
                   "bytes": os.path.getsize(os.path.join(a.out, name))}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
