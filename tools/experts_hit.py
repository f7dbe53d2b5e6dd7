"""How many distinct experts a served step hits, a layer: the number that
sizes any expert path that skips what a step does not route to.

``moe_ffn``'s many-row strategies read all E experts of a layer whatever the
router says, so their time does not depend on this count; a grouped path that
reads only the experts hit would, and uniform routing would hit
``E (1 - (1 - k/E)^rows)`` of them (56.4 of OLMoE's 64 at 16 rows).  The
seeded weights of the benchmark's OLMoE configuration are a two-state model
(PERF.md §7), so its rows may route alike: this tool counts.

The program's engine (loader, ``Engine`` and its prefill, as
``benchmarks/tools/check_logits.py`` builds them) prefills ``--rows`` seeded
sequences of ``--positions`` tokens each; ``moe_ffn`` is wrapped so that the
router's top-k (its own two lines, on the activations the engine feeds it) is
recorded per layer.  The ``--rows`` streams' rows at one position are one
step's rows: per layer and position the distinct experts among their
``rows x k`` choices are counted.  The benchmark's files are used as they
are (configuration, seeded ``.m`` file); nothing is timed.

``--dump DIR`` also writes what every layer chose for the first stream,
``DIR/<config>.npy`` ``(layers, positions, k)`` int32: the routing ``tools/
sweep_q40.py --grouped --routing DIR`` times a prompt's experts under.

Usage: python tools/experts_hit.py --config benchmarks/configs/olmoe-1b-7b.json
       [--rows 16] [--positions 64] [--dump DIR] [--cpu]   (--cpu: toy widths, control flow)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED = 32


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--positions", type=int, default=64)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--dump")
    a = ap.parse_args(argv)
    if a.positions > 512:
        raise SystemExit("--positions over 512 would prefill in several passes")
    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for p in (ROOT, BENCH, os.path.join(BENCH, "tools")):
        sys.path.insert(0, p)
    import jax
    import numpy as np

    import run as bench_run
    from harness import correct, models

    from dllama_tpu import cli
    from dllama_tpu.models import transformer as tf

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("experts_hit needs a TPU (or --cpu for the control flow)")

    chosen: dict[int, np.ndarray] = {}   # layer -> (positions, k) of the stream in flight
    moe_ffn = tf.moe_ffn

    def tapped(xb2d, lp, mcfg, *logits):
        idx, _ = tf.route(xb2d, lp, mcfg, *logits)  # the program's own choice
        jax.debug.callback(lambda layer, i: chosen.__setitem__(int(layer), np.asarray(i)),
                           lp["up"].layer, idx)
        return moe_ffn(xb2d, lp, mcfg, *logits)

    tf.moe_ffn = tapped
    try:
        engine, _ = cli.load_stack(cli.build_parser().parse_args(
            ["inference", "--model", mpath, "--tokenizer", tpath, "--workers", "tpu:1",
             "--temperature", "0", "--max-seq-len", str(max(256, a.positions))]))
        if a.positions > engine.cfg.prefill_chunk():
            raise SystemExit(f"--positions over {engine.cfg.prefill_chunk()} would "
                             "prefill in several passes")
        streams = []
        for toks in correct.check_prompts(SEED, a.rows, a.positions, shape["vocab_size"]):
            engine.reset()
            chosen.clear()
            lg, _ = engine.prefill(list(toks))
            jax.block_until_ready(lg)
            jax.effects_barrier()
            # (L, T, k): the bucket's padding rows, at the tail, are dropped
            streams.append(np.stack([chosen[l][:len(toks)] for l in sorted(chosen)]))
    finally:
        tf.moe_ffn = moe_ffn
    idx = np.stack(streams)                                  # (rows, L, T, k)
    if a.dump:
        os.makedirs(a.dump, exist_ok=True)
        np.save(os.path.join(a.dump, name + ".npy"), idx[0].astype(np.int32))
    n_exp, k = shape["n_experts"], shape["n_active_experts"]
    hit = np.array([[len(np.unique(idx[:, l, t])) for t in range(idx.shape[2])]
                    for l in range(idx.shape[1])])           # (L, T)
    out = {"config": name, "weights_seed": int(cfg["weights_seed"]), "rows": a.rows,
           "positions": a.positions, "experts": n_exp, "k": k,
           "uniform_routing_would_hit": n_exp * (1 - (1 - k / n_exp) ** a.rows),
           "distinct_experts_a_layer": {"mean": float(hit.mean()), "min": int(hit.min()),
                                        "max": int(hit.max()),
                                        "by_layer_mean": [float(v) for v in hit.mean(1)]},
           "device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind}}
    if a.cpu:
        out = {"rehearsal": True, "layers": int(hit.shape[0]), "positions": int(hit.shape[1])}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
