#!/usr/bin/env python3
"""Logits of the program's engine against the plain float32 reference for a
configuration with sliding-window layers, deep enough that a window layer's
ring has wrapped: ``python3 benchmarks/tools/check_window.py --config
benchmarks/configs/smallthinker-21b-a3b.json``.  On the chip, at the published
widths and the configuration's depth, outside any timed window.

``check_logits.py`` beside this file compares at 32 + 8 positions (its lengths
are constants), where no window binds and no ring wraps; ``run.py``'s check
prompts are 32 tokens too.  Here the engine (the program's loader, ``Engine``
and mesh through ``cli.load_stack``, as ``dllama inference`` builds them)
prefills ONE seeded prompt of ``window + chunk + TAIL`` tokens, chunk by chunk
through ``Engine.prefill`` (the window and the chunk are the engine's own:
``cfg.window``, ``cfg.prefill_chunk()``), so that chunks have crossed the
window, the ring has wrapped and the last chunk is a bucketed tail; then it
decodes ``STEPS`` more seeded tokens through the cache, one ``decode_one`` a
token (seeded, not greedy: ``check_logits.py`` has why).  The reference
(``models/<name>.py logits_at``: float32, ``highest`` precision, no cache, no
ring, the window as a mask over the whole sequence) runs one forward over all
the tokens and returns the logits at the same ``STEPS + 1`` positions.

Tolerances: ``check_logits.py``'s two, applied to the worst position: rms 0.04
and max 0.2 sigma (the standard deviation of the reference's logits over the
vocabulary at that position); its docstring derives them for 60 dense layers of
bfloat16 activations.  With experts a position whose router nearly ties can
exceed them by the flipped pair's weight (``check_routing.py`` separates those
by the reference's routing margins); this tool reports each position and judges
all of them, and PERF.md says what was read.

``--save`` stops after the engine and writes its tokens and logits, ``--load``
skips the engine and compares a saved file; without either the engine is
dropped before the reference runs (one chip holds one of them at a time).
``--cpu`` rehearses the control flow at toy widths.  Exit code 0 if within
tolerance.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

TOL_RMS_SIGMA = 0.04
TOL_MAX_SIGMA = 0.2
TAIL, STEPS = 392, 8
SEED = 38              # of the tokens; the weights' seed is the configuration's


def log(msg: str) -> None:
    print(f"check_window: {msg}", file=sys.stderr, flush=True)


def engine_logits(mpath: str, tpath: str, seq_len: int, vocab: int):
    """``(tokens (n + STEPS,), logits (STEPS + 1, V), facts)``."""
    import jax
    import numpy as np

    from dllama_tpu import cli
    from dllama_tpu.obs import dispatch as obs_dispatch

    t0 = time.time()
    args = cli.build_parser().parse_args(
        ["inference", "--model", mpath, "--tokenizer", tpath, "--workers",
         "tpu:1", "--temperature", "0", "--max-seq-len", str(seq_len)])
    engine, _ = cli.load_stack(args)
    load_s = time.time() - t0
    cfg = engine.cfg
    if not cfg.window:
        raise SystemExit("check_window: this configuration has no window layers")
    chunk = cfg.prefill_chunk()
    n = cfg.window + chunk + TAIL
    if n + STEPS > engine.seq_len:
        raise SystemExit(f"check_window: {n + STEPS} positions do not fit "
                         f"--max-seq-len {engine.seq_len}")
    rng = random.Random(f"{SEED}/window")
    toks = [rng.randrange(3, vocab) for _ in range(n + STEPS)]
    planes = {k: list(v.shape) for k, v in engine.cache.planes().items()}
    t0 = time.time()
    lg, _ = engine.prefill(toks[:n])
    prefill_s = time.time() - t0
    rows = [np.asarray(lg, np.float32)[0]]
    for tok in toks[n:]:
        lg, _ = engine.decode_one(int(tok))
        rows.append(np.asarray(lg, np.float32)[0])
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "prefill_s": prefill_s, "prompt_len": n,
             "window": cfg.window, "chunk": chunk, "cache_planes": planes,
             "ring": planes.get("wk", [0] * 4)[3], "peak_bytes": peak,
             "ledger": obs_dispatch.summary_line(),
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind}}
    del engine
    gc.collect()
    return np.asarray(toks, np.int32), np.stack(rows), facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to whole periods (a cheaper first look)")
    ap.add_argument("--save", help="run the engine only and write this .npz")
    ap.add_argument("--load", help="skip the engine and compare this .npz")
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import numpy as np

    import run as bench_run
    from harness import models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    if not hasattr(model, "logits_at"):
        raise SystemExit("check_window: this configuration's module has no logits_at")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    if a.layers:
        shape["n_layers"] = a.layers
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_window needs a TPU (or --cpu for the control flow)")

    if a.load:
        saved = np.load(a.load)
        toks, got = saved["tokens"], saved["logits"]
        facts = json.loads(str(saved["facts"]))
    else:
        toks, got, facts = engine_logits(mpath, tpath, shape["seq_len"],
                                         shape["vocab_size"])
        log(f"engine: loaded in {facts['load_s']:.1f} s, prompt of "
            f"{facts['prompt_len']} in {facts['prefill_s']:.1f} s, ring "
            f"{facts['ring']}, peak {facts['peak_bytes'] / 1e9:.2f} GB")
    if a.save:
        os.makedirs(os.path.dirname(os.path.abspath(a.save)), exist_ok=True)
        np.savez(a.save, tokens=toks, logits=got, facts=json.dumps(facts))
        print(json.dumps({"saved": a.save, "engine": facts}))
        return 0

    n = len(toks) - STEPS
    t0 = time.time()
    ref = model.logits_at(mpath, [[int(t) for t in toks]],
                          range(n - 1, n + STEPS))[0]
    ref_s = time.time() - t0
    rows = []
    for k in range(STEPS + 1):
        sigma = float(ref[k].std())
        diff = got[k] - ref[k]
        rows.append({"position": n + k - 1,
                     "what": "prefill" if k == 0 else f"decode {k}",
                     "max_sigma": float(np.abs(diff).max() / sigma),
                     "rms_sigma": float(np.sqrt((diff ** 2).mean()) / sigma),
                     "argmax_equal": bool(got[k].argmax() == ref[k].argmax())})
        log(str(rows[-1]))
    worst_max = max(r["max_sigma"] for r in rows)
    worst_rms = max(r["rms_sigma"] for r in rows)
    ok = worst_max <= TOL_MAX_SIGMA and worst_rms <= TOL_RMS_SIGMA
    out = {"ok": bool(ok), "config": name, "layers": shape["n_layers"],
           "prompt_len": int(n), "steps": STEPS, "max_sigma": worst_max,
           "rms_sigma": worst_rms, "tol_max_sigma": TOL_MAX_SIGMA,
           "tol_rms_sigma": TOL_RMS_SIGMA, "positions": rows,
           "reference_pass_s": ref_s, "engine": facts}
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "positions": len(rows),
               "prompt_len": int(n), "ring": facts["ring"]}
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
