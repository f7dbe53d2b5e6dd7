#!/usr/bin/env python3
"""Logits of the program's engine against the plain float32 reference, on the
chip, at a configuration's published widths and full depth, outside any timed
window: ``python3 benchmarks/tools/check_logits.py --config
benchmarks/configs/yi-34b.json --tp 4``.

For each of ``N_PROMPTS`` seeded prompts of ``PROMPT_LEN`` tokens the
engine (the program's loader, ``Engine`` and mesh, through ``cli.load_stack``,
as ``dllama inference --workers tpu:<tp>`` builds them) prefills the prompt
and then decodes ``STEPS`` more seeded tokens through its cache, one
``decode_one`` a token; the logits after the prefill and after every decode
step are kept.  (The continuation is seeded, not greedy: with random weights a
greedy continuation collapses onto one repeated token, identical tokens have
near-identical keys, attention over scores this peaked is a hard choice, and a
near-tie that bfloat16 flips against float32 swaps the whole output: seen at 4
layers, engine on the chip and on the CPU's XLA path alike, PERF.md PR 26.)  The reference
(the ``last_logits`` of the configuration's architecture, ``models/<name>.py``:
float32, ``highest`` matmul precision, no cache, one tensor at a time) then
runs its full forward over the same tokens, once per sequence length (it
returns the last position's logits), and the two are compared position by
position.

Reported per position and overall, in sigmas (the standard deviation of the
reference's logits over the vocabulary at that position): the largest
``|engine - reference|`` and its root mean square.  Tolerances, stated here
and applied to the worst position:

* ``TOL_RMS_SIGMA`` 0.04.  The engine keeps activations and the residual
  stream in bfloat16 (relative rounding noise 2**-9 / sqrt(3) = 1.1e-3 a
  rounding) and accumulates in float32 from 4-bit weights that both sides
  read exactly.  About 7 roundings a layer over 60 layers add to
  sqrt(420) x 1.1e-3 = 0.023 of the signal; PERF.md's earlier readings of the
  served first token are 0.01-0.03 sigma.  An 8-bit (Q80) activation path
  rounds 4 x as coarsely (max/127 over blocks of 32 against 2**-9 of each
  element) and reads about 0.09: it fails, as does a dropped layer or a wrong
  shard boundary (whole sigmas).
* ``TOL_MAX_SIGMA`` 0.2: the largest of 64000 Gaussian errors is 4.3 x their
  root mean square.

The reference needs one device; ``--save`` stops after the engine and writes
its tokens and logits, ``--load`` skips the engine and compares a saved file,
so that the reference's passes (each streams the whole model file) can run on
one chip while the engine ran on four.  Exit code 0 if within tolerance.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

TOL_RMS_SIGMA = 0.04
TOL_MAX_SIGMA = 0.2
N_PROMPTS, PROMPT_LEN, STEPS = 4, 32, 8
SEED = 26              # of the prompts; the weights' seed is the configuration's
MAX_SEQ_LEN = 32768    # as the cell serves it


def log(msg: str) -> None:
    print(f"check_logits: {msg}", file=sys.stderr, flush=True)


def engine_logits(mpath: str, tpath: str, tp: int, seqs: list[list[int]],
                  steps: int, seq_len: int):
    """``(tokens (P, n + steps), logits (P, steps + 1, V), facts)``: each of
    ``seqs`` is a prompt followed by the ``steps`` tokens to decode."""
    import jax
    import numpy as np

    from dllama_tpu import cli
    from dllama_tpu.obs import dispatch as obs_dispatch

    t0 = time.time()
    args = cli.build_parser().parse_args(
        ["inference", "--model", mpath, "--tokenizer", tpath, "--workers",
         f"tpu:{tp}", "--temperature", "0", "--max-seq-len", str(seq_len)])
    engine, _ = cli.load_stack(args)
    load_s = time.time() - t0
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in engine.mesh.devices.flat]
    toks_out, logits_out = [], []
    for toks in seqs:
        engine.reset()
        lg, _ = engine.prefill(list(toks[:len(toks) - steps]))
        rows = [np.asarray(lg, np.float32)[0]]
        for tok in toks[len(toks) - steps:]:
            lg, _ = engine.decode_one(int(tok))
            rows.append(np.asarray(lg, np.float32)[0])
        toks_out.append(list(toks))
        logits_out.append(np.stack(rows))
    facts = {"load_s": load_s, "load_peak_bytes": peaks,
             "ledger": obs_dispatch.summary_line(),
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind, "count": tp}}
    del engine
    gc.collect()
    return np.asarray(toks_out, np.int32), np.stack(logits_out), facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth (a cheaper first look; never a width)")
    ap.add_argument("--save", help="run the engine only and write this .npz")
    ap.add_argument("--load", help="skip the engine and compare this .npz")
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if a.tp > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={a.tp}")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import numpy as np

    import run as bench_run
    from harness import correct, models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    shape = bench_run.model_shape(model, cfg, a.cpu)
    if a.layers:
        shape["n_layers"] = a.layers
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_logits needs a TPU (or --cpu for the control flow)")

    if a.load:
        saved = np.load(a.load)
        toks, got = saved["tokens"], saved["logits"]
        facts = json.loads(str(saved["facts"]))
    else:
        seqs = correct.check_prompts(SEED, N_PROMPTS, PROMPT_LEN + STEPS,
                                     shape["vocab_size"])
        toks, got, facts = engine_logits(
            mpath, tpath, a.tp, seqs, STEPS,
            min(MAX_SEQ_LEN, shape["seq_len"]))
        log(f"engine: loaded in {facts['load_s']:.1f} s, peaks after load "
            f"{facts['load_peak_bytes']}")
    if a.save:
        os.makedirs(os.path.dirname(os.path.abspath(a.save)), exist_ok=True)
        np.savez(a.save, tokens=toks, logits=got, facts=json.dumps(facts))
        print(json.dumps({"saved": a.save, "engine": facts}))
        return 0

    n0 = toks.shape[1] - (got.shape[1] - 1)
    rows, ref_s = [], []
    for k in range(got.shape[1]):
        t0 = time.time()
        ref = model.last_logits(mpath, [list(map(int, t[:n0 + k]))
                                        for t in toks])
        ref_s.append(time.time() - t0)
        sigma = ref.std(axis=1)
        diff = got[:, k] - ref
        rows.append({
            "position": n0 + k - 1, "what": "prefill" if k == 0 else f"decode {k}",
            "max_sigma": float((np.abs(diff).max(axis=1) / sigma).max()),
            "rms_sigma": float((np.sqrt((diff ** 2).mean(axis=1)) / sigma).max()),
            "argmax_equal": int((got[:, k].argmax(1) == ref.argmax(1)).sum())})
        log(f"{rows[-1]} ({ref_s[-1]:.1f} s of reference)")
    worst_max = max(r["max_sigma"] for r in rows)
    worst_rms = max(r["rms_sigma"] for r in rows)
    ok = worst_max <= TOL_MAX_SIGMA and worst_rms <= TOL_RMS_SIGMA
    out = {"ok": bool(ok), "config": name, "layers": shape["n_layers"],
           "tp": a.tp, "prompts": int(toks.shape[0]), "prompt_len": int(n0),
           "steps": int(got.shape[1] - 1), "max_sigma": worst_max,
           "rms_sigma": worst_rms, "tol_max_sigma": TOL_MAX_SIGMA,
           "tol_rms_sigma": TOL_RMS_SIGMA, "positions": rows,
           "reference_pass_s": ref_s, "engine": facts}
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "positions": len(rows)}
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
