#!/usr/bin/env python3
"""Logits of the program's engine against the plain float32 reference for a
mixture-of-experts configuration, position by position, with the reference's
routing margins beside them: ``python3 benchmarks/tools/check_routing.py
--config benchmarks/configs/olmoe-1b-7b.json``.  On the chip, at the published
widths and full depth, outside any timed window.

``check_logits.py`` beside this file holds an engine to 0.04 / 0.2 sigma at
every position.  With experts that limit is crossed by the model itself:
where the router's k-th and (k+1)-th probabilities nearly tie, the served
precision (bfloat16 activations) chooses another expert than float32 does, and
that position's logits move by the flipped pair's weight, not by a rounding.
This tool separates the two.  The configuration's ``models/<name>.py`` exports
``routing_margins(model_path, prompts)``: the reference's logits at every
position and, per position and layer, the gap between the k-th and (k+1)-th
router logit over the spread of the row's router logits; and ``MARGIN_STEADY``,
the margin over which a position's routing cannot flip under the served
precision's error.  A position is MARGIN-STEADY where its margin exceeds that
at every layer.  There the dense limits must hold, and the exit code says
whether they do; over the other positions the error is reported, not judged.

The engine (the program's loader, ``Engine`` and mesh through
``cli.load_stack``, as ``check_logits.py`` builds them) prefills ``PROMPT_LEN``
seeded tokens of each of ``N_PROMPTS`` sequences and decodes ``STEPS`` more
seeded tokens through its cache (the prefill takes ``moe_ffn``'s many-row
strategy, each decode step its few-row one); the reference runs once over all
``PROMPT_LEN + STEPS`` tokens.  So many positions because few are steady at
full depth: with 64 experts the mean margin is 0.076 spreads, and a position
has 16 layers at which to fall under the threshold.  ``LEAST_STEADY`` is the
fewest steady positions the verdict may rest on.

Reported: per position the smallest margin, the layer it is at, max and rms
error in sigmas (of the reference's logits over the vocabulary); over the
steady and over the other positions the worst max and rms; the same split at
other thresholds (``SWEEP``), so that the threshold can be read against the
data; the share of positions whose argmax agrees; and ``far``, the positions
more than ``FAR_SIGMA`` off: no flip of one expert moves logits that far.

What ``far`` has shown with seeded weights (PERF.md, PR 31): 16 layers of
random attention with experts that carry a quarter of a dense block's weight
collapse the residual stream onto one direction, and every position's logits
are one of two vectors, ``+L`` or ``-L`` (correlation 0.9998 within a sign,
-0.997 across).  Which sign a position takes is decided where its stream
passes near zero, and there the served precision can decide otherwise than
float32: one position of 392 read 5.0 sigma off, the engine's logits 0.017
sigma from the other sign's vector, equal on three repeats, and the engine on
its XLA matmul path agreed with float32 at that position.  A property of the
seeded model under rounding, not of a kernel; such a position is reported and
is not margin-steady here only by chance.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

N_PROMPTS, PROMPT_LEN, STEPS = 8, 16, 48
SEED = 31
LEAST_STEADY = 8
FAR_SIGMA = 1.0
SWEEP = (0.0, 0.005, 0.01, 0.015, 0.02, 0.03, 0.05)


def log(msg: str) -> None:
    print(f"check_routing: {msg}", file=sys.stderr, flush=True)


def split(max_sigma, rms_sigma, steady) -> dict:
    """Worst max and rms error over the steady positions and over the rest."""
    out = {}
    for name, mask in (("steady", steady), ("other", ~steady)):
        n = int(mask.sum())
        out[name] = {"positions": n,
                     "max_sigma": float(max_sigma[mask].max()) if n else None,
                     "rms_sigma": float(rms_sigma[mask].max()) if n else None,
                     "rms_sigma_median": float(sorted(rms_sigma[mask])[n // 2])
                     if n else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth (a cheaper first look; never a width)")
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import numpy as np

    import check_logits
    import run as bench_run
    from harness import correct, models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    if not hasattr(model, "routing_margins"):
        raise SystemExit(f"{a.config}: its architecture has no routing_margins "
                         "(no experts): use check_logits.py")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    if a.layers:
        shape["n_layers"] = a.layers
    name = os.path.splitext(os.path.basename(a.config))[0]
    wseed = int(cfg["weights_seed"])
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, wseed)
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_routing needs a TPU (or --cpu for the control flow)")

    seqs = correct.check_prompts(SEED, N_PROMPTS, PROMPT_LEN + STEPS,
                                 shape["vocab_size"])
    toks, got, facts = check_logits.engine_logits(
        mpath, tpath, 1, seqs, STEPS, min(check_logits.MAX_SEQ_LEN, shape["seq_len"]))
    log(f"engine: loaded in {facts['load_s']:.1f} s; {facts['ledger']}")
    t0 = time.time()
    ref, margins = model.routing_margins(mpath, [list(map(int, t)) for t in toks])
    ref_s = time.time() - t0
    at = slice(PROMPT_LEN - 1, PROMPT_LEN + STEPS)
    ref, margins = ref[:, at], margins[:, at]          # (P, STEPS + 1, ...)
    diff = got - ref
    sigma = ref.std(-1)
    max_sigma = np.abs(diff).max(-1) / sigma
    rms_sigma = np.sqrt((diff ** 2).mean(-1)) / sigma
    least = margins.min(-1)
    steady = least > model.MARGIN_STEADY
    rows = [{"prompt": p, "position": PROMPT_LEN - 1 + k,
             "what": "prefill" if k == 0 else f"decode {k}",
             "least_margin": float(least[p, k]),
             "at_layer": int(margins[p, k].argmin()),
             "max_sigma": float(max_sigma[p, k]), "rms_sigma": float(rms_sigma[p, k]),
             "argmax_equal": bool(got[p, k].argmax() == ref[p, k].argmax())}
            for p in range(toks.shape[0]) for k in range(got.shape[1])]
    verdict = split(max_sigma, rms_sigma, steady)
    s = verdict["steady"]
    ok = (s["positions"] >= LEAST_STEADY
          and s["max_sigma"] <= check_logits.TOL_MAX_SIGMA
          and s["rms_sigma"] <= check_logits.TOL_RMS_SIGMA)
    out = {"ok": bool(ok), "config": name, "layers": shape["n_layers"],
           "weights_seed": wseed, "prompts": int(toks.shape[0]),
           "prompt_len": PROMPT_LEN, "steps": STEPS,
           "margin_steady": model.MARGIN_STEADY, "least_steady": LEAST_STEADY,
           "tol_max_sigma": check_logits.TOL_MAX_SIGMA,
           "tol_rms_sigma": check_logits.TOL_RMS_SIGMA, **verdict,
           "all": {"max_sigma": float(max_sigma.max()),
                   "rms_sigma": float(rms_sigma.max()),
                   "rms_sigma_median": float(np.median(rms_sigma)),
                   "argmax_equal_share": float(np.mean([r["argmax_equal"] for r in rows]))},
           "margin": {"mean": float(margins.mean()), "median": float(np.median(margins)),
                      "by_layer_mean": [float(v) for v in margins.mean((0, 1))]},
           "far": [r for r in rows if r["rms_sigma"] > FAR_SIGMA],
           "sweep": {str(t): split(max_sigma, rms_sigma, least > t) for t in SWEEP},
           "reference_pass_s": ref_s, "engine": facts, "positions": rows}
    for t in SWEEP:
        log(f"margin > {t}: {json.dumps(out['sweep'][str(t)])}")
    log(f"more than {FAR_SIGMA} sigma off: {json.dumps(out['far'])}")
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "positions": len(rows)}
    else:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"check_routing-{name}-s{wseed}.json"), "w") as f:
            json.dump(out, f, indent=1)
        out.pop("positions")  # the file has them; the last line stays short
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
