#!/usr/bin/env python3
"""Both engines of the program against the plain float32 reference for a
configuration whose state-space mixers are SOME of its layers (Granite-4.0-H:
the other layers of a period whose one attention layer keeps keys and values):
``python3 benchmarks/tools/check_ssm_layers.py --config
benchmarks/configs/granite-4.0-h-small.json``.  On the chip, at the published
widths, the configuration's depth and the served cell's flags, outside any
timed window.

``check_ssm.py`` is the tool for a mixer in EVERY block (Falcon-H1) and walks
``range(n_layers)``: it would ask an attention layer for ``ssm_a_log`` and watch
a state plane by the layer's index, where the planes here are as deep as the
mixer layers are many.  This file imports what that one exports (its parts (a)
and (b), ``contiguous`` and ``slots``, its ``judge``, ``Watch``, ``_control``,
its state-plane tolerance and its reason: read its docstring for what each
part feeds and compares) and brings what the layer kinds change, and logit and
token tolerances of its own (``TOL_*``: a model that chooses 10 of 72 experts
in each of 20 layers; their readings where they are set):

* **the exposed copy** (``expose``): the one-sided nibbles of every Q40 block
  drawn again by ``check_ssm._expose`` over this file's own plan, and
  ``ssm_a_log`` / ``ssm_dt_bias`` drawn again in the MIXER layers alone, to the
  same ranges (``A`` uniform in 1 .. 16, ``dt`` log-uniform in 0.001 .. 0.1);
* **the watched planes** (``PlaneWatch``): a mixer layer's place among its kind,
  the first and the last plane (``cfg.n_ssm_layers - 1``), whose ``A`` is the
  kind-stacked ``ssm_a_log`` at that place, and the ring of ``x`` read back a
  head at a time where its rows hold two heads of 64 side by side;
* no part (c): the operator alone at a head geometry is ``check_ssm.py``'s (at
  Falcon-H1's); this configuration's geometry (128 heads of 64, ONE group, a
  state of 128 rows) is held through (b)'s state planes, which compare what the
  engine folded with its own ring rows folded in float64, and compiled for the
  chip in ``tests/test_tpu_compile.py``.

``--control zero-state`` / ``bfloat16-state`` put a wrong state in the program's
place (``check_ssm._control``) and run both parts on the exposed copy; the tool
then exits 1, as it must.  ``zero-state`` must read at least twice the tolerance
in (a)'s logits, (b)'s tokens and (b)'s state planes; ``bfloat16-state`` in (b)'s
state planes (no logit judges the state's precision: ``check_ssm.py`` has why).

``--cpu`` rehearses the control flow at toy widths.  Exit code 0 if within
tolerance.  The result goes to ``chiprun_out/check_ssm_layers[-control].json``.
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
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import check_ssm  # noqa: E402
from check_ssm import PLANE_TOL, SEED  # noqa: E402

# Logits, in sigmas of the reference's logits over the vocabulary at that
# position.  ``check_ssm.py``'s two (rms 0.04, max 0.2: ``check_logits.py``'s,
# derived for dense layers) do not fit 20 layers that each choose 10 of 72
# experts: 200 choices a token, of which a few always fall the other way once
# the router's input is rounded to bfloat16.  Read on the chip on the exposed
# copy (PR 65): the REFERENCE with bfloat16 activations reads 0.055 rms (median
# of 18 positions; 0.090 at worst) and 0.41 max from itself at float32 (float16:
# 0.016 / 0.063 / 0.30), the program 0.041 to 0.068 rms and 0.19 to 0.31 max at
# every position of both engines; the reference at float8 (the nearest precision
# below) 0.66 rms and 3.5 max, a zeroed state 0.68 rms / 3.4 max.  Three times
# the program's largest, a third of either failure.
TOL_RMS_SIGMA = 0.2
TOL_MAX_SIGMA = 1.0
# Tokens: the served token's reference logit within this many sigmas of the
# reference's maximum.  ``harness/correct.py``'s 0.08 is a dense cell's; the
# program's greedy tokens on the exposed copy lie up to 0.21 below (one of 555,
# 0.58; the bfloat16 reference's own argmax differs at 3 of 18 positions), a
# zeroed state's 3.97.
TOL_TOKEN_SIGMA = 1.0


def rejudge(rows: list[dict]) -> list[dict]:
    """``check_ssm.judge``'s rows held to this configuration's tolerances."""
    for r in rows:
        r["over_tol"] = r["below_max_sigma"] / TOL_TOKEN_SIGMA \
            if r["compared"] == "greedy token" else max(
                r["max_sigma"] / TOL_MAX_SIGMA, r["rms_sigma"] / TOL_RMS_SIGMA)
        r["ok"] = r["over_tol"] <= 1.0
    return rows


def log(msg: str) -> None:
    print(f"check_ssm_layers: {msg}", file=sys.stderr, flush=True)


def mixer_layers(shape: dict) -> list[int]:
    return [i for i in range(shape["n_layers"])
            if i % shape["window_period"] != shape["window_full_at"]]


def expose(model, shape: dict, src: str, dst: str) -> None:
    """``src`` copied to ``dst`` with the one-sided nibbles of every Q40 block
    drawn again (``check_ssm._expose``, which is handed the file's plan and no
    layer to walk) and the mixer layers' ``ssm_a_log`` and ``ssm_dt_bias``."""
    import numpy as np

    if os.path.exists(dst):
        return
    t0 = time.time()
    plan = model.plan(shape)

    class Planned:  # the file as it lies, whatever ``n_layers`` says
        plan = staticmethod(lambda _shape: plan)

    tmp = dst + ".nibbles"
    check_ssm._expose(Planned, dict(shape, n_layers=0), src, tmp)
    by_name = {t[0]: t for t in plan}
    raw = np.memmap(tmp, np.uint8, "r+")
    rng = np.random.default_rng([SEED, 1])
    h = shape["ssm_heads"]
    for i in mixer_layers(shape):
        for name, vals in (
                ("ssm_a_log", np.log(rng.uniform(1.0, 16.0, h))),
                ("ssm_dt_bias", np.log(np.expm1(np.exp(rng.uniform(
                    np.log(0.001), np.log(0.1), h)))))):
            _, _, _, off, nbytes = by_name[f"layers.{i}.{name}"]
            raw[off:off + nbytes].view(np.float32)[:] = vals.astype(np.float32)
    raw.flush()
    del raw
    os.replace(tmp, dst)
    log(f"wrote {dst} in {time.time() - t0:.0f} s")


class PlaneWatch(check_ssm.Watch):
    """``check_ssm.Watch`` of a state PLANE: ``slots`` asks for layers 0 and
    ``n_layers - 1``, and the planes (and the kind-stacked ``ssm_a_log``) are
    ``n_ssm_layers`` deep, so the last layer asked for is the last plane."""

    def __init__(self, engine, layer: int, slot: int):
        super().__init__(engine, min(layer, engine.cfg.n_ssm_layers - 1), slot)

    def wrote(self, pos: int, n: int) -> None:
        """``check_ssm.Watch.wrote`` for a ring of ``x`` whose rows hold two
        heads of 64 side by side (``ops/ssm.py heads_a_row``): a position's
        row read back a head at a time, then the same comparison."""
        import numpy as np
        cache, (li, s) = self.engine.cache, (self.layer, self.slot)
        if pos == 0:
            self.w, self.b, self.x, self.dt = 0, [], [], []
        at = (pos + np.arange(n)) % cache.rk.shape[3]
        heads, p = cache.rs.shape[2], cache.rs.shape[4]
        self.b += list(np.asarray(cache.rk[li, s], np.float64)[:, at].transpose(1, 0, 2))
        self.x += list(np.asarray(cache.rv[li, s], np.float64)[:, at].transpose(
            1, 0, 2).reshape(n, heads, p))
        self.dt += list(np.asarray(cache.rg[li, s, 0], np.float64)[at])
        w = int(np.asarray(cache.rw).ravel()[s])
        if w == self.w:
            return
        self.w = w
        b, x, dt = np.array(self.b[:w]), np.array(self.x[:w]), np.array(self.dt[:w])
        la = dt * self.a                                         # (w, H)
        coef = np.exp(la.sum(0) - np.cumsum(la, 0)) * dt         # decay from j to w
        want = np.einsum("jhn,jhp->hnp", np.repeat(b, heads // b.shape[1], axis=1)
                         * coef[..., None], x)
        got = np.asarray(cache.rs[li, s], np.float64)
        share = np.abs(got - want).max(axis=(1, 2)) / np.abs(want).max(axis=(1, 2))
        self.checked += 1
        self.worst = max(self.worst, float(share.max()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--control", choices=("bfloat16-state", "zero-state"))
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import run as bench_run
    from check_state import cell_argv
    from harness import models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    if not hasattr(model, "ssm_bytes"):
        raise SystemExit("check_ssm_layers: this configuration has no "
                         "state-space mixer")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    if "window_period" not in shape:
        raise SystemExit("check_ssm_layers: every block of this configuration "
                         "has a mixer: check_ssm.py is its tool")
    name = os.path.splitext(os.path.basename(a.config))[0]
    seeded, tpath = bench_run.ensure_files(
        name + ("-rehearse" if a.cpu else ""), model, shape, int(cfg["weights_seed"]))
    exposed = seeded[:-2] + "-exposed.m"
    expose(model, shape, seeded, exposed)
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_ssm_layers needs a TPU (or --cpu for the "
                         "control flow)")
    vocab = shape["vocab_size"]
    served = cell_argv(name, True)
    out = {"config": name, "layers": shape["n_layers"],
           "mixer_layers": len(mixer_layers(shape)), "control": a.control}
    ok = True
    check_ssm.Watch = PlaneWatch
    if a.control:
        check_ssm._control(a.control)
    files = {"seeded": seeded, "exposed": exposed}
    for label in (["exposed"] if a.control else list(files)):
        mpath = files[label]
        rows, facts_a = check_ssm.contiguous(mpath, tpath, served, vocab)
        log(f"{label}: contiguous engine loaded in {facts_a['load_s']:.1f} s, dt "
            f"{facts_a['dt_in_the_rings']}, rewinds {facts_a['rewinds']}, peak "
            f"{facts_a['peak_bytes'] / 1e9:.2f} GB")
        rows_b, facts_b = check_ssm.slots(mpath, tpath, served, vocab)
        planes = facts_b["state_planes"]
        depths = {k: v[0] for k, v in facts_b["cache_planes"].items()}
        log(f"{label}: slot programs loaded in {facts_b['load_s']:.1f} s, steps "
            f"{facts_b['steps']}, folds {facts_b['folds_counted']}, planes' depths "
            f"{depths}, state planes {planes}, peak "
            f"{facts_b['peak_bytes'] / 1e9:.2f} GB")
        judged, ref_s = check_ssm.judge(model, mpath, rows + rows_b)
        judged = rejudge(judged)
        for r in judged:
            if not r["ok"] and not a.control:
                log(f"OUT OF TOLERANCE ({label}): {r}")
        rewinds = facts_a["rewinds"]
        counted = (rewinds["after"] or {}).get("in_ring", 0) \
            - (rewinds["before"] or {}).get("in_ring", 0)
        if counted < 1:
            log("the rewind was not counted in ssm_state_rewinds{in_ring}")
        lg = [r for r in judged if r["compared"] == "logits"]
        tk = [r for r in judged if r["compared"] == "greedy token"]
        deep = depths["rs"] == len(mixer_layers(shape)) \
            and depths["k"] == shape["n_layers"] - depths["rs"]
        fine = all(r["ok"] for r in judged) and counted >= 1 and deep \
            and planes["folds_checked"] > 0 and planes["max_error_share"] <= PLANE_TOL
        ok = ok and fine
        out[label] = {
            "ok": fine, "worst_over_tol": max(r["over_tol"] for r in judged),
            "planes_depths": depths,
            "logits": {"positions": len(lg),
                       "max_sigma": max(r["max_sigma"] for r in lg),
                       "rms_sigma": max(r["rms_sigma"] for r in lg),
                       "over_tol": max(r["over_tol"] for r in lg),
                       "tol_max_sigma": TOL_MAX_SIGMA,
                       "tol_rms_sigma": TOL_RMS_SIGMA},
            "tokens": {"positions": len(tk), "exact": sum(r["exact"] for r in tk),
                       "distinct": len({r["token"] for r in tk}),
                       "worst_below_max_sigma": max(
                           r["below_max_sigma"] for r in tk),
                       "over_tol": max(r["over_tol"] for r in tk)},
            "rewinds_in_ring": counted, "reference_pass_s": ref_s,
            "contiguous": facts_a, "slot_programs": facts_b,
            "rows": None if a.cpu else judged}
    if a.control:
        # what each comparison reads under the wrong state, in its tolerances
        got = out["exposed"]
        reads = {"logits (a)": got["logits"]["over_tol"],
                 "tokens (b)": got["tokens"]["over_tol"],
                 "state planes (b)": got["slot_programs"]["state_planes"][
                     "max_error_share"] / PLANE_TOL}
        must = list(reads) if a.control == "zero-state" else ["state planes (b)"]
        out["control_over_tol"] = reads
        out["must_fail"] = must
        out["fails_by_twice"] = all(reads[k] >= 2.0 for k in must)
        log(f"control {a.control}: " + ", ".join(
            f"{k} {v:.3g} x its tolerance" for k, v in reads.items())
            + f"; of {must} " + ("each fails by twice, as it must"
                                 if out["fails_by_twice"] else "A CONTROL PASSES"))
        ok = not out["fails_by_twice"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = f"-{a.control}" if a.control else ""
    with open(os.path.join(ROOT, "chiprun_out", f"check_ssm_layers{tag}.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    for label in files:
        if label in out:
            out[label] = {k: v for k, v in out[label].items() if k != "rows"}
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
