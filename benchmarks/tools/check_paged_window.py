#!/usr/bin/env python3
"""The program's SLOT programs (the paged path a served cell runs) against the
plain float32 reference for a configuration with sliding-window layers, past a
slot ring of pages that has wrapped: ``python3
benchmarks/tools/check_paged_window.py --config
benchmarks/configs/k-exaone-236b-a23b.json``.  On the chip, at the published
widths and the configuration's depth, outside any timed window.

``check_window.py`` beside this file drives the contiguous one-stream engine
(a ring of positions); ``run.py``'s check prompts are 32 tokens, under every
window.  Here the engine is built as the cell's server builds its batch engine
(``cli.load_stack``, then an ``Engine`` over the same placed weights with the
cell's ``--batch-slots``, ``--kv-pages``, ``--kv-page-size``,
``--max-seq-len``: a pool for the full layers behind page tables, and for the
window layers a ring of pages a slot) and is driven
through ``Engine.slot_step``, the call the slot scheduler makes: ONE slot (the
last one, so that its ring is not the planes' first) prefills a seeded prompt
of ``PROMPT`` tokens in chunks of ``CHUNK`` rows, the other slots idle at
position 0 as free slots do, then ``STEPS`` pure-decode steps.  ``PROMPT``
is at least 400, so a ring of ten pages of 16 (160 positions) has wrapped
twice before the first decode step.  The slot's page table is a seeded
permutation of the pool's pages.

What is compared.  The slot programs sample on the device and hand out token
ids, not logits (the paged path exposes none: PERF.md section 7), so each
GREEDY token is judged on the reference's logits as ``harness/correct.py``
judges a served token: the reference (``models/<name>.py logits_at``: float32,
``highest`` precision, no cache, no ring, no pages, the window as a mask over
the whole sequence) runs one forward over the prompt and the tokens the slot
emitted, and the token the slot chose after position ``p`` must lie within
``correct.TOL_SIGMA`` (0.08) standard deviations of the reference's maximum at
``p``.  The run reports ``"compared": "greedy tokens"`` so that no reader takes
it for a logits check.  A chosen token is fed back, so every later position
also tests the cache the earlier steps wrote.

``--cpu`` rehearses the control flow at toy widths.  Exit code 0 if every
token is within tolerance.
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

PROMPT, CHUNK, STEPS = 416, 16, 12
SEED = 40              # of the tokens; the weights' seed is the configuration's


def log(msg: str) -> None:
    print(f"check_paged_window: {msg}", file=sys.stderr, flush=True)


def cell_argv(config_name: str) -> list[str]:
    """The server flags of the configuration's first served cell."""
    import run as bench_run
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in manifest["workloads"]:
        if w["config"] == config_name:
            argv = bench_run.load_json(
                os.path.join(BENCH, "cells", w["name"] + ".json"))["argv"]
            if "--batch-slots" in argv:
                return argv
    raise SystemExit(f"check_paged_window: no served cell of {config_name}")


def slot_tokens(mpath: str, tpath: str, argv: list[str], vocab: int):
    """``(tokens fed (PROMPT + STEPS,), tokens chosen (STEPS + 1,), facts)``:
    the greedy token after the prompt's last position and after each decoded
    one, from the slot programs."""
    import jax
    import numpy as np

    from dllama_tpu import cli
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.engine import Engine

    t0 = time.time()
    flag = dict(zip(argv[::2], argv[1::2]))
    args = cli.build_parser().parse_args(
        ["inference", "--model", mpath, "--tokenizer", tpath, "--temperature",
         "0", "--workers", flag["--workers"], "--max-seq-len",
         flag["--max-seq-len"]])
    chat, _ = cli.load_stack(args)   # as server/api.py serve(): the chat engine,
    engine = Engine(chat.cfg, chat.params, mesh=chat.mesh,   # then the batch one
                    batch=int(flag["--batch-slots"]), seq_len=args.max_seq_len,
                    kv_dtype=chat.cache.k.dtype, kv_pages=int(flag["--kv-pages"]),
                    kv_page_size=int(flag["--kv-page-size"]))
    load_s = time.time() - t0
    cfg = engine.cfg
    if not (cfg.window and engine.paged and engine.ring_pages):
        raise SystemExit("check_paged_window: this configuration has no window "
                         "layers on a paged engine")
    b, ps, ring = engine.batch, engine.kv_page_size, engine.ring_pages
    if PROMPT <= 2 * ring * ps:
        raise SystemExit(f"check_paged_window: a prompt of {PROMPT} does not "
                         f"wrap a ring of {ring} pages of {ps} twice")
    slot = b - 1
    rng = random.Random(f"{SEED}/paged-window")
    prompt = [rng.randrange(3, vocab) for _ in range(PROMPT)]
    pages = list(range(1, engine.kv_pages))
    rng.shuffle(pages)
    need = -(-(PROMPT + STEPS + 1) // ps)
    table = np.zeros((b, engine.max_pages_per_slot), np.int32)
    table[slot, :need] = pages[:need]
    zeros_f = np.zeros((b,), np.float32)

    def step(tokens_row: list[int], pos: int) -> int:
        t = len(tokens_row)
        tk = np.zeros((b, t), np.int32)
        tk[slot] = tokens_row
        pos_rows = np.zeros((b,), np.int32)
        pos_rows[slot] = pos
        n_valid = np.zeros((b,), np.int32)
        n_valid[slot] = t
        out = engine.slot_step(tk, pos_rows, n_valid, temps_np=zeros_f,
                               topps_np=zeros_f + 1.0, page_tables_np=table)
        return int(np.asarray(out)[0, slot])

    t0 = time.time()
    for lo in range(0, PROMPT, CHUNK):
        tok = step(prompt[lo:lo + CHUNK], lo)
    prefill_s = time.time() - t0
    fed, chosen = list(prompt), [tok]
    for k in range(STEPS):
        fed.append(chosen[-1])
        chosen.append(step([chosen[-1]], PROMPT + k))
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "prefill_s": prefill_s, "prompt_len": PROMPT,
             "chunk": CHUNK, "slot": slot, "slots": b, "window": cfg.window,
             "ring_pages": ring, "page_size": ps,
             "ring_laps": (PROMPT + STEPS) / (ring * ps),
             "cache_planes": {k: list(v.shape)
                              for k, v in engine.cache.planes().items()},
             "peak_bytes": peak, "ledger": obs_dispatch.summary_line(),
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind}}
    del engine, chat
    gc.collect()
    return np.asarray(fed, np.int32), np.asarray(chosen, np.int32), facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import numpy as np

    import run as bench_run
    from harness import correct, models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    if not hasattr(model, "logits_at"):
        raise SystemExit("check_paged_window: this configuration's module has "
                         "no logits_at")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_paged_window needs a TPU (or --cpu for the "
                         "control flow)")
    fed, chosen, facts = slot_tokens(mpath, tpath, cell_argv(name),
                                     shape["vocab_size"])
    log(f"slot programs: loaded in {facts['load_s']:.1f} s, prompt of {PROMPT} in "
        f"{facts['prefill_s']:.1f} s, ring of {facts['ring_pages']} pages lapped "
        f"{facts['ring_laps']:.2f} times, peak {facts['peak_bytes'] / 1e9:.2f} GB")
    t0 = time.time()
    ref = model.logits_at(mpath, [[int(t) for t in fed]],
                          range(PROMPT - 1, PROMPT + STEPS))[0]
    ref_s = time.time() - t0
    verdict = correct.compare(ref, [int(t) for t in chosen])
    rows = [dict(r, position=PROMPT - 1 + k,
                 what="prefill" if k == 0 else f"decode {k}")
            for k, r in enumerate(verdict["prompts"])]
    for r in rows:
        log(str(r))
    worst = max(r["below_max_sigma"] for r in rows)
    out = {"ok": bool(verdict["ok"]), "config": name, "compared": "greedy tokens",
           "tol_sigma": verdict["tol_sigma"], "worst_below_max_sigma": worst,
           "exact": verdict["exact"], "positions": rows,
           "layers": shape["n_layers"], "reference_pass_s": ref_s,
           "slot_programs": facts}
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(verdict["ok"]), "rehearsal": True,
               "compared": "greedy tokens", "positions": len(rows),
               "ring_pages": facts["ring_pages"], "ring_laps": facts["ring_laps"]}
    print(json.dumps(out))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
