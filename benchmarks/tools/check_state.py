#!/usr/bin/env python3
"""Both engines of the program against the plain float32 reference for a
configuration whose layers keep a recurrent state beside the KV cache (LFM2's
gated short convolutions): ``python3 benchmarks/tools/check_state.py --config
benchmarks/configs/lfm2-24b-a2b.json``.  On the chip, at the published widths
and the configuration's depth, outside any timed window.

``run.py``'s check prompts are 32 tokens through one prefill call: no decode
burst, no rewind, no second turn, no slot out of step.  Here:

(a) **the contiguous engine** (the program's loader, ``Engine`` and mesh
    through ``cli.load_stack``, the cell's ``--max-seq-len``): a prompt of
    ``PROMPT`` tokens, no bucket's size, prefilled (its logits are compared);
    the same prompt through ``Engine.generate_stream``, greedy, ``GEN`` tokens
    over three decode bursts (each greedy token is judged); the same again
    with a token that the stream yields inside a burst for the first time (the
    latest such) as the end-of-sequence id, so that the engine stops INSIDE a
    burst with the next one already written and sets its position back over
    both (``conv_state_rewinds{in_ring}`` must
    count it); then a second turn of ``TURN`` seeded tokens (no bucket's size)
    prefilled at the rewound position and ``STEPS`` seeded tokens decoded one by
    one (all their logits are compared).
(b) **the slot programs** (a paged ``Engine`` with the served cell's flags,
    ``slot_step`` as the scheduler calls it): request A in the last slot, its
    prompt in chunks of ``CHUNK`` with a ragged last one; request B in another
    slot, two steps later, so that the two are out of step (B prefills a whole
    chunk while A feeds its ragged one, and again while A decodes one token in
    a ``CHUNK``-row step); both decode ``STEPS`` tokens side by side; then
    request C takes A's slot over, at position 0 over the state A left, and
    decodes beside B.  The slot programs hand out tokens, not logits: each
    greedy token is judged on the reference's logits.

The reference (``models/<name>.py logits_at``: float32, ``highest`` precision,
no cache, no ring, no state, the convolution as shifted copies of ``z`` over the
whole sequence) runs ONE forward over all five sequences, right-padded to one
length (the model is causal: a position's logits do not depend on what
follows).

Tolerances.  Logits: ``check_logits.py``'s two, in sigmas of the reference's
logits over the vocabulary at that position: rms 0.04 and max 0.2 (its
docstring derives them for 60 layers of bfloat16 activations; this
configuration has 32 layers with three products a conv operator where
attention has a softmax).  Tokens: ``harness/correct.py``'s rule, the served
token's reference logit within 0.08 sigma of the reference's maximum.
``--lower`` also runs the reference with every activation rounded to the
mantissa of ``float8_e4m3fn`` (3 bits; ``lax.reduce_precision``, which the
compiler may not drop as it drops a pair of converts), the nearest precision
below the configuration's bfloat16, and reports what that reads against
float32: it must fail both logit tolerances (PERF.md section 6, PR 47, has
both readings).

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
PROMPT, GEN, TURN, STEPS, BURST = 77, 44, 19, 4, 16
CHUNK, PROMPT_A, PROMPT_B, PROMPT_C, SLOT_STEPS = 16, 53, 37, 21, 8
SEED = 47              # of the tokens; the weights' seed is the configuration's


def log(msg: str) -> None:
    print(f"check_state: {msg}", file=sys.stderr, flush=True)


def cell_argv(config_name: str, served: bool) -> list[str]:
    """The server flags of the configuration's one-stream or served cell."""
    import run as bench_run
    manifest = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in manifest["workloads"]:
        if w["config"] == config_name:
            argv = bench_run.load_json(
                os.path.join(BENCH, "cells", w["name"] + ".json"))["argv"]
            if ("--batch-slots" in argv) == served:
                return argv
    raise SystemExit(f"check_state: no {'served' if served else 'one-stream'} "
                     f"cell of {config_name}")


def _load(mpath: str, tpath: str, argv: list[str]):
    from dllama_tpu import cli
    flag = dict(zip(argv[::2], argv[1::2]))
    args = cli.build_parser().parse_args(
        ["inference", "--model", mpath, "--tokenizer", tpath, "--temperature",
         "0", "--workers", flag["--workers"], "--max-seq-len",
         flag["--max-seq-len"]])
    return cli.load_stack(args)[0], args, flag


def contiguous(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (a): ``(rows, facts)``; a row is ``(what, tokens fed, {position:
    logits}, {position: greedy token})``."""
    import jax
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics

    t0 = time.time()
    engine, _, _ = _load(mpath, tpath, argv)
    load_s = time.time() - t0
    if not engine.cfg.conv_taps:
        raise SystemExit("check_state: this configuration keeps no state")
    rng = random.Random(f"{SEED}/state")
    prompt = [rng.randrange(3, vocab) for _ in range(PROMPT)]
    lg, _ = engine.prefill(prompt)
    first = {PROMPT - 1: np.asarray(lg, np.float32)[0]}
    engine.reset()
    gen = [t for t, _ in engine.generate_stream(
        prompt, PROMPT + GEN, temperature=0.0, chunk=BURST)][PROMPT:]
    if len(gen) != GEN:
        raise SystemExit(f"check_state: {len(gen)} tokens of {GEN} came back")
    row_a = ("bursts", prompt + gen[:-1], first,
             {PROMPT - 1 + i: t for i, t in enumerate(gen)})
    # the end-of-sequence id: a token the stream yields INSIDE a burst for the
    # first time (gen[0] comes from the prefill, bursts start at gen[1]; not a
    # burst's last token, so that the bursts dispatched ahead are overshoot),
    # the latest such: a seeded model repeats itself, so it is often early
    firsts = [i for i, t in enumerate(gen)
              if i >= 1 and t not in gen[:i] and (i - 1) % BURST != BURST - 1]
    if not firsts:
        raise SystemExit("check_state: the stream yields no token for the "
                         "first time inside a burst; no id to stop at")
    stop = firsts[-1]
    before = obs_metrics.CONV_STATE_REWINDS.json_value()
    engine.reset()
    again = [t for t, _ in engine.generate_stream(
        prompt, PROMPT + GEN, temperature=0.0, chunk=BURST,
        eos_ids=(gen[stop],))][PROMPT:]
    if again != gen[:stop + 1] or engine.pos != PROMPT + stop:
        raise SystemExit(f"check_state: the stream stopped at {len(again)} "
                         f"tokens, position {engine.pos}; expected "
                         f"{stop + 1}, {PROMPT + stop}")
    turn = [rng.randrange(3, vocab) for _ in range(TURN + STEPS)]
    fed = prompt + gen[:stop] + turn
    base = PROMPT + stop
    lg, _ = engine.prefill(turn[:TURN])
    logits = {base + TURN - 1: np.asarray(lg, np.float32)[0]}
    for k, tok in enumerate(turn[TURN:]):
        lg, _ = engine.decode_one(int(tok))
        logits[base + TURN + k] = np.asarray(lg, np.float32)[0]
    after = obs_metrics.CONV_STATE_REWINDS.json_value()
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "prompt": PROMPT, "generated": GEN,
             "stopped_at": stop, "rewound_to": base,
             "rewinds": {"before": before, "after": after},
             "cache_planes": {k: list(v.shape)
                              for k, v in engine.cache.planes().items()},
             "peak_bytes": peak, "ledger": obs_dispatch.summary_line()}
    del engine
    gc.collect()
    return [row_a, ("second turn", fed, logits, {})], facts


def slots(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (b): ``(rows, facts)`` as :func:`contiguous`, tokens only."""
    import jax
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.engine import Engine

    t0 = time.time()
    chat, args, flag = _load(mpath, tpath, argv)
    engine = Engine(chat.cfg, chat.params, mesh=chat.mesh,
                    batch=int(flag["--batch-slots"]), seq_len=args.max_seq_len,
                    kv_dtype=chat.cache.k.dtype, kv_pages=int(flag["--kv-pages"]),
                    kv_page_size=int(flag["--kv-page-size"]))
    load_s = time.time() - t0
    b, ps = engine.batch, engine.kv_page_size
    rng = random.Random(f"{SEED}/slots")
    pages = list(range(1, engine.kv_pages))
    rng.shuffle(pages)
    table = np.zeros((b, engine.max_pages_per_slot), np.int32)
    zeros_f = np.zeros((b,), np.float32)

    class Seq:
        def __init__(self, what, slot, n_prompt):
            self.what, self.slot, self.pos = what, slot, 0
            self.prompt = [rng.randrange(3, vocab) for _ in range(n_prompt)]
            self.fed, self.chosen = [], {}
            need = -(-(n_prompt + 2 * SLOT_STEPS + 2) // ps)
            table[slot] = 0
            table[slot, :need] = [pages.pop() for _ in range(need)]

        def take(self, n):  # the next n tokens to feed
            if self.pos < len(self.prompt):
                return self.prompt[self.pos:self.pos + n]
            return [self.chosen[self.pos - 1]]

    def step(feeds: dict) -> None:
        """One slot step: ``feeds`` maps a sequence to how many tokens it
        feeds; the step is ``CHUNK`` rows wide if any feeds more than one."""
        rows = {s: s.take(n) for s, n in feeds.items()}
        t = CHUNK if any(len(r) > 1 for r in rows.values()) else 1
        tk = np.zeros((b, t), np.int32)
        pos_rows = np.zeros((b,), np.int32)
        n_valid = np.zeros((b,), np.int32)
        for s, r in rows.items():
            tk[s.slot, :len(r)] = r
            pos_rows[s.slot], n_valid[s.slot] = s.pos, len(r)
        out = np.asarray(engine.slot_step(
            tk, pos_rows, n_valid, temps_np=zeros_f, topps_np=zeros_f + 1.0,
            page_tables_np=table))
        for s, r in rows.items():
            s.fed += r
            s.pos += len(r)
            if s.pos >= len(s.prompt):
                s.chosen[s.pos - 1] = int(out[0, s.slot])

    a = Seq("slot A", b - 1, PROMPT_A)
    bb = Seq("slot B, two steps behind", 3, PROMPT_B)
    step({a: CHUNK})
    step({a: CHUNK})
    step({a: CHUNK, bb: CHUNK})
    step({a: CHUNK, bb: CHUNK})          # A's ragged last chunk of 5
    step({a: 1, bb: CHUNK})              # A decodes in a CHUNK-row step; B's ragged 5
    for _ in range(SLOT_STEPS):
        step({a: 1, bb: 1})
    c = Seq("slot A's next tenant", a.slot, PROMPT_C)   # the state left dirty
    step({c: CHUNK, bb: 1})
    step({c: CHUNK, bb: 1})
    for _ in range(SLOT_STEPS):
        step({c: 1, bb: 1})
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "slots": b, "page_size": ps, "chunk": CHUNK,
             "cache_planes": {k: list(v.shape)
                              for k, v in engine.cache.planes().items()},
             "slot_state": engine.slot_state, "peak_bytes": peak,
             "ledger": obs_dispatch.summary_line()}
    rows = [(s.what, s.fed, {}, s.chosen) for s in (a, bb, c)]
    del engine, chat
    gc.collect()
    return rows, facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--lower", action="store_true",
                    help="also read the reference at float8 activations")
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
        raise SystemExit("check_state: this configuration's module has no logits_at")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_state needs a TPU (or --cpu for the control flow)")
    vocab = shape["vocab_size"]
    rows_a, facts_a = contiguous(mpath, tpath, cell_argv(name, False), vocab)
    log(f"contiguous engine: loaded in {facts_a['load_s']:.1f} s, stopped inside "
        f"a burst at token {facts_a['stopped_at']}, rewinds {facts_a['rewinds']}, "
        f"peak {facts_a['peak_bytes'] / 1e9:.2f} GB")
    rows_b, facts_b = slots(mpath, tpath, cell_argv(name, True), vocab)
    log(f"slot programs: loaded in {facts_b['load_s']:.1f} s, peak "
        f"{facts_b['peak_bytes'] / 1e9:.2f} GB")
    rows = rows_a + rows_b
    width = max(len(r[1]) for r in rows)
    padded = [[int(t) for t in r[1]] + [3] * (width - len(r[1])) for r in rows]
    t0 = time.time()
    ref = model.logits_at(mpath, padded, range(width))
    ref_s = time.time() - t0

    out_rows, ok = [], True
    for i, (what, fed, logits, chosen) in enumerate(rows):
        for pos, got in sorted(logits.items()):
            sigma = float(ref[i, pos].std())
            diff = got - ref[i, pos]
            r = {"sequence": what, "position": pos, "compared": "logits",
                 "max_sigma": float(np.abs(diff).max() / sigma),
                 "rms_sigma": float(np.sqrt((diff ** 2).mean()) / sigma),
                 "argmax_equal": bool(got.argmax() == ref[i, pos].argmax())}
            r["ok"] = r["max_sigma"] <= TOL_MAX_SIGMA and r["rms_sigma"] <= TOL_RMS_SIGMA
            out_rows.append(r)
        if chosen:
            at = sorted(chosen)
            verdict = correct.compare(ref[i, at], [chosen[p] for p in at])
            for pos, v in zip(at, verdict["prompts"]):
                out_rows.append({"sequence": what, "position": pos,
                                 "compared": "greedy token",
                                 "below_max_sigma": v["below_max_sigma"],
                                 "top2_gap_sigma": v["top2_gap_sigma"],
                                 "exact": v["served"] == v["argmax"],
                                 "ok": v["below_max_sigma"] <= verdict["tol_sigma"]})
    for r in out_rows:
        if not r["ok"]:
            log(f"OUT OF TOLERANCE: {r}")
        ok = ok and r["ok"]
    rewinds = facts_a["rewinds"]
    counted = (rewinds["after"] or {}).get("in_ring", 0) \
        - (rewinds["before"] or {}).get("in_ring", 0)
    if counted < 1:
        log("the rewind inside a burst was not counted in conv_state_rewinds")
        ok = False
    lg = [r for r in out_rows if r["compared"] == "logits"]
    tk = [r for r in out_rows if r["compared"] == "greedy token"]
    out = {"ok": bool(ok), "config": name, "layers": shape["n_layers"],
           "logits": {"positions": len(lg),
                      "max_sigma": max(r["max_sigma"] for r in lg),
                      "rms_sigma": max(r["rms_sigma"] for r in lg),
                      "tol_max_sigma": TOL_MAX_SIGMA, "tol_rms_sigma": TOL_RMS_SIGMA},
           "tokens": {"positions": len(tk), "exact": sum(r["exact"] for r in tk),
                      "worst_below_max_sigma": max(r["below_max_sigma"] for r in tk),
                      "tol_sigma": correct.TOL_SIGMA},
           "rewinds_in_ring": counted, "reference_pass_s": ref_s,
           "contiguous": facts_a, "slot_programs": facts_b, "rows": out_rows}
    if a.lower:
        import jax.numpy as jnp
        i, logits = 1, rows[1][2]
        low = model.logits_at(mpath, [padded[1]], sorted(logits),
                              act_dtype=jnp.float8_e4m3fn)[0]
        worst_max = worst_rms = 0.0
        for k, pos in enumerate(sorted(logits)):
            sigma = float(ref[i, pos].std())
            diff = low[k] - ref[i, pos]
            worst_max = max(worst_max, float(np.abs(diff).max() / sigma))
            worst_rms = max(worst_rms, float(np.sqrt((diff ** 2).mean()) / sigma))
        out["float8_reference"] = {"max_sigma": worst_max, "rms_sigma": worst_rms,
                                   "fails": bool(worst_max > TOL_MAX_SIGMA
                                                 and worst_rms > TOL_RMS_SIGMA)}
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "logit_positions": len(lg),
               "token_positions": len(tk), "rewinds_in_ring": counted}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_state.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
