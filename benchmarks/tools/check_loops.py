#!/usr/bin/env python3
"""A looped model's two engines against the plain float32 reference, on the
chip, at the configuration's published widths and full depth, outside any timed
window: ``python3 benchmarks/tools/check_loops.py --config
benchmarks/configs/ouro-2.6b.json``.

(i) The ONE-STREAM engine (the program's loader, ``Engine`` and mesh through
``cli.load_stack``, as ``dllama inference --workers tpu:1`` builds them) at
``--max-seq-len 1024`` (``check_logits.py`` hands the contiguous cache
``min(32768, max_position_embeddings)`` positions, 51 GB of planes here): for
each of ``N_PROMPTS`` seeded prompts of ``PROMPT_LEN`` tokens it prefills the
prompt and decodes ``STEPS`` more seeded tokens through its cache (seeded, not
greedy: ``check_logits.py`` says why); the logits after the prefill and after
every step against the reference's (``models/<name>.py logits_at``: float32,
``highest`` precision, no cache, every pass over the whole sequence), in sigmas
of the reference's logits at the position.  Tolerances ``check_logits.py``'s,
with its reasons: worst position's root mean square ``TOL_RMS_SIGMA`` 0.04 and
largest error ``TOL_MAX_SIGMA`` 0.2.  This was also Step 0's reading (ISSUE 57:
under 0.03 at the worst position the residual stream stays in the activation
dtype, like every other arch's).  As the issue wrote the rule it FAILED: the
worst of the 36 positions read 4.0 sigma; over the STEADY positions (below; the
set was defined after that reading) it read 0.0278; PERF.md section 6, PR 57.

(ii) The SLOT programs, built as the cell's server builds its batch engine (the
cell's ``--batch-slots``, ``--kv-pages``, ``--kv-page-size``,
``--max-seq-len``) and driven through ``Engine.slot_step``, the call the slot
scheduler makes: every slot prefills its own seeded prompt of 100-170 tokens
in chunks of ``CHUNK`` rows (slots that finish early ride along as decoding
slots do, which makes the last chunk steps mixed), over a seeded permutation of
the pool's pages, then ``DECODE`` pure-decode steps; half way one slot is
retired and taken by a new prompt at position 0 over the pages its last tenant
wrote.  The slot programs hand out token ids, not logits, so each GREEDY token
is judged on the reference's logits as ``harness/correct.py`` judges a served
token (within ``correct.TOL_SIGMA`` of the reference's maximum at that
position; the chosen token is fed back, so a later position also tests the
planes the earlier steps wrote): ``"compared": "greedy tokens"``.

STEADY positions.  A seeded model of this depth has positions at which it has
no stable value at the configuration's precision: 192 applications of randomly
weighted attention collapse a sequence's rows onto one of two attractor states
(the reference's logits have one of two spreads, 1.561 or about 1.53 sigma-units,
at every position of every prompt), and at a few positions the choice between
them hangs on a rounding: there the float32 reference and THE SAME REFERENCE
with its activations rounded to bfloat16 (``logits_at(act_dtype=bfloat16)``, the
precision the configuration states) differ by 0.17 to 4.0 sigma, where they
differ by 0.002 to 0.017 everywhere else (my chip runs, PR 57: one position of
36, and the one after it).  No implementation in bfloat16 has a right answer
there, so both checks judge the positions at which that difference is at most
``STEADY_SIGMA`` 0.05 (chosen AFTER the readings: three times the steady ones,
a third of the least unsteady one), list the others with both readings, and fail
if fewer than ``MIN_STEADY`` of the positions are steady.  The same collapse
makes (ii) a weak comparison on this seed of weights: of its 194 greedy tokens
4 are distinct (one token is the argmax at most positions), which is what
``--control`` is for.

``--control NAME`` puts a WRONG computation in the program's place in both
comparisons and runs no engine: ``float8`` is the reference with its stream and
every matmul operand rounded to float8_e4m3fn (the precision below the one the
configuration states), ``a-pass-fewer`` the reference run ``loops - 1`` times
over its own output.  In (i) its logits stand where the engine's did; in (ii)
its argmax after each of the last ``DECODE + 1`` positions of ``N_CONTROL``
seeded sequences stands where the served tokens did.  The comparison must see
it: a control that exits 0 says the check would have passed that wrong program
(``"seen"`` in the report says which of the two comparisons did).

``--cpu`` rehearses the control flow at toy widths.  Exit code 0 if both are
within tolerance.
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
sys.path.insert(0, HERE)

from check_paged_window import cell_argv  # noqa: E402  (a served cell's flags)

TOL_RMS_SIGMA = 0.04   # check_logits.py's, with its reasons
TOL_MAX_SIGMA = 0.2
STEADY_SIGMA = 0.05    # the reference against itself in bfloat16 (module docstring)
MIN_STEADY = 0.75      # of the positions judged
N_PROMPTS, PROMPT_LEN, STEPS = 4, 32, 8
ONE_STREAM_SEQ_LEN = 1024
CHUNK, DECODE = 16, 16
N_CONTROL = 4          # sequences a --control's tokens are taken from
SEED = 57              # of the tokens; the weights' seed is the configuration's


def log(msg: str) -> None:
    print(f"check_loops: {msg}", file=sys.stderr, flush=True)


def _load(mpath: str, tpath: str, seq_len: int):
    from dllama_tpu import cli
    args = cli.build_parser().parse_args(
        ["inference", "--model", mpath, "--tokenizer", tpath, "--workers",
         "tpu:1", "--temperature", "0", "--max-seq-len", str(seq_len)])
    return cli.load_stack(args)[0], args


def one_stream(mpath: str, tpath: str, seqs: list[list[int]]):
    """``(logits (P, STEPS + 1, V), facts)`` of the one-stream engine."""
    import jax
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch

    t0 = time.time()
    engine, _ = _load(mpath, tpath, ONE_STREAM_SEQ_LEN)
    load_s = time.time() - t0
    out = []
    for toks in seqs:
        engine.reset()
        lg, _ = engine.prefill(list(toks[:PROMPT_LEN]))
        rows = [np.asarray(lg, np.float32)[0]]
        for tok in toks[PROMPT_LEN:]:
            lg, _ = engine.decode_one(int(tok))
            rows.append(np.asarray(lg, np.float32)[0])
        out.append(np.stack(rows))
    cfg = engine.cfg
    facts = {"load_s": load_s, "seq_len": ONE_STREAM_SEQ_LEN,
             "passes": cfg.n_loops, "planes": cfg.n_cache_planes,
             "cache_k": list(engine.cache.k.shape),
             "ledger": obs_dispatch.summary_line(),
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind}}
    del engine
    gc.collect()
    return np.stack(out), facts


def wrong_computation(model, mpath: str, name: str) -> dict:
    """``logits_at``'s keywords for ``--control name``."""
    import jax.numpy as jnp
    if name == "float8":
        return {"act_dtype": jnp.float8_e4m3fn}
    return {"passes": model.read_header(mpath)["loops"] - 1}


def control_tokens(model, mpath: str, vocab: int, wrong: dict, cpu: bool):
    """``(sequences, tokens chosen)`` in ``slot_tokens``' form, the tokens the
    wrong computation's argmax after the last ``DECODE + 1`` positions of
    seeded sequences as long as (ii)'s."""
    rng = random.Random(f"{SEED}/control")
    lo, hi = (20, 40) if cpu else (100, 170)
    fed, chosen = [], []
    for _ in range(N_CONTROL):
        toks = [rng.randrange(3, vocab) for _ in range(rng.randint(lo, hi) + DECODE)]
        at = list(range(len(toks) - DECODE - 1, len(toks)))
        picks = model.logits_at(mpath, [toks], at, **wrong)[0].argmax(-1)
        fed.append(toks)
        chosen.append([(p, int(t)) for p, t in zip(at, picks)])
    return fed, chosen


def _rms_sigma(got, ref):
    import numpy as np
    return np.sqrt(((got - ref) ** 2).mean(-1)) / ref.std(-1)


def judge_logits(model, mpath: str, seqs, got) -> dict:
    """Per position, in sigmas of the reference's logits there, over the
    prompts that are steady at it."""
    import jax.numpy as jnp
    import numpy as np
    at = range(PROMPT_LEN - 1, PROMPT_LEN + STEPS)
    t0 = time.time()
    prompts = [list(map(int, s)) for s in seqs]
    ref = model.logits_at(mpath, prompts, at)
    wobble = _rms_sigma(model.logits_at(mpath, prompts, at, act_dtype=jnp.bfloat16),
                        ref)                                   # (P, positions)
    steady = wobble <= STEADY_SIGMA
    sigma = ref.std(-1)
    rms = _rms_sigma(got, ref)
    mx = np.abs(got - ref).max(-1) / sigma
    rows = [{"position": PROMPT_LEN - 1 + k,
             "what": "prefill" if k == 0 else f"decode {k}",
             "steady_prompts": int(steady[:, k].sum()),
             "max_sigma": float(np.where(steady[:, k], mx[:, k], 0).max()),
             "rms_sigma": float(np.where(steady[:, k], rms[:, k], 0).max()),
             "argmax_equal": int((got[:, k].argmax(1) == ref[:, k].argmax(1)).sum())}
            for k in range(got.shape[1])]
    unsteady = [{"prompt": int(p), "position": PROMPT_LEN - 1 + int(k),
                 "reference_bf16_rms_sigma": float(wobble[p, k]),
                 "engine_rms_sigma": float(rms[p, k])}
                for p, k in zip(*np.nonzero(~steady))]
    worst_max = max(r["max_sigma"] for r in rows)
    worst_rms = max(r["rms_sigma"] for r in rows)
    share = float(steady.mean())
    return {"ok": bool(worst_max <= TOL_MAX_SIGMA and worst_rms <= TOL_RMS_SIGMA
                       and share >= MIN_STEADY),
            "max_sigma": worst_max, "rms_sigma": worst_rms,
            "rms_sigma_every_position": float(rms.max()),
            "tol_max_sigma": TOL_MAX_SIGMA, "tol_rms_sigma": TOL_RMS_SIGMA,
            "steady_sigma": STEADY_SIGMA, "steady_share": share,
            "steady_reference_bf16_rms_sigma": float(np.where(steady, wobble, 0).max()),
            "unsteady": unsteady, "positions": rows,
            "reference_pass_s": time.time() - t0}


def slot_tokens(mpath: str, tpath: str, argv: list[str], vocab: int, cpu: bool):
    """``(sequences fed, per slot; the greedy tokens chosen and the positions
    they were chosen after, per sequence; facts)`` from the slot programs."""
    import jax
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.engine import Engine

    t0 = time.time()
    flag = dict(zip(argv[::2], argv[1::2]))
    seq_len = int(flag["--max-seq-len"])
    chat, args = _load(mpath, tpath, seq_len)  # as server/api.py serve(): the
    engine = Engine(chat.cfg, chat.params, mesh=chat.mesh,   # chat engine, then
                    batch=int(flag["--batch-slots"]), seq_len=seq_len,  # the batch one
                    kv_dtype=chat.cache.k.dtype, kv_pages=int(flag["--kv-pages"]),
                    kv_page_size=int(flag["--kv-page-size"]))
    load_s = time.time() - t0
    b, ps = engine.batch, engine.kv_page_size
    rng = random.Random(f"{SEED}/slots")
    lo, hi = (20, 40) if cpu else (100, 170)
    # sequence s: its slot, its prompt; the last one takes a retired slot
    prompts = [[rng.randrange(3, vocab) for _ in range(rng.randint(lo, hi))]
               for _ in range(b + 1)]
    pages = list(range(1, engine.kv_pages))
    rng.shuffle(pages)
    per = -(-(hi + DECODE + 1) // ps)
    table = np.zeros((b, engine.max_pages_per_slot), np.int32)
    for r in range(b):
        table[r, :per] = pages[r * per:(r + 1) * per]
    zeros_f = np.zeros((b,), np.float32)
    owner = list(range(b))              # the sequence in each slot
    fed = [list(p) for p in prompts]    # what each sequence has been fed
    done = [0] * (b + 1)                # how much of it is in the cache
    chosen = [[] for _ in prompts]      # (position chosen after, token)
    mixed = 0

    def step(t: int) -> None:
        """One scheduler step of width ``t``: every slot feeds what it has."""
        nonlocal mixed
        tk = np.zeros((b, t), np.int32)
        pos, nv = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
        for r, s in enumerate(owner):
            n = min(t, len(fed[s]) - done[s])
            tk[r, :n], pos[r], nv[r] = fed[s][done[s]:done[s] + n], done[s], n
        mixed += int(t > 1 and (nv == 1).any() and (nv > 1).any())
        out = np.asarray(engine.slot_step(tk, pos, nv, temps_np=zeros_f,
                                          topps_np=zeros_f + 1.0,
                                          page_tables_np=table))[0]
        for r, s in enumerate(owner):
            done[s] += int(nv[r])
            if nv[r] and done[s] == len(fed[s]):  # its last fed token: a greedy one
                chosen[s].append((done[s] - 1, int(out[r])))
                fed[s].append(int(out[r]))

    t0 = time.time()
    while any(len(fed[s]) - done[s] > 1 for s in owner):
        step(CHUNK)
    prefill_s = time.time() - t0
    for k in range(DECODE):
        if k == DECODE // 2:
            # slot 1 is retired; its successor prefills over its pages while the
            # other slots go on decoding (mixed steps), then decodes with them
            owner[1] = b
            while len(fed[b]) - done[b] > 1:
                step(CHUNK)
        step(1)
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    for s in range(b + 1):  # the last chosen token was never fed
        fed[s] = fed[s][:done[s]]
    facts = {"load_s": load_s, "prefill_s": prefill_s, "slots": b,
             "prompt_lens": [len(p) for p in prompts], "chunk": CHUNK,
             "decode_steps": DECODE, "mixed_steps": mixed, "page_size": ps,
             "pool_k": list(engine.cache.k.shape), "peak_bytes": peak,
             "kv_bytes_per_token": engine.kv_bytes_per_token,
             "ledger": obs_dispatch.summary_line(),
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind}}
    del engine, chat
    gc.collect()
    return fed, chosen, facts


def judge_tokens(model, mpath: str, fed, chosen) -> dict:
    import jax.numpy as jnp

    from harness import correct
    t0 = time.time()
    rows = []
    for s, (toks, picks) in enumerate(zip(fed, chosen)):
        at = [p for p, _ in picks]
        ref = model.logits_at(mpath, [toks], at)[0]
        wobble = _rms_sigma(
            model.logits_at(mpath, [toks], at, act_dtype=jnp.bfloat16)[0], ref)
        v = correct.compare(ref, [t for _, t in picks])
        rows += [dict(r, sequence=s, position=p, steady=bool(w <= STEADY_SIGMA),
                      reference_bf16_rms_sigma=float(w))
                 for r, (p, _), w in zip(v["prompts"], picks, wobble)]
    judged = [r for r in rows if r["steady"]]

    def below(r):  # None: the served token is not in the vocabulary
        return 9.0 if r["below_max_sigma"] is None else r["below_max_sigma"]

    worst = max(below(r) for r in judged)
    share = len(judged) / len(rows)
    return {"ok": bool(worst <= correct.TOL_SIGMA and share >= MIN_STEADY),
            "compared": "greedy tokens",
            "tol_sigma": correct.TOL_SIGMA, "worst_below_max_sigma": worst,
            "tokens": len(rows), "steady_tokens": len(judged),
            "distinct_tokens": len({r["served"] for r in rows}),
            "exact": sum(r["served"] == r["argmax"] for r in judged),
            "worst": sorted(judged, key=below, reverse=True)[:4],
            "unsteady": [r for r in rows if not r["steady"]][:8],
            "reference_pass_s": time.time() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--skip-slots", action="store_true")
    ap.add_argument("--control", choices=("float8", "a-pass-fewer"),
                    help="a wrong computation in the program's place, no "
                         "engine: the comparison must exit 1")
    ap.add_argument("--cpu", action="store_true",
                    help="control flow on the CPU at toy widths; no reading")
    a = ap.parse_args(argv)

    if a.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import run as bench_run
    from harness import correct, models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    mpath, tpath = bench_run.ensure_files(name + ("-rehearse" if a.cpu else ""),
                                          model, shape, int(cfg["weights_seed"]))
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_loops needs a TPU (or --cpu for the control flow)")

    seqs = correct.check_prompts(SEED, N_PROMPTS, PROMPT_LEN + STEPS,
                                 shape["vocab_size"])
    if a.control:
        wrong = wrong_computation(model, mpath, a.control)
        got = model.logits_at(mpath, [list(map(int, s)) for s in seqs],
                              range(PROMPT_LEN - 1, PROMPT_LEN + STEPS), **wrong)
        facts = {"control": a.control}
    else:
        got, facts = one_stream(mpath, tpath, seqs)
    one = dict(judge_logits(model, mpath, seqs, got), engine=facts)
    log(f"one stream: rms {one['rms_sigma']:.4f} "
        f"max {one['max_sigma']:.4f} sigma over the steady positions "
        f"({one['steady_share']:.2f} of them; {one['rms_sigma_every_position']:.4f} "
        f"over every position)")
    out = {"config": name, "layers": shape["n_layers"], "one_stream": one}
    ok = one["ok"]
    if a.control:
        fed, chosen = control_tokens(model, mpath, shape["vocab_size"], wrong, a.cpu)
        slots = judge_tokens(model, mpath, fed, chosen)
        log(f"control {a.control}: {slots['tokens']} tokens, {slots['exact']} of "
            f"{slots['steady_tokens']} steady ones the reference's argmax, worst "
            f"{slots['worst_below_max_sigma']:.4f} sigma below the maximum")
        out.update(control=a.control, slots=slots,
                   seen={"logits": not one["ok"], "greedy tokens": not slots["ok"]})
        ok = ok and slots["ok"]
    elif not a.skip_slots:
        fed, chosen, sfacts = slot_tokens(mpath, tpath, cell_argv(name),
                                          shape["vocab_size"], a.cpu)
        slots = dict(judge_tokens(model, mpath, fed, chosen), slot_programs=sfacts)
        log(f"slot programs: {slots['tokens']} greedy tokens, worst "
            f"{slots['worst_below_max_sigma']:.4f} sigma below the maximum, "
            f"{sfacts['mixed_steps']} mixed steps, peak "
            f"{sfacts['peak_bytes'] / 1e9:.2f} GB")
        out["slots"] = slots
        ok = ok and slots["ok"]
    out["ok"] = bool(ok)
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "seen": out.get("seen"),
               "compared": ["logits", "greedy tokens"],
               "tokens": out.get("slots", {}).get("tokens")}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    stem = "check_loops" + (f".{a.control}" if a.control else "")
    with open(os.path.join(ROOT, "chiprun_out", stem + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
