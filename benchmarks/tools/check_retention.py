#!/usr/bin/env python3
"""Both engines of the program against the plain float32 reference for a
configuration of power-retention layers (Brumby), whose state is a matrix a kv
head that lags the position clock: ``python3 benchmarks/tools/check_retention.py
--config benchmarks/configs/brumby-14b-base.json``.  On the chip, at the
published widths, the configuration's depth and the served cell's flags,
outside any timed window.

What ``run.py``'s ``correct`` cannot see: its check (a) takes the first token of
32-token prompts, which never reach the state (the newest 32 positions and more
are answered from the ring of recent keys and values), and its seeded gates
decay so fast that nothing older than a few tens of positions moves a logit.
Here:

**The file.**  The configuration's seeded file, written again under another
name with the embedding and the gates redrawn (``_redraw``): every embedding
row gets a shared direction ``e`` of the noise's own length, and each layer's
``wg`` is ``a m / |m|^2`` plus N(0, 0.005), ``a`` uniform in 1.5 .. 4.5 a kv
head and ``m`` the mean of THAT layer's normed input over 256 random tokens,
taken in one pass of the reference that sets each layer's gates as it reaches
it (``models/brumby.py regate``).  ``W_g u`` is then about ``a`` at every depth
and ``log gamma`` lies mostly in -0.01 .. -0.5: not stuck at 0 or 1, so that
the decay, the gate's running sum and what was folded into the state hundreds
of positions ago all move the logits.  (A gate along ``e`` itself, the first
draft, read -0.3 .. -1.06 on the chip: the seeded residual stream grows with
depth and turns away from ``e``, so from the third layer on ``W_g u`` was near
0.)  The ring's own ``log gamma`` (plane ``rg``) is reported.

(a) **the slot programs** (an ``Engine`` with the served cell's flags,
    ``slot_step`` as the scheduler calls it): request A alone in the last
    slot, ``PROMPT_A`` tokens in chunks of 16 with a ragged last one, then
    ``ALONE`` tokens decoded alone; seven neighbours join, one a step, each
    prefilling its own few hundred tokens in mixed steps while A decodes in
    them; all eight decode ``SIDE`` tokens side by side (the packed pure-decode
    step, slots folding in different steps); request C takes A's slot over, at
    position 0 over the state A left, prefills beside the seven and decodes
    ``SIDE`` more; then A's request again alone in the same slot, decoded in
    steps of 16 rows where A's tokens were decoded in steps of one (what
    ``run.py``'s check (b) meets when its solo request runs beside prefilling
    neighbours: the two streams are counted token against token).  The slot
    programs hand out tokens: each greedy token is judged on the reference's
    logits (``harness/correct.py``'s rule).
(b) **the contiguous engine**: a prompt prefilled in calls of 32 (its logits
    are compared), ``GEN`` greedy tokens in decode bursts, the same again
    stopped INSIDE a burst with the next one already written
    (``retention_rewinds{in_ring}`` must count it; where the greedy stream
    repeats itself and yields no token for the first time inside a burst, the
    stopped stream is a sampled one, temperature 1, the same seed twice), a
    second turn prefilled at
    the rewound position and ``STEPS`` tokens decoded one by one (all their
    logits are compared).

(c) **the operator alone** (``dllama_tpu/ops/retention.py`` ``clock``, ``fold``,
    ``write``, ``read``, as ``_retention_block`` calls them) at the published
    head geometry (8 kv heads, 5 query heads each, heads of 128, ``D`` = 9216),
    two rows, ``OP_LEN`` positions of unit-variance ``q``, ``k``, ``v`` and
    ``log gamma`` in -0.2 .. -0.005, through calls of every shape the engines
    make (chunks of 32 and 16, a ragged chunk in its bucket, decoded rows in
    steps of 1 and of 16, a row that rides along), against the attention form
    in float64 numpy: the largest error over every position, as a share of the
    largest output, beside the same walk with the state rounded to bfloat16
    after every call and with the state zeroed.  This is where the state's
    precision on the chip is seen: on the seeded file every position's normed
    input is nine tenths one shared vector, so all values are nearly alike, the
    quotient renormalises whatever is dropped, and a ZEROED state moves the
    logits by 1e-3 sigma, thirty times less than bfloat16 activations do
    (readings below (a) and (b)'s: reported, and no judge of the state).

The reference (``models/<name>.py logits_at``: the ATTENTION form, float32,
``highest`` precision, no state, no ring, no ``phi``) runs one forward over all
the sequences, right-padded to one length.  Beside it, two counter-readings of
the same reference with the tokens behind each query's last 32 positions put
through a WRONG state: dropped (``state="zero"``) and carried block to block in
bfloat16 (``state="bfloat16"``); each is read against the true reference at the
compared positions, so the tolerances are shown to separate them, or not.

Tolerances.  Logits: ``check_logits.py``'s two, in sigmas of the reference's
logits over the vocabulary at that position: rms 0.04 and max 0.2.  Tokens: the
served token's reference logit within 0.08 sigma of the reference's maximum.

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
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

TOL_RMS_SIGMA = 0.04
TOL_MAX_SIGMA = 0.2
CHUNK, PROMPT_A, PROMPT_C, ALONE, SIDE = 16, 333, 277, 40, 24
NEIGHBOURS = (301, 212, 263, 230, 288, 205, 247)
PROMPT, GEN, TURN, STEPS, BURST = 333, 44, 19, 4, 16
OP_LEN, OP_TOL = 420, 1e-5   # (c): float32 sums of D = 9216 products
SEED = 51              # of the tokens and of the redrawn gates


def log(msg: str) -> None:
    print(f"check_retention: {msg}", file=sys.stderr, flush=True)


def _redraw(model, shape: dict, src: str, dst: str) -> None:
    """``src`` copied to ``dst`` with the embedding and the gates redrawn
    (module docstring)."""
    import shutil

    import numpy as np

    if os.path.exists(dst):
        return
    shutil.copyfile(src, dst + ".part")
    by_name = {t[0]: t for t in model.plan(shape)}
    rng = np.random.default_rng([SEED, 1])
    dim = shape["dim"]
    e = rng.standard_normal(dim).astype(np.float32)
    e /= np.linalg.norm(e)
    raw = np.memmap(dst + ".part", np.uint8, "r+")

    def f32(name):
        _, shp, _, off, nbytes = by_name[name]
        return raw[off:off + nbytes].view(np.float32).reshape(shp)

    emb = f32("token_embedding")
    length = float(np.linalg.norm(emb[:1024], axis=1).mean())
    for lo in range(0, emb.shape[0], 8192):
        emb[lo:lo + 8192] += length * e
    raw.flush()

    def draw(i, mean):
        a = rng.uniform(1.5, 4.5, (shape["n_kv_heads"], 1)).astype(np.float32)
        wg = a * mean[None, :] / float(mean @ mean) + 0.005 * rng.standard_normal(
            (shape["n_kv_heads"], dim)).astype(np.float32)
        f32(f"layers.{i}.wg")[:] = wg
        return wg

    toks = rng.integers(3, shape["vocab_size"], (1, 256)).tolist()
    model.regate(dst + ".part", toks, draw)
    raw.flush()
    del raw
    os.replace(dst + ".part", dst)


def slots(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (a): ``(rows, facts)``; a row is ``(what, tokens fed, {position:
    logits}, {position: greedy token})``."""
    import jax
    import numpy as np
    from check_state import _load

    from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
    from dllama_tpu.runtime.engine import Engine

    t0 = time.time()
    chat, args, flag = _load(mpath, tpath, argv)
    params, cfg, mesh, dt = chat.params, chat.cfg, chat.mesh, chat.cache.rk.dtype
    del chat           # its one-sequence cache
    gc.collect()
    engine = Engine(cfg, params, mesh=mesh, batch=int(flag["--batch-slots"]),
                    seq_len=args.max_seq_len, kv_dtype=dt)
    load_s = time.time() - t0
    b = engine.batch
    rng = random.Random(f"{SEED}/slots")
    zeros_f = np.zeros((b,), np.float32)
    folds0 = obs_metrics.RETENTION_FOLDS.json_value()

    class Seq:
        def __init__(self, what, slot, n_prompt):
            self.what, self.slot, self.pos = what, slot, 0
            self.prompt = [rng.randrange(3, vocab) for _ in range(n_prompt)]
            self.fed, self.chosen = [], {}

        def take(self, n):  # the next n tokens to feed
            if self.pos < len(self.prompt):
                return self.prompt[self.pos:self.pos + n]
            return [self.chosen[self.pos - 1]]

    kinds = {"decode": 0, "mixed": 0}

    def step(seqs, wide=False) -> None:
        """One slot step over ``seqs``: a sequence still in its prompt feeds a
        chunk, the others their last token; ``CHUNK`` rows if any prefills (or
        ``wide``: a decoded row rides in a step of ``CHUNK``, as it does beside
        a prefilling neighbour)."""
        rows = {s: s.take(CHUNK) for s in seqs}
        t = CHUNK if wide or any(len(r) > 1 or s.pos < len(s.prompt)
                                 for s, r in rows.items()) else 1
        kinds["mixed" if t > 1 else "decode"] += 1
        tk = np.zeros((b, t), np.int32)
        pos_rows = np.zeros((b,), np.int32)
        n_valid = np.zeros((b,), np.int32)
        for s, r in rows.items():
            tk[s.slot, :len(r)] = r
            pos_rows[s.slot], n_valid[s.slot] = s.pos, len(r)
        out = np.asarray(engine.slot_step(
            tk, pos_rows, n_valid, temps_np=zeros_f, topps_np=zeros_f + 1.0))
        for s, r in rows.items():
            s.fed += r
            s.pos += len(r)
            if s.pos >= len(s.prompt):
                s.chosen[s.pos - 1] = int(out[0, s.slot])

    a = Seq("A: alone, then beside seven", b - 1, PROMPT_A)
    while a.pos < PROMPT_A:
        step([a])
    for _ in range(ALONE):
        step([a])
    live = [a]
    for i, n in enumerate(NEIGHBOURS[:b - 1]):      # one more joins each step
        live.append(Seq(f"neighbour {i}", i, n))
        step(live)
    while any(s.pos < len(s.prompt) for s in live):
        step(live)
    for _ in range(SIDE):
        step(live)
    c = Seq("C: A's slot, reused", a.slot, PROMPT_C)    # over the state A left
    live = [c] + live[1:]
    while c.pos < PROMPT_C:
        step(live)
    for _ in range(SIDE):
        step(live)
    gates = np.asarray(engine.cache.rg, np.float32)
    gates = gates[gates != 0]
    marks = np.asarray(engine.cache.rw).ravel().tolist()
    # what run.py's check (b) asks of the cell: A's request again, alone in a
    # reused slot, its tokens decoded in steps of CHUNK rows (fifteen of
    # padding) where A's were decoded in steps of one; the watermark follows the
    # clock alone, so the stream should be A's own, token for token
    d = Seq("D: A again, decoded in steps of 16", a.slot, PROMPT_A)
    d.prompt = list(a.prompt)
    while d.pos < PROMPT_A:
        step([d])
    for _ in range(ALONE):
        step([d], wide=True)
    same = sum(d.chosen[p] == a.chosen[p]
               for p in range(PROMPT_A - 1, PROMPT_A + ALONE))
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "slots": b, "chunk": CHUNK, "steps": kinds,
             "cache_planes": {k: list(v.shape)
                              for k, v in engine.cache.planes().items()},
             "watermarks": marks,
             "folds_counted": obs_metrics.RETENTION_FOLDS.json_value() - folds0,
             "log_gamma_in_the_rings": {
                 f"p{q}": float(np.percentile(gates, q)) for q in (5, 25, 50, 75, 95)},
             "alone_in_steps_of_1_and_of_16": {"tokens": ALONE + 1, "equal": same},
             "slot_state": engine.slot_state, "peak_bytes": peak,
             "ledger": obs_dispatch.summary_line()}
    rows = [(s.what, s.fed, {}, s.chosen) for s in [a] + live[1:] + [c]]
    del engine, params
    gc.collect()
    return rows, facts


def contiguous(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (b): ``(rows, facts)`` as :func:`slots`, logits too."""
    import jax
    import numpy as np
    from check_state import _load

    from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics

    t0 = time.time()
    engine, _, _ = _load(mpath, tpath, argv)
    load_s = time.time() - t0
    rng = random.Random(f"{SEED}/stream")
    prompt = [rng.randrange(3, vocab) for _ in range(PROMPT)]
    lg, _ = engine.prefill(prompt)
    first = {PROMPT - 1: np.asarray(lg, np.float32)[0]}
    engine.reset()
    t1 = time.time()
    gen = [t for t, _ in engine.generate_stream(
        prompt, PROMPT + GEN, temperature=0.0, chunk=BURST)][PROMPT:]
    stream_s = time.time() - t1
    if len(gen) != GEN:
        raise SystemExit(f"check_retention: {len(gen)} tokens of {GEN} came back")
    row_a = ("bursts", prompt + gen[:-1], first,
             {PROMPT - 1 + i: t for i, t in enumerate(gen)})
    # the end-of-sequence id: a token the stream yields INSIDE a burst for the
    # first time, the latest such (check_state.py has why)
    def inside(gen):  # ... and not in the last burst: the next one is written
        return [i for i, t in enumerate(gen[:GEN - BURST])
                if i >= 1 and t not in gen[:i] and (i - 1) % BURST != BURST - 1]

    temp = 0.0
    if not inside(gen):  # a greedy stream that repeats itself: sample one
        temp = 1.0
        engine.reset()
        gen = [t for t, _ in engine.generate_stream(
            prompt, PROMPT + GEN, temperature=temp, chunk=BURST)][PROMPT:]
    if not inside(gen):
        raise SystemExit("check_retention: the stream yields no token for the "
                         "first time inside a burst; no id to stop at")
    stop = inside(gen)[-1]
    before = obs_metrics.RETENTION_REWINDS.json_value()
    engine.reset()
    again = [t for t, _ in engine.generate_stream(
        prompt, PROMPT + GEN, temperature=temp, chunk=BURST,
        eos_ids=(gen[stop],))][PROMPT:]
    if again != gen[:stop + 1] or engine.pos != PROMPT + stop:
        raise SystemExit(f"check_retention: the stream stopped at {len(again)} "
                         f"tokens, position {engine.pos}; expected "
                         f"{stop + 1}, {PROMPT + stop}")
    turn = [rng.randrange(3, vocab) for _ in range(TURN + STEPS)]
    fed = prompt + gen[:stop] + turn
    base = PROMPT + stop
    held = (engine._state_lo, engine._state_hi)
    lg, _ = engine.prefill(turn[:TURN])
    logits = {base + TURN - 1: np.asarray(lg, np.float32)[0]}
    for k, tok in enumerate(turn[TURN:]):
        lg, _ = engine.decode_one(int(tok))
        logits[base + TURN + k] = np.asarray(lg, np.float32)[0]
    after = obs_metrics.RETENTION_REWINDS.json_value()
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "prompt": PROMPT, "generated": GEN,
             "stream_s": stream_s, "stopped_at": stop, "rewound_to": base,
             "stopped_stream_temperature": temp,
             "state_held_at_the_rewind": held,
             "rewinds": {"before": before, "after": after},
             "peak_bytes": peak, "ledger": obs_dispatch.summary_line()}
    del engine
    gc.collect()
    return [row_a, ("second turn", fed, logits, {})], facts


def operator(cpu: bool) -> dict:
    """Part (c): the three forms of the operator through its planes against
    the attention form in float64, with a bfloat16 and a zeroed state beside."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import retention as rt

    b, g, m, dh = (2, 2, 5, 16) if cpu else (2, 8, 5, 128)
    rng = np.random.default_rng([SEED, 3])
    n = OP_LEN
    q = rng.standard_normal((b, g * m, n, dh)).astype(np.float32)
    k, v = rng.standard_normal((2, b, g, n, dh)).astype(np.float32)
    lg = -rng.uniform(0.005, 0.2, (b, g, n)).astype(np.float32)
    cs = np.cumsum(lg.astype(np.float64), -1)
    want = np.zeros((b, g * m, n, dh))
    for h in range(g * m):
        s = np.einsum("btd,bjd->btj", q[:, h].astype(np.float64),
                      k[:, h // m].astype(np.float64)) / np.sqrt(dh)
        a = np.tril(s * s * np.exp(np.minimum(
            cs[:, h // m][:, :, None] - cs[:, h // m][:, None, :], 0.0)))
        want[:, h] = np.einsum("btj,bjd->btd", a, v[:, h // m].astype(np.float64)
                               ) / (a.sum(-1, keepdims=True) + rt.EPS)
    layer = jnp.int32(0)

    @jax.jit
    def call(planes, q, k, v, lg, pos, n_real):
        w, wn = rt.clock(planes["rw"], pos, q.shape[2], n_real)
        rs, rz = rt.fold(planes["rs"], planes["rz"], planes["rk"], planes["rv"],
                         planes["rg"], layer, w, wn)
        rk, rv, rg = rt.write(planes["rk"], planes["rv"], planes["rg"], k, v, lg,
                              layer, pos)
        y = rt.read(q, rs, rz, rk, rv, rg, layer, pos, wn)
        return y, dict(rs=rs, rz=rz, rk=rk, rv=rv, rg=rg,
                       rw=wn.reshape(planes["rw"].shape))

    # (rows, rows that hold a token): every shape the engines make
    calls = [(32, 32)] * 4 + [(32, 19), (16, 16), (16, 7)] + [(1, 1)] * 40 \
        + [(16, 1)] * 40 + [(16, 0), (16, 16), (32, 32)] + [(1, 1)] * 70
    calls += [(16, 1)] * (n - sum(c[1] for c in calls) - 15)

    def walk(state):
        planes = rt.init_planes(1, b, g, dh, jnp.float32)
        pos, worst = 0, 0.0
        for t, n_real in calls:
            if state == "zero":
                planes = dict(planes, rs=jnp.zeros_like(planes["rs"]),
                              rz=jnp.zeros_like(planes["rz"]))
            sl = slice(pos, pos + t)
            y, planes = call(planes, q[:, :, sl], k[:, :, sl], v[:, :, sl],
                             lg[:, :, sl], jnp.full((b,), pos, jnp.int32),
                             jnp.full((b,), n_real, jnp.int32))
            if state == "bfloat16":
                planes = dict(planes, **{
                    key: planes[key].astype(jnp.bfloat16).astype(jnp.float32)
                    for key in ("rs", "rz")})
            if n_real:
                worst = max(worst, float(np.abs(
                    np.asarray(y, np.float64)[:, :, :n_real]
                    - want[:, :, pos:pos + n_real]).max()))
            pos += n_real
        return worst / float(np.abs(want).max()), pos, \
            np.asarray(planes["rw"]).ravel().tolist()

    err, pos, marks = walk("")
    return {"heads": [g, m, dh], "D": rt.state_dim(dh), "positions": pos,
            "calls": len(calls), "watermarks": marks,
            "max_error_share": err, "tol": OP_TOL,
            "bfloat16_state_max_error_share": walk("bfloat16")[0],
            "zero_state_max_error_share": walk("zero")[0],
            "ok": err <= OP_TOL and marks == [rt.watermark(0, pos)] * b}


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
    from check_state import cell_argv
    from harness import correct, models

    cfg = bench_run.load_json(a.config)
    model = models.for_config(cfg)
    if not hasattr(model, "retention_bytes"):
        raise SystemExit("check_retention: this configuration has no retention layers")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    seeded, tpath = bench_run.ensure_files(
        name + ("-rehearse" if a.cpu else ""), model, shape, int(cfg["weights_seed"]))
    mpath = seeded[:-2] + "-gates.m"
    _redraw(model, shape, seeded, mpath)
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_retention needs a TPU (or --cpu for the control flow)")
    vocab = shape["vocab_size"]
    served = cell_argv(name, True)
    rows_a, facts_a = slots(mpath, tpath, served, vocab)
    log(f"slot programs: loaded in {facts_a['load_s']:.1f} s, A again in steps "
        f"of 16: {facts_a['alone_in_steps_of_1_and_of_16']}, steps "
        f"{facts_a['steps']}, watermarks {facts_a['watermarks']}, log gamma "
        f"{facts_a['log_gamma_in_the_rings']}, peak "
        f"{facts_a['peak_bytes'] / 1e9:.2f} GB")
    rows_b, facts_b = contiguous(mpath, tpath, served, vocab)
    log(f"contiguous engine: loaded in {facts_b['load_s']:.1f} s, stopped inside "
        f"a burst at token {facts_b['stopped_at']}, rewinds {facts_b['rewinds']}, "
        f"{GEN} tokens in {facts_b['stream_s']:.2f} s, peak "
        f"{facts_b['peak_bytes'] / 1e9:.2f} GB")
    facts_c = operator(a.cpu)
    log(f"the operator alone: {facts_c}")
    rows = rows_a + rows_b
    width = max(len(r[1]) for r in rows)
    padded = [[int(t) for t in r[1]] + [3] * (width - len(r[1])) for r in rows]
    # what is compared, and the positions deep enough for a state to matter
    places = sorted({p for r in rows for p in list(r[2]) + list(r[3])})
    t0 = time.time()
    ref = dict(zip(places, np.moveaxis(model.logits_at(mpath, padded, places), 1, 0)))
    ref_s = time.time() - t0
    wrong = {state: dict(zip(places, np.moveaxis(
        model.logits_at(mpath, padded, places, state=state), 1, 0)))
        for state in ("zero", "bfloat16")}

    def sig(got, want):
        diff, sigma = got - want, float(want.std())
        return (float(np.abs(diff).max() / sigma),
                float(np.sqrt((diff ** 2).mean()) / sigma))

    out_rows, ok = [], True
    for i, (what, fed, logits, chosen) in enumerate(rows):
        for pos, got in sorted(logits.items()):
            mx, rms = sig(got, ref[pos][i])
            r = {"sequence": what, "position": pos, "compared": "logits",
                 "max_sigma": mx, "rms_sigma": rms,
                 "argmax_equal": bool(got.argmax() == ref[pos][i].argmax())}
            for state in wrong:
                r[state + "_state_max_sigma"], r[state + "_state_rms_sigma"] = sig(
                    wrong[state][pos][i], ref[pos][i])
            r["ok"] = mx <= TOL_MAX_SIGMA and rms <= TOL_RMS_SIGMA
            out_rows.append(r)
        for pos, tok in sorted(chosen.items()):
            want = ref[pos][i]
            sigma = float(want.std())
            r = {"sequence": what, "position": pos, "compared": "greedy token",
                 "below_max_sigma": float((want.max() - want[tok]) / sigma),
                 "exact": bool(tok == want.argmax())}
            for state in wrong:
                other = int(wrong[state][pos][i].argmax())
                r[state + "_state_below_max_sigma"] = float(
                    (want.max() - want[other]) / sigma)
                r[state + "_state_max_sigma"] = sig(wrong[state][pos][i], want)[0]
            r["ok"] = r["below_max_sigma"] <= correct.TOL_SIGMA
            out_rows.append(r)
    for r in out_rows:
        if not r["ok"]:
            log(f"OUT OF TOLERANCE: {r}")
        ok = ok and r["ok"]
    if not facts_c["ok"]:
        log(f"the operator alone is out of tolerance: {facts_c}")
        ok = False
    rewinds = facts_b["rewinds"]
    counted = (rewinds["after"] or {}).get("in_ring", 0) \
        - (rewinds["before"] or {}).get("in_ring", 0)
    if counted < 1:
        log("the rewind inside a burst was not counted in retention_rewinds")
        ok = False
    lg = [r for r in out_rows if r["compared"] == "logits"]
    tk = [r for r in out_rows if r["compared"] == "greedy token"]
    deep = [r for r in tk if r["position"] >= 128]      # past a fold
    out = {"ok": bool(ok), "config": name, "layers": shape["n_layers"],
           "logits": {"positions": len(lg),
                      "max_sigma": max(r["max_sigma"] for r in lg),
                      "rms_sigma": max(r["rms_sigma"] for r in lg),
                      "tol_max_sigma": TOL_MAX_SIGMA, "tol_rms_sigma": TOL_RMS_SIGMA,
                      **{f"{s}_state_{k}_sigma": [
                          min(r[f"{s}_state_{k}_sigma"] for r in lg),
                          max(r[f"{s}_state_{k}_sigma"] for r in lg)]
                         for s in wrong for k in ("max", "rms")}},
           "tokens": {"positions": len(tk), "past_a_fold": len(deep),
                      "exact": sum(r["exact"] for r in tk),
                      "worst_below_max_sigma": max(r["below_max_sigma"] for r in tk),
                      "tol_sigma": correct.TOL_SIGMA,
                      **{f"{s}_state_logits_max_sigma": [
                          min(r[f"{s}_state_max_sigma"] for r in deep),
                          float(np.median([r[f"{s}_state_max_sigma"] for r in deep])),
                          max(r[f"{s}_state_max_sigma"] for r in deep)]
                         for s in wrong},
                      **{f"{s}_state_tokens_out_of_tolerance": sum(
                          r[f"{s}_state_below_max_sigma"] > correct.TOL_SIGMA
                          for r in deep) for s in wrong}},
           "rewinds_in_ring": counted, "reference_pass_s": ref_s,
           "operator": facts_c,
           "slot_programs": facts_a, "contiguous": facts_b, "rows": out_rows}
    if a.cpu:  # a CPU run carries no reading
        out = {"ok": bool(ok), "rehearsal": True, "logit_positions": len(lg),
               "token_positions": len(tk), "rewinds_in_ring": counted,
               "steps": facts_a["steps"], "watermarks": facts_a["watermarks"],
               "alone_in_steps_of_1_and_of_16":
                   facts_a["alone_in_steps_of_1_and_of_16"],
               "operator": facts_c}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_retention.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
