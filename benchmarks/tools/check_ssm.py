#!/usr/bin/env python3
"""Both engines of the program against the plain float32 reference for a
configuration with a state-space mixer beside attention in every block
(Falcon-H1), whose state is a matrix a head that lags the position clock:
``python3 benchmarks/tools/check_ssm.py --config
benchmarks/configs/falcon-h1-34b.json``.  On the chip, at the published widths,
the configuration's depth and the served cell's flags, outside any timed window.

What ``run.py``'s ``correct`` cannot see: its check (a) takes the first token of
32-token prompts, which never reach the state (the newest 32 positions and more
are answered from the rings), and the seeded file hides the state twice over
(below).  Here:

**The files.**  Both parts run on the configuration's seeded file AND on a copy
(``_expose``) in which two things are drawn again:

* **the one-sided nibbles of every Q40 block.**  ``harness/mformat.py`` draws a
  block's sixteen packed bytes as two 63-bit integers (``rng.integers(0, 1 <<
  63)``), so the top bit of bytes 7 and 15 is never set: values 23 and 31 of
  every block of 32 come out of -7 .. 0 and not of -7 .. 7, mean -3.5 steps
  where every other value's is 0.  Every row of every Q40 matrix therefore sums
  to about ``-0.028 * n / 16`` (-8.9 at ``n`` = 5120 against a spread of 2.4;
  -37 at 21504 against 5.1), whatever goes in with a mean comes out as one
  shift of EVERY output, 3.6 to 7.3 times as strong as it went in, and two
  blocks into the stack the residual stream is one fixed direction: eight
  random prompts serve one token at a top-two gap of 1.12 sigma, the
  reference's logits of any two prompts correlate 1.000 (0.06 once the two
  nibbles are drawn like the other thirty; the reference at these widths and a
  vocabulary of 8192, on the CPU), and a logit tolerance in sigmas of THAT
  spread passes a zeroed state.  The finding is the harness's (every
  configuration's file has it); the cure there is a ``benchmark`` issue's.
* ``ssm_a_log`` and ``ssm_dt_bias``, to the published initialisation's ranges:
  ``A`` uniform in 1 .. 16 (``A_log`` its log) and ``dt`` log-uniform in 0.001
  .. 0.1 (``dt_bias`` its inverse softplus; the seeded ``dt`` rows add about
  N(0, 0.13) before the softplus, so ``dt`` stays within a fifth of what was
  drawn), a head at a time: a position decays by ``e^-0.001`` to ``e^-1.6``
  where the seeded vectors (``mformat`` draws every f32 vector ``1 + N(0,
  0.02)``: ``A`` = -2.72, ``dt`` = 1.31) let nothing older than three
  positions through.  The rings' own ``dt`` (plane ``rg``) is reported.

On that copy the prompts serve distinct tokens at top-two gaps of about 0.2
sigma, and what the state holds moves the logits: the reference with
everything behind the watermark left out of its sum reads 0.35 sigma rms and
1.3 to 1.5 sigma max from itself after 200 tokens (CPU, as above): nine and
seven times the tolerances.

(a) **the contiguous engine** (the program's loader, ``Engine`` and mesh
    through ``cli.load_stack``): a prompt of ``PROMPT`` tokens (past the ring:
    the state is read) prefilled in calls of 32 (its logits are compared);
    ``STEPS`` seeded tokens decoded one by one (all their logits are compared);
    the clock set back ``BACK`` positions, what a burst's overshoot past an
    end token leaves, and another token decoded there (its logits are compared
    with the truncated sequence's; ``ssm_state_rewinds{in_ring}`` must count
    it); then the prompt again through ``generate_stream`` in bursts of 16,
    ``GEN`` greedy tokens judged on the reference's logits.
(b) **the slot programs** (a paged ``Engine`` with the served cell's flags,
    ``slot_step`` as the scheduler calls it): request A alone in the last slot,
    ``PROMPT`` tokens in chunks of 16 with a ragged last one, then ``STEPS``
    tokens decoded alone; every other slot's neighbour joins, four a step, each
    prefilling its own 40 to 150 tokens in mixed (packed) steps while A decodes
    in them; all decode ``STEPS`` tokens side by side (the packed pure-decode
    step at 32 rows); request C takes A's slot over, at position 0 over the
    state and the pages A left, prefills beside the others and decodes
    ``STEPS`` more.  The slot programs hand out tokens: each greedy token is
    judged on the reference's logits (``harness/correct.py``'s rule).
    **And the state planes themselves** (``Watch``): for A's slot (then C's,
    over what A left) and the first neighbour's, in the first and the last
    layer, the rows the engine wrote into its rings (``B``, ``x``, ``dt`` as
    the planes hold them) are read back after every step, and after every step
    that moved a watched slot's watermark the state plane ``rs`` of that layer
    and slot is compared with those rows folded in float64: the engine's own
    inputs, so the activations' bfloat16 is not in the comparison and a state
    that is not carried, not reset, or kept in fewer bits shows as itself.
    Tolerance ``PLANE_TOL``, as a share of a head's largest state value.

(c) **the operator alone** (``dllama_tpu/ops/ssm.py`` ``fold``, ``write``,
    ``read`` under ``retention.clock``, as ``_ssm_block`` calls them) at the
    published head geometry (32 heads of 128 in 2 groups, a state of 256 rows),
    two rows, ``OP_LEN`` positions, every head its own pace (``dt`` 0.001 .. 0.1
    and ``A`` 1 .. 16 spread evenly and paired at random), through calls of
    every shape the engines make (chunks of 32 and 16, a ragged chunk in its
    bucket, decoded rows in steps of 1 and of 16, a row that rides along),
    against the attention form in float64 numpy: the largest error of any head
    as a share of that head's largest output, beside the same walk with the
    state rounded to bfloat16 after every call and with the state zeroed.
    First readings on the chip (PR 60): the float32 walk reads 4.1e-6 (sums
    over 256 state rows and 128 ring rows at ``highest`` precision), a bfloat16
    state 1.6e-3 and a zeroed one 0.72; ``OP_TOL`` 2e-5 is five times the first
    and an eightieth of the second.

``--control bfloat16-state`` and ``--control zero-state`` put a wrong state in
the program's place (``ops/ssm.py fold`` replaced in the loaded program: what
it returns rounded to bfloat16, or zeroed) and run parts (a) and (b) on the
exposed copy through both engines; the tool then exits 1, as it must, and a
control that passes is the finding.  ``zero-state`` must read at least twice
the tolerance in (a)'s logits, in (b)'s tokens, in (b)'s state planes and in
(c); ``bfloat16-state`` in (b)'s state planes and in (c).  **Why no logit
judges the state's precision**: rounding the state to bfloat16 moves what it
contributes by 2^-9 of itself, and that contribution is a third of a sigma:
under a thousandth of a sigma, where the engines' bfloat16 activations read
0.012 rms against the float32 reference on their own; its reading is printed
beside and judges nothing.  First readings on the chip (PR 60, the exposed
copy): the right state 0.066 sigma max and 0.0125 rms over (a)'s ten positions,
1045 distinct tokens in (b)'s 1055, the worst 0.054 sigma under the reference's
maximum, state planes 1.3e-5; zeroed 1.58 max and 0.338 rms (8.4 times the
tolerance), a token 1.64 sigma under (20 times), planes 1.0; bfloat16 planes
4.6e-3 (15 times), logits 0.060 / 0.0126.

Tolerances.  Logits: ``check_logits.py``'s two, in sigmas of the reference's
logits over the vocabulary at that position: rms 0.04 and max 0.2 (derived there
for 60 layers of bfloat16 activations; this configuration has 18 blocks of two
mixers).  Tokens: the served token's reference logit within 0.08 sigma of the
reference's maximum.  State planes: ``PLANE_TOL``; operator: ``OP_TOL``; both
with their reasons where they are set.

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
CHUNK, PROMPT, PROMPT_C, STEPS, BACK, GEN = 16, 200, 150, 8, 5, 24
OP_LEN, OP_TOL = 420, 2e-5   # (c): float32 sums over 256 state rows and 128 ring rows
# (b)'s state planes: a fold weighs position j by exp(total - cumsum_j), a
# difference of two float32 running sums of dt * A that reach 230 on the seeded
# file (3.6 a position, 64 positions).  First readings on the chip (PR 60):
# 1.03e-4 of a head's largest value on the seeded file, 1.3e-5 on the exposed
# copy (dt * A up to 1.6); a state rounded to bfloat16 cannot read under 2^-9 =
# 1.95e-3 (half a unit in the last of eight bits, at the largest value's binade).
# Three times the first, a sixth of the last.
PLANE_TOL = 3e-4
SEED = 60              # of the tokens and of the redrawn vectors


def log(msg: str) -> None:
    print(f"check_ssm: {msg}", file=sys.stderr, flush=True)


def _expose(model, shape: dict, src: str, dst: str) -> None:
    """``src`` copied to ``dst`` with the one-sided nibbles of every Q40 block
    and every layer's ``ssm_a_log`` and ``ssm_dt_bias`` drawn again (module
    docstring)."""
    import shutil
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from harness import mformat

    if os.path.exists(dst):
        return
    t0 = time.time()
    shutil.copyfile(src, dst + ".part")
    plan = model.plan(shape)
    by_name = {t[0]: t for t in plan}
    raw = np.memmap(dst + ".part", np.uint8, "r+")

    def even(i: int) -> None:
        """Values 23 and 31 of every block of tensor ``i`` (the high nibbles of
        packed bytes 7 and 15) drawn as ``mformat`` draws the other thirty:
        uniform over 1 .. 15 but for 8 twice as likely."""
        _, _, _, off, nbytes = plan[i]
        rng = np.random.default_rng([SEED, 2, i])
        step = (1 << 26) // mformat.Q40_BLOCK * mformat.Q40_BLOCK
        for lo in range(0, nbytes, step):
            blocks = raw[off + lo:off + min(lo + step, nbytes)].reshape(
                -1, mformat.Q40_BLOCK)
            for byte in (2 + 7, 2 + 15):
                hi = rng.integers(0, 16, len(blocks), np.uint8)
                hi[hi == 0] = 8
                blocks[:, byte] = (blocks[:, byte] & 0x0F) | (hi << 4)

    with ThreadPoolExecutor(8) as ex:
        list(ex.map(even, [i for i, t in enumerate(plan) if t[2] == mformat.Q40]))
    rng = np.random.default_rng([SEED, 1])
    h = shape["ssm_heads"]
    for i in range(shape["n_layers"]):
        for name, vals in (
                ("ssm_a_log", np.log(rng.uniform(1.0, 16.0, h))),
                ("ssm_dt_bias", np.log(np.expm1(np.exp(rng.uniform(
                    np.log(0.001), np.log(0.1), h)))))):
            _, _, _, off, nbytes = by_name[f"layers.{i}.{name}"]
            raw[off:off + nbytes].view(np.float32)[:] = vals.astype(np.float32)
    raw.flush()
    del raw
    os.replace(dst + ".part", dst)
    log(f"wrote {dst} in {time.time() - t0:.0f} s")


class Watch:
    """One slot's mixer in one layer as the engine itself fed it: the rows of
    ``B``, ``x`` and ``dt`` read back from the rings after the step that wrote
    them, and the state plane held to those rows folded in float64."""

    def __init__(self, engine, layer: int, slot: int):
        import numpy as np
        self.engine, self.layer, self.slot = engine, layer, slot
        self.a = -np.exp(np.asarray(engine.params["ssm_a_log"][layer], np.float64))
        self.w, self.checked, self.worst = 0, 0, 0.0
        self.b, self.x, self.dt = [], [], []

    def wrote(self, pos: int, n: int) -> None:
        """The step fed this slot ``n`` rows from position ``pos`` (0: a new
        tenant, over what the last one left)."""
        import numpy as np
        cache, (li, s) = self.engine.cache, (self.layer, self.slot)
        if pos == 0:
            self.w, self.b, self.x, self.dt = 0, [], [], []
        at = (pos + np.arange(n)) % cache.rk.shape[3]
        self.b += list(np.asarray(cache.rk[li, s], np.float64)[:, at].transpose(1, 0, 2))
        self.x += list(np.asarray(cache.rv[li, s], np.float64)[:, at].transpose(1, 0, 2))
        self.dt += list(np.asarray(cache.rg[li, s, 0], np.float64)[at])
        w = int(np.asarray(cache.rw).ravel()[s])
        if w == self.w:
            return
        self.w = w
        b, x, dt = np.array(self.b[:w]), np.array(self.x[:w]), np.array(self.dt[:w])
        h, g = x.shape[1], b.shape[1]
        la = dt * self.a                                         # (w, H)
        coef = np.exp(la.sum(0) - np.cumsum(la, 0)) * dt         # decay from j to w
        want = np.einsum("jhn,jhp->hnp", np.repeat(b, h // g, axis=1)
                         * coef[..., None], x)
        got = np.asarray(cache.rs[li, s], np.float64)
        share = np.abs(got - want).max(axis=(1, 2)) / np.abs(want).max(axis=(1, 2))
        self.checked += 1
        self.worst = max(self.worst, float(share.max()))


def _control(kind: str) -> None:
    """Replace ``ops/ssm.py fold`` in the loaded program by a wrong one: the
    state it returns zeroed, or rounded to bfloat16's eight bits
    (``reduce_precision``: the TPU's compiler drops a pair of converts inside a
    program, and a control made of one read, to the last digit, what the right
    state reads: PR 60's first chip runs)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops import ssm
    real = ssm.fold

    def fold(rs, *rest):
        out = real(rs, *rest)
        if kind == "zero-state":
            return jnp.zeros_like(out)
        return jax.lax.reduce_precision(out, exponent_bits=8, mantissa_bits=7)

    ssm.fold = fold


def contiguous(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (a): ``(rows, facts)``; a row is ``(what, tokens fed, {position:
    logits}, {position: greedy token})``."""
    import jax
    import numpy as np
    from check_state import _load

    from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics

    t0 = time.time()
    engine, _, _ = _load(mpath, tpath, argv)
    load_s = time.time() - t0
    if not engine.cfg.has_ssm:
        raise SystemExit("check_ssm: this configuration has no state-space mixer")
    rng = random.Random(f"{SEED}/stream")
    prompt = [rng.randrange(3, vocab) for _ in range(PROMPT)]
    more = [rng.randrange(3, vocab) for _ in range(STEPS + 1)]
    lg, _ = engine.prefill(prompt)
    logits = {PROMPT - 1: np.asarray(lg, np.float32)[0]}
    for k, tok in enumerate(more[:STEPS]):
        lg, _ = engine.decode_one(int(tok))
        logits[PROMPT + k] = np.asarray(lg, np.float32)[0]
    held = (engine._state_lo, engine._state_ring_lo, engine._state_hi)
    dt = np.asarray(engine.cache.rg, np.float32)
    dt = dt[dt != 0]
    before = obs_metrics.SSM_STATE_REWINDS.json_value()
    engine.pos -= BACK                      # a burst's overshoot, rewound over
    lg, _ = engine.decode_one(int(more[STEPS]))
    back = {engine.pos - 1: np.asarray(lg, np.float32)[0]}
    after = obs_metrics.SSM_STATE_REWINDS.json_value()
    engine.reset()
    t1 = time.time()
    gen = [t for t, _ in engine.generate_stream(
        prompt, PROMPT + GEN, temperature=0.0, chunk=CHUNK)][PROMPT:]
    stream_s = time.time() - t1
    if len(gen) != GEN:
        raise SystemExit(f"check_ssm: {len(gen)} tokens of {GEN} came back")
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "prompt": PROMPT, "stream_s": stream_s,
             "state_held_before_the_rewind": held,
             "watermark": int(np.asarray(engine.cache.rw).ravel()[0]),
             "dt_in_the_rings": {f"p{q}": float(np.percentile(dt, q))
                                 for q in (5, 25, 50, 75, 95)},
             "rewinds": {"before": before, "after": after},
             "peak_bytes": peak, "ledger": obs_dispatch.summary_line()}
    rows = [("one by one", prompt + more[:STEPS], logits, {}),
            ("rewound", prompt + more[:STEPS - BACK] + more[STEPS:], back, {}),
            ("bursts", prompt + gen[:-1], {},
             {PROMPT - 1 + i: t for i, t in enumerate(gen)})]
    del engine
    gc.collect()
    return rows, facts


def slots(mpath: str, tpath: str, argv: list[str], vocab: int):
    """Part (b): ``(rows, facts)`` as :func:`contiguous`, tokens only."""
    import jax
    import numpy as np
    from check_state import _load

    from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
    from dllama_tpu.runtime.engine import Engine

    t0 = time.time()
    chat, args, flag = _load(mpath, tpath, argv)
    params, cfg, mesh, dt = chat.params, chat.cfg, chat.mesh, chat.cache.k.dtype
    del chat           # its one-sequence cache
    gc.collect()
    engine = Engine(cfg, params, mesh=mesh, batch=int(flag["--batch-slots"]),
                    seq_len=args.max_seq_len, kv_dtype=dt,
                    kv_pages=int(flag["--kv-pages"]),
                    kv_page_size=int(flag["--kv-page-size"]))
    load_s = time.time() - t0
    b, ps = engine.batch, engine.kv_page_size
    rng = random.Random(f"{SEED}/slots")
    zeros_f = np.zeros((b,), np.float32)
    table = np.zeros((b, engine.max_pages_per_slot), np.int32)
    pages = list(range(engine.kv_pages - 1, 0, -1))      # page 0 is scratch
    folds0 = obs_metrics.SSM_FOLDS.json_value()
    watches = [Watch(engine, layer, slot)
               for layer in sorted({0, cfg.n_layers - 1}) for slot in (b - 1, 0)]

    class Seq:
        def __init__(self, what, slot, n_prompt):
            self.what, self.slot, self.pos = what, slot, 0
            self.prompt = [rng.randrange(3, vocab) for _ in range(n_prompt)]
            self.fed, self.chosen = [], {}
            need = -(-(n_prompt + 3 * STEPS + 2) // ps)
            pages.extend(int(p) for p in table[slot] if p)   # the last tenant's
            table[slot] = 0
            table[slot, :need] = [pages.pop() for _ in range(need)]

        def take(self, n):  # the next n tokens to feed
            if self.pos < len(self.prompt):
                return self.prompt[self.pos:self.pos + n]
            return [self.chosen[self.pos - 1]]

    kinds = {"decode": 0, "mixed": 0}

    def step(seqs) -> None:
        """One slot step over ``seqs``: a sequence still in its prompt feeds a
        chunk, the others their last token; ``CHUNK`` rows if any prefills."""
        rows = {s: s.take(CHUNK) for s in seqs}
        t = CHUNK if any(s.pos < len(s.prompt) for s in rows) else 1
        kinds["mixed" if t > 1 else "decode"] += 1
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
            for w in watches:
                if w.slot == s.slot:
                    w.wrote(s.pos, len(r))
            s.fed += r
            s.pos += len(r)
            if s.pos >= len(s.prompt):
                s.chosen[s.pos - 1] = int(out[0, s.slot])

    a = Seq("A: alone, then beside the others", b - 1, PROMPT)
    while a.pos < PROMPT:
        step([a])
    for _ in range(STEPS):
        step([a])
    live = [a]
    for i in range(b - 1):                          # four more join each step
        live.append(Seq(f"neighbour {i}", i, rng.randrange(40, 151)))
        if i % 4 == 3 or i == b - 2:
            step(live)
    while any(s.pos < len(s.prompt) for s in live):
        step(live)
    for _ in range(STEPS):
        step(live)
    c = Seq("C: A's slot, reused", a.slot, PROMPT_C)    # over what A left
    live = [c] + live[1:]
    while c.pos < PROMPT_C:
        step(live)
    for _ in range(STEPS):
        step(live)
    peak = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    facts = {"load_s": load_s, "slots": b, "chunk": CHUNK, "steps": kinds,
             "cache_planes": {k: list(v.shape)
                              for k, v in engine.cache.planes().items()},
             "watermarks": np.asarray(engine.cache.rw).ravel().tolist(),
             "folds_counted": obs_metrics.SSM_FOLDS.json_value() - folds0,
             "state_planes": {
                 "watched": [[w.layer, w.slot] for w in watches],
                 "folds_checked": sum(w.checked for w in watches),
                 "max_error_share": max(w.worst for w in watches), "tol": PLANE_TOL},
             "slot_state": engine.slot_state, "peak_bytes": peak,
             "ledger": obs_dispatch.summary_line()}
    rows = [(s.what, s.fed, {}, s.chosen) for s in [a] + live[1:] + [c]]
    del engine, params, watches
    gc.collect()
    return rows, facts


def operator(cpu: bool) -> dict:
    """Part (c): the operator through its planes (``ops/ssm.py`` ``fold``,
    ``write``, ``read`` under ``retention.clock``, as ``_ssm_block`` calls them)
    against the attention form in float64, with a bfloat16 and a zeroed state
    beside: where the state's precision at the published head geometry is seen,
    whatever the seeded logits let through."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import retention as rt, ssm

    b, h, p, g, n_state = (2, 4, 16, 2, 24) if cpu else (2, 32, 128, 2, 256)
    m = h // g
    rng = np.random.default_rng([SEED, 3])
    n = OP_LEN
    c, bm = rng.standard_normal((2, b, g, n, n_state)).astype(np.float32) \
        / np.float32(np.sqrt(n_state))
    x = rng.standard_normal((b, h, n, p)).astype(np.float32)
    # every head its own pace, the published initialisation's ranges spread
    # evenly and paired at random: dt 0.001 .. 0.1 (a fifth of jitter a
    # position), A 1 .. 16; the slow heads are where a state shows
    dt = (rng.permutation(np.geomspace(0.001, 0.1, h))[None, :, None]
          * rng.uniform(0.8, 1.2, (b, h, n))).astype(np.float32)
    a = -rng.permutation(np.linspace(1.0, 16.0, h))
    cs = np.cumsum(dt.astype(np.float64) * a[None, :, None], -1)
    want = np.zeros((b, h, n, p))
    for i in range(h):
        s = np.einsum("btn,bjn->btj", c[:, i // m].astype(np.float64),
                      bm[:, i // m].astype(np.float64))
        w = np.tril(s * np.exp(np.minimum(
            cs[:, i][:, :, None] - cs[:, i][:, None, :], 0.0)))
        want[:, i] = np.einsum("btj,bjp->btp", w * dt[:, i][:, None, :],
                               x[:, i].astype(np.float64))
    layer, a32 = jnp.int32(0), jnp.asarray(a, jnp.float32)

    class Sizes:
        n_layers, ssm_heads, ssm_groups, ssm_state, ssm_head_dim, ssm_channels = \
            1, h, g, n_state, p, 8

    @jax.jit
    def call(planes, c, bm, x, dt, pos, n_real):
        w, wn = rt.clock(planes["rw"], pos, x.shape[2], n_real)
        rs = ssm.fold(planes["rs"], planes["rk"], planes["rv"], planes["rg"], a32,
                      layer, w, wn)
        rk, rv, rg = ssm.write(planes["rk"], planes["rv"], planes["rg"], bm, x,
                               ssm.live_dt(dt.transpose(0, 2, 1), pos, None, n_real),
                               layer, pos)
        y = ssm.read(c, rs, rk, rv, rg, a32, layer, pos, wn)
        return y, dict(planes, rs=rs, rk=rk, rv=rv, rg=rg,
                       rw=wn.reshape(planes["rw"].shape))

    # (rows, rows that hold a token): every shape the engines make
    calls = [(32, 32)] * 4 + [(32, 19), (16, 16), (16, 7)] + [(1, 1)] * 40 \
        + [(16, 1)] * 40 + [(16, 0), (16, 16), (32, 32)] + [(1, 1)] * 70
    calls += [(16, 1)] * (n - sum(k[1] for k in calls) - 15)

    scale = np.abs(want).max(axis=(0, 2, 3))                       # (H,)

    def walk(state):
        planes = ssm.init_planes(Sizes, b, jnp.float32)
        pos, worst = 0, 0.0
        for t, n_real in calls:
            if state == "zero":
                planes = dict(planes, rs=jnp.zeros_like(planes["rs"]))
            sl = slice(pos, pos + t)
            y, planes = call(planes, c[:, :, sl], bm[:, :, sl], x[:, :, sl],
                             dt[:, :, sl], jnp.full((b,), pos, jnp.int32),
                             jnp.full((b,), n_real, jnp.int32))
            if state == "bfloat16":
                planes = dict(planes, rs=planes["rs"].astype(jnp.bfloat16).astype(
                    jnp.float32))
            if n_real:  # a head at a time, as a share of that head's largest output
                err = np.abs(np.asarray(y, np.float64)[:, :, :n_real]
                             - want[:, :, pos:pos + n_real]).max(axis=(0, 2, 3))
                worst = max(worst, float((err / scale).max()))
            pos += n_real
        return worst, pos, np.asarray(planes["rw"]).ravel().tolist()

    with jax.default_matmul_precision("highest"):
        err, pos, marks = walk("")
        low, zero = walk("bfloat16")[0], walk("zero")[0]
    return {"heads": [h, p, g, n_state], "positions": pos, "calls": len(calls),
            "watermarks": marks, "max_error_share": err, "tol": OP_TOL,
            "bfloat16_state_max_error_share": low,
            "zero_state_max_error_share": zero,
            "controls_fail_by_twice": bool(min(low, zero) >= 2 * OP_TOL),
            "ok": err <= OP_TOL and marks == [rt.watermark(0, pos)] * b
            and min(low, zero) >= 2 * OP_TOL}


def judge(model, mpath: str, rows) -> tuple[list[dict], float]:
    """Every compared position against the reference: ``(rows, seconds)``."""
    import numpy as np

    from harness import correct

    width = max(len(r[1]) for r in rows)
    padded = [[int(t) for t in r[1]] + [3] * (width - len(r[1])) for r in rows]
    places = sorted({p for r in rows for p in list(r[2]) + list(r[3])})
    t0 = time.time()
    ref = dict(zip(places, np.moveaxis(model.logits_at(mpath, padded, places), 1, 0)))
    ref_s = time.time() - t0
    out = []
    for i, (what, _, logits, chosen) in enumerate(rows):
        for pos, got in sorted(logits.items()):
            want = ref[pos][i]
            diff, sigma = got - want, float(want.std())
            mx = float(np.abs(diff).max() / sigma)
            rms = float(np.sqrt((diff ** 2).mean()) / sigma)
            out.append({"sequence": what, "position": pos, "compared": "logits",
                        "max_sigma": mx, "rms_sigma": rms,
                        "argmax_equal": bool(got.argmax() == want.argmax()),
                        "over_tol": max(mx / TOL_MAX_SIGMA, rms / TOL_RMS_SIGMA)})
        for pos, tok in sorted(chosen.items()):
            want = ref[pos][i]
            below = float((want.max() - want[tok]) / float(want.std()))
            out.append({"sequence": what, "position": pos, "token": int(tok),
                        "compared": "greedy token", "below_max_sigma": below,
                        "exact": bool(tok == want.argmax()),
                        "over_tol": below / correct.TOL_SIGMA})
    for r in out:
        r["ok"] = r["over_tol"] <= 1.0
    return out, ref_s


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
        raise SystemExit("check_ssm: this configuration has no state-space mixer")
    shape = bench_run.model_shape(model, cfg, a.cpu)
    name = os.path.splitext(os.path.basename(a.config))[0]
    seeded, tpath = bench_run.ensure_files(
        name + ("-rehearse" if a.cpu else ""), model, shape, int(cfg["weights_seed"]))
    exposed = seeded[:-2] + "-exposed.m"
    _expose(model, shape, seeded, exposed)
    import jax
    if not a.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_ssm needs a TPU (or --cpu for the control flow)")
    vocab = shape["vocab_size"]
    served = cell_argv(name, True)
    out, ok = {"config": name, "layers": shape["n_layers"], "control": a.control}, True
    out["operator"] = operator(a.cpu)       # before any control replaces the fold
    log(f"the operator alone: {out['operator']}")
    ok = ok and out["operator"]["ok"]
    if a.control:
        _control(a.control)
    files = {"seeded": seeded, "exposed": exposed}
    for label in (["exposed"] if a.control else list(files)):
        mpath = files[label]
        rows, facts_a = contiguous(mpath, tpath, served, vocab)
        log(f"{label}: contiguous engine loaded in {facts_a['load_s']:.1f} s, dt "
            f"{facts_a['dt_in_the_rings']}, rewinds {facts_a['rewinds']}, peak "
            f"{facts_a['peak_bytes'] / 1e9:.2f} GB")
        rows_b, facts_b = slots(mpath, tpath, served, vocab)
        planes = facts_b["state_planes"]
        log(f"{label}: slot programs loaded in {facts_b['load_s']:.1f} s, steps "
            f"{facts_b['steps']}, folds {facts_b['folds_counted']}, state planes "
            f"{planes}, peak {facts_b['peak_bytes'] / 1e9:.2f} GB")
        judged, ref_s = judge(model, mpath, rows + rows_b)
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
        fine = all(r["ok"] for r in judged) and counted >= 1 \
            and planes["folds_checked"] > 0 and planes["max_error_share"] <= PLANE_TOL
        ok = ok and fine
        out[label] = {
            "ok": fine, "worst_over_tol": max(r["over_tol"] for r in judged),
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
                     "max_error_share"] / PLANE_TOL,
                 "operator (c)": out["operator"][
                     a.control.split("-")[0] + "_state_max_error_share"] / OP_TOL}
        must = list(reads) if a.control == "zero-state" \
            else ["state planes (b)", "operator (c)"]
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
    with open(os.path.join(ROOT, "chiprun_out", f"check_ssm{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    for label in files:
        if label in out:
            out[label] = {k: v for k, v in out[label].items() if k != "rows"}
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
