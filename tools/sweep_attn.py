"""Hardware sweep of the attention reads: KV block width of the contiguous
cache's live walk at prefill rows, and (``--paged``) the fused page walk
against the gather form over a paged pool.

Times ``ops.attention`` on one chip at Mistral-7B's attention shapes (32
query and 8 KV heads of 128, 32 layers, a 32k bf16 cache) for a few
``(t, pos)`` points.  Every measurement happens *inside one jitted
``fori_loop``* over the layer index with the blocks sliced from the stacked
cache, exactly like the prefill program runs it.  ``auto`` is the program's
own choice through ``gqa_attention_at`` (so the tool also runs on a tree
without the walk: ``--repo <parent checkout>``); the widths go through
``live_gqa_attention``'s ``block=``.  The program's width is
``attention._kv_chunk(s)`` for every ``t`` (PERF.md §6, PR 29 has the table
this tool gave); judge a candidate in the cell, not here.

``--paged`` times the served steps' read, ``paged_gqa_attention_at``'s two
dense-pool arms, at the served geometries (Mistral-7B: 32 query and 8 KV
heads of 128, 32 layers; OLMoE-1B-7B: 16 and 16 of 128, 16 layers, both over
a 64-page table; LFM2-24B-A2B: 32 and 8 heads of 64, 8 attention layers, a
128-page table, the pool stored two heads to a row as ``pool_rows`` says; 16
slots, 16-token pages out of the cell's pool; ``--geo`` picks some):
``fused`` (``fused_paged_attention``, kernel ``paged_attn_fused``) and
``gather`` (``paged_gather_layer`` + ``_rows_ceiling_attention``) at ``t`` 1
and 16 and a live context of 256 and 1024 tokens a slot (``--ts``, ``--lives``
for others), inside one jitted loop over the layers, each checked against the
gather form (``rel_err``).  ``auto`` is the program's own choice, with the
ledger path it recorded.  PERF.md §6, PR 37 has the table, and the two
per-kv-head reads of the chunk buffer that were timed against the kept body
and not kept; PR 48 has the folded pool's.

``--paged --slots 1,8,16,32`` times the fused walk alone over that many
slots at every live context, and prints for each geometry and ``t`` the
least-squares fit of a launch's time, ``us_launch + us_slot * slots +
us_chunk * slots * chunks`` (a chunk is 8 pages): what a slot costs before
its chunks, which is where a walk that starts cold at every slot pays, apart
from what a chunk costs (PERF.md §6, PR 64 has both tables; Ouro-2.6B's
geometry, 16 and 16 heads of 128 over a 48-page table, is its default there).

``--ring-write`` times a pure-decode step's ring writes (``ops/window.py``):
one token a row into a stacked plane ``(L, B, H, R, Dh)`` at every layer, as
``windows`` (``_write_row`` a row: every call before PR 66, and every call of
more than one token a row), as ``put-kernel`` (``ring_put``: the rows' aligned
windows in one launch) and as ``put-slab`` (``_put_slab``: the layer's slab in
one fused update), at 1 to 32 rows, for the planes of the served cells that
have rings (Falcon-H1's and Granite's ``rk`` / ``rv`` / ``rg`` / ``cz``,
Brumby's three, LFM2's ``cz``), inside one jitted loop over the layers with the
plane donated; ``rule`` is ``_put_form``'s own choice.  Each form's plane is
compared with the windows' bit for bit (``equal``), and ``vmem`` says whether
XLA moved the whole plane into VMEM for the loop (in a served step it did so
around a launch that aliased a plane that fits, which is why the launch asks
for VMEM's scope itself: PERF.md section 6, PR 66 has the table).

``--ring-read`` times the part ``recent`` of a decoded token's state-space read
(``ops/ssm.py``): the XLA form over the whole ring (``_recent``) against the
launch over the live positions (``recent_walk``), us a layer at 1 to 32 slots
and 33 / 64 / 96 live positions a slot, at Falcon-H1's and Granite's widths,
each checked against the XLA form (``rel_err``); ``rule`` is ``_read_form``'s
choice at that many slots (PERF.md section 6, PR 67 has the table).

Usage: python tools/sweep_attn.py [--repo DIR] [--blocks 256,512,1024]
       python tools/sweep_attn.py --ring-write [--rows 1,2,4,8,16,32]
                                  [--geo falcon-h1-34b.rv,...]
       python tools/sweep_attn.py --ring-read [--rows 1,8,16,32]
                                  [--lives 33,64,96] [--geo falcon-h1-34b]
       python tools/sweep_attn.py --paged [--repo DIR] [--geo lfm2-24b-a2b]
                                  [--ts 1,16] [--lives 416,1024,2048]
                                  [--slots 1,8,16,32]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HQ, HKV, DH, LAYERS, S = 32, 8, 128, 32, 32768
POINTS = [(256, 0), (128, 0), (64, 0), (256, 16384), (16, 300), (1, 300)]


# (name, query heads, kv heads, head size, layers, pool pages, table pages):
# the served cells' reads
PAGED_GEOMETRIES = [("mistral-7b", 32, 8, 128, 32, 1032, 64),
                    ("olmoe-1b-7b", 16, 16, 128, 16, 2056, 64),
                    ("lfm2-24b-a2b", 32, 8, 64, 8, 2056, 128),
                    ("ouro-2.6b", 16, 16, 128, 16, 1544, 48)]
PAGED_SLOTS, PAGE = 16, 16


def _median_ms(run, args, reps):
    import jax
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append(time.perf_counter() - t0)
    return (round(1e3 * sorted(times)[len(times) // 2], 3),
            round(1e3 * min(times), 3))


# (name, layers, H, ring, Dh, dtype): the planes a served pure-decode step
# writes one token a row into (32 rows in Falcon-H1's cell, 16 in Granite's and
# LFM2's, 8 in Brumby's)
RING_PLANES = [("falcon-h1-34b.rk", 18, 2, 128, 256, "bfloat16"),
               ("falcon-h1-34b.rv", 18, 32, 128, 128, "bfloat16"),
               ("falcon-h1-34b.rg", 18, 1, 128, 32, "float32"),
               ("falcon-h1-34b.cz", 18, 1, 64, 5120, "bfloat16"),
               ("granite-4.0-h-small.rk", 18, 1, 128, 128, "bfloat16"),
               ("granite-4.0-h-small.rv", 18, 64, 128, 128, "bfloat16"),
               ("granite-4.0-h-small.rg", 18, 1, 128, 128, "float32"),
               ("granite-4.0-h-small.cz", 18, 1, 64, 8448, "bfloat16"),
               ("brumby-14b-base.rk", 40, 8, 128, 128, "bfloat16"),
               ("brumby-14b-base.rg", 40, 1, 128, 8, "float32"),
               ("lfm2-24b-a2b.cz", 30, 1, 64, 2048, "bfloat16")]


def sweep_ring_write(a) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import window

    def windows(ring, new, layer, pos):
        for row in range(new.shape[0]):
            ring = window._write_row(ring, new[row], layer, row, pos[row],
                                     ring.shape[3])
        return ring

    forms = {"windows": windows, "rule": window.ring_write_plane}
    if hasattr(window, "ring_put"):   # a tree without the launch: --repo
        forms.update({
            "put-kernel": functools.partial(window.ring_put, interpret=a.rehearse),
            "put-slab": window._put_slab})
    results = []
    geos = a.geo.split(",") if a.geo else None
    for name, layers, h, r, dh, dt in RING_PLANES:
        if geos and name not in geos:
            continue
        if a.rehearse:
            layers, dh = 2, min(dh, 256)
        passes = max(1, 400 // layers)
        for b in (int(x) for x in (a.rows or "1,2,4,8,16,32").split(",")):
            shape = (layers, b, h, r, dh)
            new = jax.random.normal(jax.random.PRNGKey(1), (b, h, 1, dh),
                                    jnp.bfloat16)
            # rows out of step, as a served step's: every slot class
            pos = jnp.asarray(np.random.RandomState(b).randint(0, 4096, b),
                              jnp.int32)
            ref = None
            for form, fn in forms.items():
                if form == "put-kernel" and dh % 128:
                    continue   # Mosaic copies no part of a 128-lane row

                def run(ring, new_, pos_, fn=fn):
                    # some hundreds of writes a call: a program's dispatch
                    # (0.4 ms) is then a microsecond of a layer's figure
                    return jax.lax.fori_loop(
                        0, passes * layers, lambda i, ring_: fn(
                            ring_, new_, i % layers, pos_ + i), ring)

                obs_dispatch.reset()
                ring = jnp.zeros(shape, jnp.dtype(dt))
                comp = jax.jit(run, donate_argnums=0).lower(
                    ring, new, pos).compile()
                path = sorted(k for k in obs_dispatch.dispatches()
                              if k.startswith("ring/"))
                plane = "[" + ",".join(map(str, shape)) + "]"
                vmem = any(plane in line and "S(1)" in line.split(plane)[1][:40]
                           for line in comp.as_text().splitlines())
                times = []
                for _ in range(a.reps + 1):
                    t0 = time.perf_counter()
                    ring = jax.block_until_ready(comp(ring, new, pos))
                    times.append(time.perf_counter() - t0)
                times = sorted(times[1:])
                rec = {"plane": name, "rows": b, "form": form,
                       "us_a_layer": round(
                           1e6 * times[len(times) // 2] / (passes * layers), 2),
                       "min_us_a_layer": round(
                           1e6 * times[0] / (passes * layers), 2),
                       "layers": layers, "vmem": vmem, "repo": a.repo}
                if form == "rule":
                    rec["ledger"] = path
                if form == "windows":
                    ref = ring
                else:
                    rec["equal"] = bool(jnp.array_equal(ring, ref))
                results.append(rec)
                print(json.dumps(rec), flush=True)
            del ref, ring
    return results


# (name, layers, H, P, G, N): the mixers whose rings a served pure-decode step
# reads (32 slots in Falcon-H1's cell, 16 in Granite's)
MIXERS = [("falcon-h1-34b", 18, 32, 128, 2, 256),
          ("granite-4.0-h-small", 18, 128, 64, 1, 128)]


def sweep_ring_read(a) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import ssm
    from dllama_tpu.ops.retention import FOLD, RING

    forms = {"xla": lambda c, *rest: ssm._recent(c.astype(jnp.float32), *rest),
             "walk": functools.partial(ssm.recent_walk, interpret=a.rehearse)}
    results = []
    geos = a.geo.split(",") if a.geo else None
    for name, layers, h, p, g, n in MIXERS:
        if geos and name not in geos:
            continue
        if a.rehearse:
            layers = 2
        f = ssm.heads_a_row(h, p)
        passes = max(1, 200 // layers)
        for b in (int(x) for x in (a.rows or "1,8,16,32").split(",")):
            keys = jax.random.split(jax.random.PRNGKey(b), 5)
            rk = jax.random.normal(keys[0], (layers, b, g, RING, n), jnp.bfloat16)
            rv = jax.random.normal(keys[1], (layers, b, h // f, RING, f * p),
                                   jnp.bfloat16)
            rg = jax.random.uniform(keys[2], (layers, b, 1, RING, h), jnp.float32,
                                    0.01, 0.1)
            c = jax.random.normal(keys[3], (b, g, 1, n), jnp.bfloat16)
            av = -jax.random.uniform(keys[4], (h,), jnp.float32, 0.5, 2.0)
            for live in (int(x) for x in (a.lives or "33,64,96").split(",")):
                # watermarks out of step, as a served step's: both halves
                base = jnp.asarray(FOLD * np.random.RandomState(b).randint(
                    0, 64, b), jnp.int32)
                pos = base + live - 1
                ref = None
                for form, fn in forms.items():
                    def run(c_, rk_, rv_, rg_, pos_, base_, fn=fn):
                        # each layer's queries depend on the last layer's
                        # output, as in the model: nothing runs side by side
                        def one(i, y):
                            return fn(c_ + (y[:, :g, :, :1] * 0).astype(c_.dtype),
                                      rk_, rv_, rg_, av, i % layers, pos_,
                                      base_)[0]
                        return jax.lax.fori_loop(
                            0, passes * layers, one,
                            jnp.zeros((b, h, 1, p), jnp.float32))

                    med, low = _median_ms(jax.jit(run), (c, rk, rv, rg, pos, base),
                                          a.reps)
                    y = jax.jit(fn)(c, rk, rv, rg, av, jnp.int32(1), pos, base)[0]
                    rec = {"mixer": name, "slots": b, "live": live, "form": form,
                           "us_a_layer": round(1e3 * med / (passes * layers), 2),
                           "min_us_a_layer": round(1e3 * low / (passes * layers), 2),
                           "layers": layers, "repo": a.repo}
                    if form == "xla":
                        ref = y
                        rec["rule"] = ssm._read_form(rv.shape, b, 1)
                    else:
                        rec["rel_err"] = float(jnp.max(jnp.abs(y - ref))
                                               / jnp.max(jnp.abs(ref)))
                    results.append(rec)
                    print(json.dumps(rec), flush=True)
            del rk, rv, rg
    return results


def sweep_paged(a, att) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch

    def gather(q, pk, pv, layer, table, pos):
        dh = q.shape[-1]
        return att._rows_ceiling_attention(
            q, att.paged_gather_layer(pk, layer, table, dh=dh),
            att.paged_gather_layer(pv, layer, table, dh=dh), pos)

    forms = {"auto": att.paged_gqa_attention_at, "gather": gather,
             "fused": att.fused_paged_attention}
    ps = PAGE
    slots = [int(x) for x in a.slots.split(",")] if a.slots else \
        [2 if a.rehearse else PAGED_SLOTS]
    if a.slots:   # the fit is the walk's; the gather form is its check
        del forms["auto"]
    lives = [int(x) for x in a.lives.split(",")] if a.lives else \
        (32, 64) if a.rehearse else (256, 1024)
    results = []
    geos = a.geo.split(",") if a.geo else ["ouro-2.6b"] if a.slots else None
    for geo, hq, hkv, dh, layers, n_pages, maxp in PAGED_GEOMETRIES:
        if geos and geo not in geos:
            continue
        if a.rehearse:
            layers, maxp = 2, 24 if a.slots else 8
            n_pages = 1 + max(slots) * maxp
        if n_pages <= max(slots) * maxp:
            raise SystemExit(f"{geo}'s pool is under {max(slots)} tables")
        kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (layers, n_pages, ps) + att.pool_rows(hkv, dh)
        pk = jax.random.normal(kk, shape, jnp.bfloat16)
        pv = jax.random.normal(kv, shape, jnp.bfloat16)
        pages = np.random.RandomState(0).permutation(np.arange(1, n_pages))
        for t, b in ((int(x), b) for x in a.ts.split(",") for b in slots):
            table = jnp.asarray(pages[:b * maxp].reshape(b, maxp), jnp.int32)
            q = jax.random.normal(kq, (b, hq, t, dh), jnp.bfloat16)
            for ctx in lives:
                # every slot's last query token is the context's last
                pos = jnp.full((b,), ctx - t, jnp.int32)
                ref = None
                for name, fn in forms.items():
                    if a.rehearse and name == "fused":
                        fn = functools.partial(fn, interpret=True)

                    @jax.jit
                    def run(q_, pk_, pv_, table_, pos_, fn=fn):
                        # each layer's queries depend on the last layer's
                        # output, as in the model
                        return jax.lax.fori_loop(
                            0, layers, lambda i, acc: fn(
                                q_ + (acc * 0).astype(q_.dtype), pk_, pv_, i,
                                table_, pos_).astype(jnp.float32),
                            jnp.zeros(q_.shape, jnp.float32))

                    obs_dispatch.reset()
                    args = (q, pk, pv, table, pos)
                    med, low = _median_ms(run, args, a.reps)
                    path = sorted(k for k in obs_dispatch.dispatches()
                                  if k.startswith("kv_"))
                    obs_dispatch.reset()
                    one = jax.jit(fn)(q, pk, pv, jnp.int32(layers - 1), table,
                                      pos).astype(jnp.float32)
                    if name == "gather":
                        ref = one
                    rec = {"geometry": geo, "t": t, "slots": b, "live": ctx,
                           "form": name, "ms_all_layers": med, "min_ms": low,
                           "layers": layers, "repo": a.repo}
                    if name == "auto":
                        rec["ledger"] = path
                    if ref is not None and name != "gather":
                        rec["rel_err"] = float(jnp.abs(one - ref).max()
                                               / jnp.abs(ref).max())
                    results.append(rec)
                    print(json.dumps(rec), flush=True)
    if a.slots:
        results += _slot_fits(results, att._WALK_PAGES * ps, a.repo)
    return results


def _slot_fits(results: list[dict], chunk: int, repo: str) -> list[dict]:
    """A launch of the fused walk as ``us_launch + us_slot * slots + us_chunk
    * slots * chunks``, least squares over a geometry's and a ``t``'s points
    (the fastest repetition of each: the walk's own time, a program's
    dispatch in the constant)."""
    import numpy as np

    fits = []
    walks = [r for r in results if r["form"] == "fused"]
    for geo, t in sorted({(r["geometry"], r["t"]) for r in walks}):
        pts = [r for r in walks if (r["geometry"], r["t"]) == (geo, t)]
        chunks = [-(-r["live"] // chunk) for r in pts]
        x = np.array([[1, r["slots"], r["slots"] * c]
                      for r, c in zip(pts, chunks)], float)
        y = np.array([1e3 * r["min_ms"] / r["layers"] for r in pts])
        if np.linalg.matrix_rank(x) < 3:
            continue   # one slot count or one depth: nothing to tell apart
        coef = np.linalg.lstsq(x, y, rcond=None)[0]
        fit = {"geometry": geo, "t": t, "fit": "launch+slot+chunk",
               "points": len(pts), "us_launch": round(coef[0], 2),
               "us_slot": round(coef[1], 3), "us_chunk": round(coef[2], 3),
               "worst_residual_us": round(float(np.abs(x @ coef - y).max()), 2),
               "repo": repo}
        fits.append(fit)
        print(json.dumps(fit), flush=True)
    return fits


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=HERE)
    ap.add_argument("--paged", action="store_true",
                    help="the paged pool's reads: fused page walk vs gather")
    ap.add_argument("--ts", default="1,16", help="--paged: query tokens a slot")
    ap.add_argument("--lives", default="",
                    help="--paged: live contexts a slot, in tokens; "
                         "--ring-read: live positions a slot (33,64,96)")
    ap.add_argument("--geo", default="", help="--paged: geometries, by name")
    ap.add_argument("--slots", default="",
                    help="--paged: slot counts; times the fused walk and fits "
                         "a launch as launch + slot + chunk")
    ap.add_argument("--ring-write", action="store_true",
                    help="a pure-decode step's ring writes: windows vs the "
                         "launch vs the slab")
    ap.add_argument("--ring-read", action="store_true",
                    help="a decoded token's recent rows: the XLA form over the "
                         "ring vs the launch over the live positions")
    ap.add_argument("--rows", default="",
                    help="--ring-write: rows a call (1,2,4,8,16,32); "
                         "--ring-read: slots (1,8,16,32)")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="control flow only, on any backend, at 2 layers")
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.repo))
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops import attention as att

    if not a.rehearse and jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU"}))
        sys.exit(1)
    if a.paged:
        _write(sweep_paged(a, att), "sweep_attn_paged.jsonl")
        return
    if a.ring_write:
        _write(sweep_ring_write(a), "sweep_ring_write.jsonl")
        return
    if a.ring_read:
        _write(sweep_ring_read(a), "sweep_ring_read.jsonl")
        return
    layers = 2 if a.rehearse else LAYERS
    widths = [None] + ([int(b) for b in a.blocks.split(",") if b]
                       if hasattr(att, "live_gqa_attention") else [])
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (layers, 1, HKV, S, DH)
    ck = jax.random.normal(kk, shape, jnp.bfloat16)
    cv = jax.random.normal(kv, shape, jnp.bfloat16)
    results = []
    for t, pos in POINTS:
        q = jax.random.normal(kq, (1, HQ, t, DH), jnp.bfloat16)
        for block in widths:
            # the caches are arguments: closed over, 4 GB of them would be
            # baked into the program as constants
            def one(layer, q_, pos_, ck_, cv_):
                if block is None:
                    return att.gqa_attention_at(q_, ck_, cv_, layer, pos_, t)
                return att.live_gqa_attention(q_, ck_, cv_, pos_, layer=layer,
                                              block=block)

            @jax.jit
            def run(q_, pos_, ck_, cv_):
                # each layer's queries depend on the last layer's output,
                # as in the model: nothing is hoisted or run side by side
                return jax.lax.fori_loop(
                    0, layers, lambda i, acc: one(
                        i, q_ + (acc * 0).astype(q_.dtype), pos_, ck_, cv_)
                    .astype(jnp.float32), jnp.zeros(q_.shape, jnp.float32))

            med, low = _median_ms(run, (q, jnp.int32(pos), ck, cv), a.reps)
            rec = {"t": t, "pos": pos, "block": block or "auto",
                   "ms_32_layers": med, "min_ms": low, "repo": a.repo}
            results.append(rec)
            print(json.dumps(rec), flush=True)
    _write(results, "sweep_attn.jsonl")


def _write(results: list[dict], name: str) -> None:
    out = os.path.join(HERE, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, name), "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)


if __name__ == "__main__":
    main()
