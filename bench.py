"""Benchmark stages: decode, prefill and serving-path throughput.

``python bench.py --attempt <name>`` runs ONE stage in this process and
prints one JSON object as its last stdout line:
    {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N, "backend": ...}

Stage names: a model config from ``dllama_tpu/synth.py`` (``llama2-7b``,
``llama3-8b``, ``llama2-13b``, ``tinyllama-1.1b``, ``llama2-7b-long``)
optionally suffixed ``-b8`` / ``-q8kv`` / ``-q8w`` / ``-profile`` / ``-cN``,
``llama2-7b-cli`` / ``-prefill``, and the serving stages ``-sched4`` /
``-prefix4`` / ``-pressure4`` / ``-overlap4`` / ``-fused4`` / ``-spec4`` /
``-tp4sched4``.  Every stage of a real config is a chip stage: it fails
when JAX finds no TPU, and never prints a CPU number under a device
name.  The ``cpu-tiny*`` stages exercise the same code on the CPU backend
at a toy size for the tests; their output is a count/parity check, not a
speed.  Run chip stages through the chip tool; there is no orchestrator
here (the cell grid is ROADMAP S0/D1).

Baseline is the reference's best published single-node Llama-2-7B Q40
number — 101.81 ms/token = 9.82 tok/s on a c3d-highcpu-30 VM (BASELINE.md).
The timing loop is greedy (temperature 0 → on-device argmax).  Weights
are zero-valued packed buffers: decode timing is value-independent and 7B
f32 host materialization (~27 GB) is avoided.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from dllama_tpu.synth import model_cfg as _model_cfg
from dllama_tpu.synth import synth_model_files as _synth_model_files

BASELINE_7B_TOKS = 9.82  # BASELINE.md — 101.81 ms/token, 1× c3d-highcpu-30
BASELINE_13B_TOKS = 5.43  # BASELINE.md — 184.19 ms/token, 1× c3d-highcpu-30


def _vs_baseline(toks, baseline):
    """The one headline-vs-reference helper: tok/s over the published
    reference tok/s for EVERY stage (ms/token stages convert to tok/s
    before calling).  ``None`` — never a crash — when the stage has no
    baseline to compare against."""
    if not baseline or not isinstance(toks, (int, float)):
        return None
    return round(toks / baseline, 2)


# ---------------------------------------------------------------------------
# Stages (each runs in its own process; last stdout line is a JSON object)
# ---------------------------------------------------------------------------

def _zero_q40_params(cfg, codec="q40"):
    """Params with packed quantized matmul weights (``codec`` "q40" or
    "q80"), built as zero device buffers
    (no host-side f32 materialization).  Matches the quantized loader's
    single-chip layout (load_params fuse=True): fused wqkv everywhere,
    fused w13 for dense FFNs, packed expert stacks for MoE."""
    import jax.numpy as jnp
    from dllama_tpu.models.params import param_shapes
    from dllama_tpu.ops.q40 import QTensor, padded_n

    shapes = dict(param_shapes(cfg))
    L, D = cfg.n_layers, cfg.dim
    # fused wqkv, as the quantized loader produces (load_params fuse=True)
    shapes["wqkv"] = (L, D, (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_size)
    for k in ("wq", "wk", "wv"):
        del shapes[k]
    qkeys = {"wqkv", "wo", "wcls"}
    if cfg.is_moe:
        qkeys |= {"up", "gate", "down"}
    else:
        shapes["w13"] = (L, D, 2 * cfg.hidden_dim)
        for k in ("w1", "w3"):
            del shapes[k]
        qkeys |= {"w13", "w2"}

    params = {}
    for k, shape in shapes.items():
        if k in qkeys:
            *lead, n, d = shape
            np_ = padded_n(n)
            if codec == "q80":
                from dllama_tpu.ops.q8 import Q8Tensor
                params[k] = Q8Tensor(
                    jnp.zeros((*lead, np_, d), jnp.int8),
                    jnp.zeros((*lead, np_ // 32, d), jnp.uint16), (n, d))
            else:
                params[k] = QTensor(
                    jnp.zeros((*lead, np_ // 2, d), jnp.uint8),
                    jnp.zeros((*lead, np_ // 32, d), jnp.uint16), (n, d))
        else:
            params[k] = jnp.zeros(shape, jnp.float32 if k.startswith("rms") else cfg.dtype)
    return params


def _run_cli_bench(name, steps=320, chunk=32):
    """Drive `dllama inference` end-to-end (loader → Engine →
    generate_stream → G/I/T print) and parse its run averages."""
    import re
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    mpath, tpath = _synth_model_files(name, os.environ.get("BENCH_TMP")
                                      or tempfile.gettempdir())
    cmd = [sys.executable, "-m", "dllama_tpu", "inference", "--model", mpath,
           "--tokenizer", tpath, "--prompt", "hello hello hello", "--steps",
           # warmup == steps: the warmup pass replays the exact chunk-size
           # sequence of the timed pass, so every program is compiled before
           # timing starts
           str(steps), "--chunk", str(chunk), "--warmup", str(steps),
           "--temperature", "0", "--seed", "0"]
    # the parent stage must not have claimed the chip yet: the CLI child
    # needs it (a chip belongs to one process at a time)
    r = subprocess.run(cmd, cwd=here, stdout=subprocess.PIPE, text=True,
                       env=dict(os.environ, PYTHONPATH=here + os.pathsep
                                + os.environ.get("PYTHONPATH", "")),
                       timeout=780)
    out = r.stdout
    sys.stderr.write("\n".join(out.splitlines()[-8:]) + "\n")
    if r.returncode != 0:
        raise RuntimeError(f"CLI bench rc={r.returncode}")
    m = re.search(r"Avg generation time:\s+([0-9.]+) ms", out)
    if not m:
        raise RuntimeError("CLI bench output had no 'Avg generation time'")
    return float(m.group(1))


def _profile_split_stderr(run_once, chunk):
    """Trace one decode chunk and log the compute/collective split — the
    reference's I/T attribution on a real TPU xplane —
    plus the top per-op device times, so every driver-captured bench run
    records where the step time actually goes."""
    try:
        from dllama_tpu.runtime.profiling import split_op_times, traced_op_times

        times = traced_op_times(run_once, steps=1)
        if not times:
            print("bench: profile split unavailable (no xplane tooling/trace)",
                  file=sys.stderr)
            return
        comp, coll = split_op_times(times)
        verdict = ("T≈0 contract holds" if coll < 1.0
                   else f"collectives are {100 * coll / (comp + coll):.1f}% — inspect")
        print(f"bench: profile split over {chunk}-token chunk: "
              f"compute {comp:.1f} ms, collectives {coll:.1f} ms "
              f"({comp / chunk:.2f} ms/token compute; {verdict})", file=sys.stderr)
        top = sorted(times.items(), key=lambda kv: -kv[1])[:6]
        for op, ms in top:
            print(f"bench:   top op {ms:8.2f} ms  {op}", file=sys.stderr)
    except Exception as e:
        print(f"bench: profile split failed ({type(e).__name__}: {str(e)[:120]})",
              file=sys.stderr)


def _require_tpu(name, in_child=False):
    """Every stage but the ``cpu-tiny*`` ones is a chip stage: without a
    TPU it fails before any work rather than print a CPU number under a
    device name.  ``in_child`` asks a short-lived child instead, for the
    stage whose own child needs the chip (a chip belongs to one process
    at a time, so this process must not claim it)."""
    if in_child:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            stdout=subprocess.PIPE, text=True, timeout=300)
        lines = r.stdout.split()
        backend = lines[-1] if r.returncode == 0 and lines else \
            f"unknown: probe exited {r.returncode}"
    else:
        import jax
        backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: {name} is a chip stage and JAX found no "
                         f"TPU (backend {backend})")


def _stage_impl(base):
    """``quant_impl`` for a stage.  The ``cpu-tiny`` stages run the XLA
    path on the CPU backend; a chip stage (``_require_tpu`` has passed)
    takes ``auto``, the static kernel choice (ops/q40.py ``_auto_pallas``):
    a kernel that fails to lower fails the stage."""
    return "xla" if base == "cpu-tiny" else "auto"


def _bench_decode(cfg, chunk=32, n_chunks=10, profile=False, start_pos=0,
                  batch=1, kv_quant=False, codec="q40"):
    """Greedy on-device decode loop; returns avg ms/token over the timed
    chunks (compile + warmup excluded).  ``start_pos`` places the decode
    deep into the cache so long-context runs time attention over a long
    *live* prefix, not an empty one.  ``batch`` > 1 times the lockstep
    multi-stream decode (Engine.generate_batch's hot loop): decode is
    weight-bandwidth-bound at batch 1, so the per-STEP time should stay
    near the batch-1 cost while every step yields ``batch`` tokens —
    returned ms is still per step, so aggregate tok/s = batch·1000/ms."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dllama_tpu.models.transformer import init_kv_cache
    from dllama_tpu.runtime.decode_loop import decode_chunk

    params = _zero_q40_params(cfg, codec)
    cache = init_kv_cache(cfg, batch=batch, quant=kv_quant)

    fn = jax.jit(
        lambda p, c, tok, pos, k: decode_chunk(
            p, cfg, c, tok, pos, k, steps=chunk, temperature=0.0, topp=0.9),
        donate_argnums=(1,))

    tok = jnp.zeros((batch,), jnp.int32)
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    toks, cache, tok, _, _ = fn(params, cache, tok, jnp.int32(start_pos), key)
    np.asarray(toks)  # compile+warmup
    print(f"compile+warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    # depth-1 pipelined schedule — the one Engine.generate_stream ships:
    # chunk i+1 is enqueued (device-carried token) before chunk i's ids
    # are fetched, so the timed rate includes the dispatch overlap a real
    # serving loop gets; per-chunk time is fetch-boundary to
    # fetch-boundary (chunk 0 from its dispatch)
    times = []
    boundary = time.perf_counter()
    toks, cache, tok, _, _ = fn(params, cache, tok,
                                jnp.int32(start_pos + chunk), key)
    for i in range(n_chunks):
        nxt = None
        if i + 1 < n_chunks:
            nxt = fn(params, cache, tok,
                     jnp.int32(start_pos + (i + 2) * chunk), key)
            cache, tok = nxt[1], nxt[2]
        np.asarray(toks)  # forces execution; only K int32 ids cross the boundary
        now = time.perf_counter()
        times.append((now - boundary) * 1000 / chunk)
        boundary = now
        if nxt is not None:
            toks = nxt[0]

    if profile:
        state = {"cache": cache, "tok": tok}

        def run_once():
            toks, state["cache"], state["tok"], _, _ = fn(
                params, state["cache"], state["tok"],
                jnp.int32(start_pos + (n_chunks + 1) * chunk), key)
            np.asarray(toks)

        _profile_split_stderr(run_once, chunk)

    # feed the timed chunks into the obs step-latency histogram and log
    # the distribution (stderr) — same buckets the serving layer exports,
    # so a bench number and a /metrics scrape are directly comparable
    from dllama_tpu.obs import dispatch as obs_dispatch, \
        metrics as obs_metrics
    for t in times:
        obs_metrics.ENGINE_GENERATION_MS.observe(t)
    h = obs_metrics.ENGINE_GENERATION_MS.json_value()
    print(f"bench: per-token ms distribution: count={h['count']} "
          f"avg={h['avg']:.3f} (dllama_engine_generation_ms)", file=sys.stderr)
    # per-device HBM residency next to the timing number (the gauge readers
    # are bound at runtime.engine import; {} on backends without allocator
    # stats — absent, not zero)
    from dllama_tpu.runtime import engine as _engine  # noqa: F401
    hbm = obs_metrics.HBM_BYTES_IN_USE.values()
    if hbm:
        peak = obs_metrics.HBM_BYTES_PEAK.values()
        print(f"bench: HBM in use "
              f"{sum(hbm.values()) / 2**30:.2f} GiB over {len(hbm)} "
              f"device(s), peak {sum(peak.values()) / 2**30:.2f} GiB "
              f"(dllama_hbm_bytes_in_use)", file=sys.stderr)
    # and the dispatch ledger: a decode number that fell off the fused
    # Pallas path must say so next to the number it degrades
    print(f"bench: {obs_dispatch.summary_line()}", file=sys.stderr)
    coll = obs_dispatch.collective_line()
    if coll:
        print(f"bench: {coll}", file=sys.stderr)
    return float(np.mean(times))


def _bench_prefill(cfg, T=512, reps=6):
    """Avg ms/token over ``reps`` bucketed prefill forwards (compile +
    warmup excluded).  The cache is NOT donated — each rep rewrites the
    same pos-0 window, and the extra cache copy is noise next to the
    T-token matmul volume."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dllama_tpu.models.transformer import forward_last, init_kv_cache

    params = _zero_q40_params(cfg)
    cache = init_kv_cache(cfg, batch=1)
    fn = jax.jit(lambda p, c, t: forward_last(p, cfg, t, c, jnp.int32(0),
                                              jnp.int32(T - 1)))
    toks = jnp.zeros((1, T), jnp.int32)
    t0 = time.perf_counter()
    logits, _ = fn(params, cache, toks)
    np.asarray(logits)
    print(f"compile+warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(reps):
        logits, _ = fn(params, cache, toks)
        np.asarray(logits)
    return (time.perf_counter() - t0) * 1000 / reps / T


def _bench_sched(cfg, slots=4, max_new=96, tp=1):
    """Continuous-batching aggregate decode throughput (the serving path
    behind ``--batch-slots``, runtime/scheduler.py): ``slots`` staggered
    greedy requests admitted at decode-step granularity over one
    slot-addressable engine, timed first-submit to last-retire.  Contrast
    with the lockstep ``-b8`` attempt: there the batch starts in lockstep;
    here requests JOIN while their neighbors are mid-decode, which is what
    /v1/completions traffic actually looks like.  Returns aggregate
    tok/s (completion tokens only — prefill is inside the window, as it is
    for a real request).

    ``tp`` > 1 runs the same workload on a tensor-parallel mesh (PR-12);
    the dispatch ledger records whether decode collectives took the fused
    ring or psum (their time is read from a device trace, not here)."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    params = _zero_q40_params(cfg)
    eng = Engine(cfg, params,
                 mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]),
                 batch=slots)
    sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0)
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, 8 + 4 * i)]
               for i in range(slots)]
    counts = [0] * slots

    def run(i, delay):
        time.sleep(delay)
        t = sched.submit(prompts[i], max_new)
        counts[i] = sum(1 for _ in t.tokens())

    def wave(stagger):
        ths = [threading.Thread(target=run, args=(i, stagger * i))
               for i in range(slots)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        return time.perf_counter() - t0

    t0 = time.perf_counter()
    wave(0.05)  # compile + warmup: same stagger, so the same shape set
    print(f"compile+warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    elapsed = wave(0.05)
    sched.close()
    total = sum(counts)
    print(f"bench: sched {total} tokens over {slots} staggered requests "
          f"in {elapsed:.2f}s", file=sys.stderr)
    # goodput decomposition (obs/flight.py SlotTimeline + scheduler
    # accounting): where the wall time of the measured wave actually went
    from dllama_tpu.obs import metrics as obs_metrics
    comp = obs_metrics.SCHED_STEP_TIME_MS.json_value()
    if comp:
        split = " ".join(f"{k}={v:.0f}ms" for k, v in sorted(comp.items()))
        print(f"bench: sched goodput "
              f"{obs_metrics.SCHED_GOODPUT_RATIO.value:.3f} ({split})",
              file=sys.stderr)
    # roofline utilization (obs/cost.py): achieved FLOP/s and HBM bytes/s
    # over the backend's peaks — the per-stage economics line
    from dllama_tpu.obs import cost as obs_cost
    perf = obs_cost.summary()
    if perf.get("mfu") is not None or perf.get("mbu") is not None:
        mfu = perf.get("mfu")
        mbu = perf.get("mbu")
        print(f"bench: sched mfu={mfu:.4f}" if mfu is not None
              else "bench: sched mfu=n/a", file=sys.stderr, end="")
        print(f" mbu={mbu:.4f}" if mbu is not None else " mbu=n/a",
              file=sys.stderr, end="")
        print(f" ({perf['peaks'].get('source', '?')} peaks, "
              f"{perf['flops_total'] / 1e9:.2f} GFLOP, "
              f"{perf['hbm_bytes_total'] / 1e9:.3f} GB moved)",
              file=sys.stderr)
    return total / elapsed


def _bench_sched_prefix(cfg, slots=4, max_new=96):
    """Prefix-sharing serving throughput (the paged-KV radix cache,
    runtime/pagepool.py): ``slots`` staggered greedy requests that share
    one long synthetic "system prompt" (128 tokens) ahead of a short
    unique suffix, over a paged engine sized at the same cache-length
    budget as ``_bench_sched``.  The first request prefills the shared
    block; the rest match it in the radix tree at admission, bind the
    cached pages copy-free and prefill only their suffix — the serving
    win ``prefix_tokens_reused_total`` quantifies.  Returns (aggregate
    tok/s, prefix tokens reused)."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    params = _zero_q40_params(cfg)
    page_size = 16
    eng = Engine(cfg, params,
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=slots,
                 kv_pages=slots * (-(-cfg.seq_len // page_size)) + 1,
                 kv_page_size=page_size)
    sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0)
    rng = np.random.RandomState(7)
    system = [int(t) for t in rng.randint(1, cfg.vocab_size, 128)]
    prompts = [system + [int(t) for t in rng.randint(1, cfg.vocab_size, 8)]
               for _ in range(slots)]
    counts = [0] * slots

    def run(i, delay):
        time.sleep(delay)
        t = sched.submit(prompts[i], max_new)
        counts[i] = sum(1 for _ in t.tokens())

    def wave(stagger):
        ths = [threading.Thread(target=run, args=(i, stagger * i))
               for i in range(slots)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        return time.perf_counter() - t0

    from dllama_tpu.obs import metrics as obs_metrics
    t0 = time.perf_counter()
    wave(0.05)  # compile + warmup: same stagger, so the same shape set
    print(f"compile+warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    reused0 = obs_metrics.PREFIX_TOKENS_REUSED.value
    elapsed = wave(0.05)
    reused = obs_metrics.PREFIX_TOKENS_REUSED.value - reused0
    sched.close()
    total = sum(counts)
    print(f"bench: sched-prefix {total} tokens over {slots} staggered "
          f"requests sharing a 128-token prefix in {elapsed:.2f}s "
          f"({reused} prompt tokens bound from cache)", file=sys.stderr)
    return total / elapsed, reused


def _bench_sched_pressure(cfg, slots=4, max_new=96):
    """KV-tiering serving throughput under page pressure (runtime/
    kvtier.py + scheduler grow ladder): the ``-sched4`` staggered
    workload on a paged pool deliberately sized at ~40% of what full
    reservation would demand, with ``--kv-reserve optimistic`` so every
    request seats on prompt-sized pages and grows page-by-page at
    decode.  The pool cannot hold all four requests resident, so the
    grow ladder spills idle-longest victims to the host pool and pages
    them back in as neighbors retire — the run measures what that
    thrash costs relative to an uncontended pool (``-sched4``), while
    greedy decode stays byte-identical.  A full-reservation scheduler
    on this pool could not even admit the workload concurrently.
    Returns (aggregate tok/s, pages spilled, pages paged back in)."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    params = _zero_q40_params(cfg)
    page_size = 16
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, 8 + 4 * i)]
               for i in range(slots)]
    # full-reservation demand for this workload, then size the pool at
    # 40% of it (+1 for the scratch page): optimistic reservation must
    # serve out of a pool that full reservation could not seat
    full_pages = sum(-(-min(len(p) + max_new, cfg.seq_len) // page_size)
                     for p in prompts)
    worst = max(-(-min(len(p) + max_new, cfg.seq_len) // page_size)
                for p in prompts)
    kv_pages = max(int(0.4 * full_pages), worst) + 1
    eng = Engine(cfg, params,
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=slots,
                 kv_pages=kv_pages, kv_page_size=page_size)
    sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0,
                          kv_reserve="optimistic", spill_headroom=16,
                          host_pool_mb=64.0)
    counts = [0] * slots

    def run(i, delay):
        time.sleep(delay)
        t = sched.submit(prompts[i], max_new)
        counts[i] = sum(1 for _ in t.tokens())

    def wave(stagger):
        ths = [threading.Thread(target=run, args=(i, stagger * i))
               for i in range(slots)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        return time.perf_counter() - t0

    from dllama_tpu.obs import metrics as obs_metrics
    t0 = time.perf_counter()
    wave(0.05)  # compile + warmup: same stagger, so the same shape set
    print(f"compile+warmup: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    spilled0 = obs_metrics.KV_PAGES_SPILLED.value
    paged_in0 = obs_metrics.KV_PAGES_PAGED_IN.value
    elapsed = wave(0.05)
    spilled = obs_metrics.KV_PAGES_SPILLED.value - spilled0
    paged_in = obs_metrics.KV_PAGES_PAGED_IN.value - paged_in0
    sched.pool.check()
    sched.close()
    total = sum(counts)
    print(f"bench: sched-pressure {total} tokens over {slots} staggered "
          f"requests on a {kv_pages - 1}-page pool ({full_pages} pages of "
          f"full-reservation demand) in {elapsed:.2f}s "
          f"({spilled} pages spilled, {paged_in} paged back in)",
          file=sys.stderr)
    return total / elapsed, int(spilled), int(paged_in)


def _bench_sched_overlap(cfg, slots=4, max_new=96):
    """Overlapped-dispatch A/B (the two-deep pipeline in
    runtime/scheduler.py): ``slots`` short prompts submitted together so
    the workload is pure-decode steady state — the regime where the
    speculative feed-fed dispatch keeps the device busy while the host
    fans out the previous burst.  Runs the identical workload twice,
    overlap off then on, each on a fresh engine + scheduler, and
    decomposes where the wall time went via the scheduler's goodput
    accounting.  Greedy decode is byte-identical in both modes, so the
    tok/s delta is pure dispatch-pipeline effect.  Returns a dict with
    tok/s, goodput ratio and exposed host_gap share per mode."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.obs import metrics as obs_metrics
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    params = _zero_q40_params(cfg)
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, 8)]
               for _ in range(slots)]

    def run_mode(overlap):
        eng = Engine(cfg, params,
                     mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                     batch=slots)
        sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0,
                              overlap=overlap)
        counts = [0] * slots

        def run(i):
            t = sched.submit(prompts[i], max_new)
            counts[i] = sum(1 for _ in t.tokens())

        def wave():
            ths = [threading.Thread(target=run, args=(i,))
                   for i in range(slots)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return time.perf_counter() - t0

        t0 = time.perf_counter()
        wave()  # compile + warmup: identical shape set
        print(f"compile+warmup ({'overlap' if overlap else 'sync'}): "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        comp0 = dict(obs_metrics.SCHED_STEP_TIME_MS.json_value() or {})
        hidden0 = obs_metrics.SCHED_HOST_GAP_HIDDEN_MS.value
        elapsed = wave()
        comp1 = obs_metrics.SCHED_STEP_TIME_MS.json_value() or {}
        hidden = obs_metrics.SCHED_HOST_GAP_HIDDEN_MS.value - hidden0
        sched.close()
        delta = {k: comp1.get(k, 0.0) - comp0.get(k, 0.0) for k in comp1}
        wall = sum(delta.values()) or 1.0
        mode = {
            "toks": sum(counts) / elapsed,
            "goodput": (delta.get("prefill", 0.0)
                        + delta.get("decode", 0.0)) / wall,
            "host_gap_share": delta.get("host_gap", 0.0) / wall,
            "hidden_host_ms": hidden,
        }
        split = " ".join(f"{k}={v:.0f}ms" for k, v in sorted(delta.items()))
        print(f"bench: sched-overlap {'on' if overlap else 'off'}: "
              f"{mode['toks']:.1f} tok/s, goodput {mode['goodput']:.3f}, "
              f"exposed host_gap {mode['host_gap_share']:.3f} "
              f"(hidden {hidden:.0f}ms; {split})", file=sys.stderr)
        return mode

    return {"sync": run_mode(False), "overlap": run_mode(True)}


def _bench_sched_fused(cfg, slots=4, max_new=96):
    """One-dispatch-decode A/B (the fused page-walk attention kernel in
    ops/attention.py + on-device sampling): the ``-sched4`` pure-decode
    workload on a paged pool, run twice — fused attention off, then
    forced on (``DLLAMA_FUSED_ATTN=on`` on TPU, ``interp`` elsewhere so
    the kernel logic still executes) — each on a fresh engine +
    scheduler, because the env ladder is read lazily at trace time and
    the engine's compile keys include it.  Greedy decode must be
    byte-identical across modes (checked on the emitted streams), so
    the tok/s delta is pure kernel-fusion effect.  The headline signal
    is the dispatch-family count per steady pure-decode step, taken
    from a trace-time ledger probe: reset the ledger on the fresh
    engine, trace one t=1 slot_step, and count the distinct matmul
    (``q40/``/``q8/``) + attention (``kv_``) families it recorded —
    the fused contract is ≤ 2 (one matmul family + ``paged-fused``),
    the unfused gather arm records 3–4.  Returns per-mode dicts plus
    the cross-mode parity verdict."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler

    params = _zero_q40_params(cfg)
    page_size = 16
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, 8)]
               for _ in range(slots)]
    kv_pages = sum(-(-min(len(p) + max_new, cfg.seq_len) // page_size)
                   for p in prompts) + 1
    fused_env = "on" if jax.default_backend() == "tpu" else "interp"

    def run_mode(fused):
        os.environ["DLLAMA_FUSED_ATTN"] = fused_env if fused else "off"
        tag = f"fused={fused_env}" if fused else "fused=off"
        eng = Engine(cfg, params,
                     mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                     batch=slots,
                     kv_pages=kv_pages, kv_page_size=page_size)
        # dispatch-family probe first, on the fresh engine: the ledger
        # records once per compiled call site (trace time), so reset and
        # trace exactly one steady pure-decode executable — a t=1 greedy
        # slot_step over a small page table — and count what it recorded
        obs_dispatch.reset()
        maxp = 2
        ptab = 1 + np.arange(slots * maxp, dtype=np.int32).reshape(
            slots, maxp)
        eng.slot_step(np.ones((slots, 1), np.int32),
                      np.full((slots,), page_size + 1, np.int32),
                      np.ones((slots,), np.int32),
                      temps_np=np.zeros((slots,), np.float32),
                      topps_np=np.full((slots,), 0.9, np.float32),
                      page_tables_np=ptab)
        fams = sorted(k for k in obs_dispatch.dispatches()
                      if k.startswith(("q40/", "q80/", "q8/", "kv_")))
        print(f"bench: sched-fused {tag} steady-decode dispatch "
              f"families ({len(fams)}): {' '.join(fams)}", file=sys.stderr)

        sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0)
        streams = [None] * slots

        def run(i):
            t = sched.submit(prompts[i], max_new)
            streams[i] = list(t.tokens())

        def wave():
            ths = [threading.Thread(target=run, args=(i,))
                   for i in range(slots)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return time.perf_counter() - t0

        t0 = time.perf_counter()
        wave()  # compile + warmup: identical shape set
        print(f"compile+warmup ({tag}): {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        elapsed = wave()
        sched.close()
        mode = {
            "toks": sum(len(s) for s in streams) / elapsed,
            "dispatches_per_step": len(fams),
            "families": fams,
            "streams": streams,
        }
        print(f"bench: sched-fused {tag}: {mode['toks']:.1f} tok/s, "
              f"{len(fams)} dispatch families/step", file=sys.stderr)
        return mode

    prev = os.environ.get("DLLAMA_FUSED_ATTN")
    try:
        off = run_mode(False)
        on = run_mode(True)
    finally:
        if prev is None:
            os.environ.pop("DLLAMA_FUSED_ATTN", None)
        else:
            os.environ["DLLAMA_FUSED_ATTN"] = prev
    parity = on.pop("streams") == off.pop("streams")
    if not parity:
        print("bench: sched-fused GREEDY STREAM MISMATCH between modes",
              file=sys.stderr)
    return {"fused": on, "unfused": off, "parity": parity}


def _bench_sched_spec(cfg, slots=4, max_new=96, spec_k=4):
    """Speculative-decoding A/B (runtime/spec.py + the slot-verify
    dispatch): the ``-sched4`` staggered workload run twice, speculation
    off then on with the prompt-lookup proposer.  Greedy output is
    byte-identical in both modes (the emitted stream is always the
    model's own argmax); the tok/s delta is what the verify window's
    multi-token yield buys when drafts are accepted.  Returns a dict
    with tok/s per mode plus the cumulative accept ratio."""
    import threading

    import jax
    import numpy as np
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler
    from dllama_tpu.runtime.spec import PromptLookupProposer

    params = _zero_q40_params(cfg)
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, 8 + 4 * i)]
               for i in range(slots)]

    def run_mode(spec_on):
        eng = Engine(cfg, params,
                     mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                     batch=slots)
        spec = PromptLookupProposer(vocab=cfg.vocab_size) if spec_on else None
        sched = SlotScheduler(eng, prefill_chunk=16, max_wait_ms=20.0,
                              spec=spec, spec_k=spec_k)
        counts = [0] * slots

        def run(i, delay):
            time.sleep(delay)
            t = sched.submit(prompts[i], max_new)
            counts[i] = sum(1 for _ in t.tokens())

        def wave(stagger):
            ths = [threading.Thread(target=run, args=(i, stagger * i))
                   for i in range(slots)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return time.perf_counter() - t0

        t0 = time.perf_counter()
        wave(0.05)  # compile + warmup: same stagger, so the same shape set
        print(f"compile+warmup (spec {'pld' if spec_on else 'off'}): "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        elapsed = wave(0.05)
        proposed = sched._spec_proposed
        accepted = sched._spec_accepted
        sched.close()
        mode = {
            "toks": sum(counts) / elapsed,
            "accept_ratio": accepted / proposed if proposed else None,
            "proposed": proposed, "accepted": accepted,
        }
        ratio = (f"{mode['accept_ratio']:.3f}"
                 if mode["accept_ratio"] is not None else "n/a")
        print(f"bench: sched-spec {'pld' if spec_on else 'off'}: "
              f"{mode['toks']:.1f} tok/s, accept ratio {ratio} "
              f"({accepted}/{proposed} drafts)", file=sys.stderr)
        return mode

    return {"off": run_mode(False), "spec": run_mode(True)}


def _bank_stage_metrics(name):
    """Append this stage's final metrics-registry snapshot (obs/metrics
    .py, the same families /metrics serves) to the BENCH_METRICS_BANK
    JSONL artifact — stdout stays the one-JSON-line result contract, so
    the observability evidence rides in a side file instead."""
    path = os.environ.get("BENCH_METRICS_BANK")
    if not path:
        return
    try:
        from dllama_tpu.obs import metrics as obs_metrics
        snap = obs_metrics.snapshot_json()
        # provenance stamp: which bench run and which tree produced this
        # row, plus the registry schema it speaks — so perf_sentinel.py
        # can pair rows across rounds without guessing
        line = json.dumps({"stage": name, "ts": round(time.time(), 3),
                           "schema_version": snap.get("schema_version"),
                           "bench_run_id": os.environ.get("BENCH_RUN_ID"),
                           "git_sha": os.environ.get("BENCH_GIT_SHA"),
                           "metrics": snap})
        with open(path, "a") as f:
            f.write(line + "\n")
    except Exception as e:  # noqa: BLE001 — evidence, never the number
        print(f"bench: metrics bank failed for {name}: {e}",
              file=sys.stderr)


def run_attempt(name):
    try:
        _attempt_body(name)
    finally:
        _bank_stage_metrics(name)


def _attempt_body(name):
    if not name.startswith("cpu-tiny"):
        _require_tpu(name, in_child=name == "llama2-7b-cli")
    from dllama_tpu.hostenv import configure_compile_cache
    configure_compile_cache()
    # stages log like the server does (DLLAMA_LOG honored); all dllama
    # logging goes to stderr, so the one-JSON-line stdout contract is
    # untouched
    from dllama_tpu.obs.log import configure as _configure_logging
    _configure_logging()
    import jax

    if name == "llama2-7b-cli":
        ms = _run_cli_bench("llama2-7b")  # jax is claimed only after it
        print(json.dumps({
            "metric": "llama2-7b q40 greedy decode tok/s "
                      "(1 TPU chip, dllama inference CLI end-to-end)",
            "value": round(1000.0 / ms, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(1000.0 / ms, BASELINE_7B_TOKS),
            "backend": jax.default_backend()}))
        return

    if name == "llama2-7b-prefill":
        # prompt-evaluation throughput (the reference's "evaluation" stat,
        # dllama.cpp:45-93; no published number to compare): one bucketed
        # forward over T tokens through the REAL dispatch (quant_impl
        # "auto": on one chip, rows beyond PALLAS_MAX_ROWS take the fused
        # kernel over row blocks, q40._row_block)
        ms = _bench_prefill(_model_cfg("llama2-7b"))
        print(json.dumps({
            "metric": "llama2-7b q40 prefill tok/s (1 TPU chip, T=512)",
            "value": round(1000.0 / ms, 1), "unit": "tok/s",
            "vs_baseline": None, "backend": jax.default_backend()}))
        return

    if name.endswith("-tp4sched4"):
        # tensor-parallel serving (parallel/mesh.py + ops/q40.py): the
        # -sched4 staggered workload on a tp=4 mesh — on CPU 4 of the 8
        # forced virtual devices, on TPU 4 real chips; which reduce ran is
        # in the ledger.  Must be checked before -sched4: the suffix
        # contains it.
        base = name[:-10]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        if len(jax.devices()) < 4:
            print(f"bench: {name}: needs 4 devices, have "
                  f"{len(jax.devices())}", file=sys.stderr)
            raise SystemExit(3)
        toks = _bench_sched(cfg.with_(quant_impl=impl), tp=4)
        print(json.dumps({
            "metric": f"{base} q40 tensor-parallel tp=4 continuous-batching "
                      f"slots=4 aggregate decode tok/s "
                      f"(staggered arrivals, {impl})",
            "value": round(toks, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                toks, BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "backend": jax.default_backend()}))
        return

    if name.endswith("-spec4"):
        # speculative decoding (runtime/spec.py): the -sched4 staggered
        # workload with the prompt-lookup proposer off vs on — the accept
        # ratio says how often drafts verified, the tok/s delta what the
        # multi-token verify yield bought.  Checked before -sched4 with
        # the other sched-suffix stages.
        base = name[:-6]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        ab = _bench_sched_spec(cfg.with_(quant_impl=impl))
        on, off = ab["spec"], ab["off"]
        print(json.dumps({
            "metric": f"{base} q40 speculative-decoding slots=4 aggregate "
                      f"decode tok/s (prompt-lookup drafts, spec_k=4, "
                      f"{impl})",
            "value": round(on["toks"], 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                on["toks"], BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "spec_off_toks": round(off["toks"], 2),
            "spec_speedup": round(on["toks"] / off["toks"], 3)
            if off["toks"] else None,
            "accept_ratio": round(on["accept_ratio"], 3)
            if on["accept_ratio"] is not None else None,
            "drafts_proposed": on["proposed"],
            "drafts_accepted": on["accepted"],
            "backend": jax.default_backend()}))
        return

    if name.endswith("-fused4"):
        # one-dispatch decode (ops/attention.py fused page-walk kernel +
        # runtime/decode_loop.py on-device sampling): the -sched4
        # pure-decode workload on a paged pool, fused attention off vs
        # forced on — greedy streams must be byte-identical, so the
        # tok/s delta is pure fusion; the trace-time ledger probe counts
        # matmul+attention dispatch families per steady decode step
        # (fused contract: ≤ 2, the unfused gather arm records 3–4)
        base = name[:-7]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        ab = _bench_sched_fused(cfg.with_(quant_impl=impl))
        on, off = ab["fused"], ab["unfused"]
        print(json.dumps({
            "metric": f"{base} q40 fused-attention one-dispatch decode "
                      f"slots=4 pure-decode aggregate tok/s (paged pool, "
                      f"{impl})",
            "value": round(on["toks"], 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                on["toks"], BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "unfused_toks": round(off["toks"], 2),
            "fused_speedup": round(on["toks"] / off["toks"], 3)
            if off["toks"] else None,
            "dispatches_per_step": on["dispatches_per_step"],
            "unfused_dispatches_per_step": off["dispatches_per_step"],
            "dispatch_families": on["families"],
            "greedy_parity": ab["parity"],
            "backend": jax.default_backend()}))
        return

    if name.endswith("-sched4"):
        # the continuous-batching serving lever (runtime/scheduler.py):
        # cross-request slot scheduler over the batch engine, staggered
        # arrivals — the number the --batch-slots serving path delivers
        base = name[:-7]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        toks = _bench_sched(cfg.with_(quant_impl=impl))
        print(json.dumps({
            "metric": f"{base} q40 continuous-batching slots=4 aggregate "
                      f"decode tok/s (staggered arrivals, {impl})",
            "value": round(toks, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                toks, BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "backend": jax.default_backend()}))
        return

    if name.endswith("-overlap4"):
        # overlapped dispatch pipeline (runtime/scheduler.py): the -sched4
        # engine in pure-decode steady state, run twice with the two-deep
        # pipeline off then on — the tok/s delta and the exposed-host_gap
        # drop are what the speculative feed-fed dispatch buys
        base = name[:-9]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        ab = _bench_sched_overlap(cfg.with_(quant_impl=impl))
        on, off = ab["overlap"], ab["sync"]
        print(json.dumps({
            "metric": f"{base} q40 overlapped-dispatch slots=4 pure-decode "
                      f"aggregate tok/s (two-deep pipeline on, {impl})",
            "value": round(on["toks"], 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                on["toks"], BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "sync_toks": round(off["toks"], 2),
            "overlap_speedup": round(on["toks"] / off["toks"], 3)
            if off["toks"] else None,
            "goodput_on": round(on["goodput"], 3),
            "goodput_off": round(off["goodput"], 3),
            "host_gap_share_on": round(on["host_gap_share"], 4),
            "host_gap_share_off": round(off["host_gap_share"], 4),
            "hidden_host_ms_on": round(on["hidden_host_ms"], 1),
            "backend": jax.default_backend()}))
        return

    if name.endswith("-pressure4"):
        # KV tiering under page pressure (runtime/kvtier.py): the -sched4
        # workload on a pool at ~40% of full-reservation demand, served
        # with optimistic reservation + host spill — the tok/s gap vs
        # -sched4 is what over-commit thrash costs; full reservation
        # could not run this workload concurrently at all
        base = name[:-10]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        toks, spilled, paged_in = _bench_sched_pressure(
            cfg.with_(quant_impl=impl))
        print(json.dumps({
            "metric": f"{base} q40 KV-tiering slots=4 aggregate decode "
                      f"tok/s (optimistic reservation, pool at 40% of "
                      f"full demand, {impl})",
            "value": round(toks, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                toks, BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "spill_pages": spilled,
            "pagein_pages": paged_in,
            "backend": jax.default_backend()}))
        return

    if name.endswith("-prefix4"):
        # paged KV + radix prefix cache (runtime/pagepool.py): the -sched4
        # workload but with a 128-token shared system prompt — the tok/s
        # delta over -sched4 is the prefill the radix tree avoided
        base = name[:-8]
        cfg = _model_cfg(base)
        impl = _stage_impl(base)
        toks, reused = _bench_sched_prefix(cfg.with_(quant_impl=impl))
        print(json.dumps({
            "metric": f"{base} q40 paged-KV prefix-sharing slots=4 "
                      f"aggregate decode tok/s (128-token shared system "
                      f"prompt, {impl})",
            "value": round(toks, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                toks, BASELINE_7B_TOKS if base == "llama2-7b" else None),
            "prefix_tokens_reused": int(reused),
            "backend": jax.default_backend()}))
        return

    batch = 1
    kv_quant = False
    profile = False
    if name.endswith("-b8"):
        name, batch = name[:-3], 8
    if name.endswith("-q8kv"):
        # int8 KV cache: at a 16k live prefix the cache read dominates the
        # step, so this should show ~2× less attention time than the bf16
        # run (beyond-reference capability, models/transformer.py)
        name, kv_quant = name[:-5], True
    codec = "q40"  # codec_label below keeps every metric string honest
    if name.endswith("-q8w"):
        # Q80 weight files (the reference's fallback codec): the fused Q80
        # kernel's first hardware number — ~1.9x the Q40 weight bytes but
        # cheaper per-weight unpack, so where it lands vs Q40 is empirical
        name, codec = name[:-4], "q80"
    if name.endswith("-profile"):
        # xplane profiling rides its OWN attempt: a trace slows the host,
        # so end-to-end numbers are taken with the profiler off
        name, profile = name[:-8], True
    chunk_override = None
    if "-c" in name and name.rsplit("-c", 1)[-1].isdigit():
        # decode chunk-size probe: per-token wall cost = compute + (per-
        # chunk dispatch overhead)/chunk — a larger K amortizes it
        # (runtime/decode_loop.py K-step chunk; --chunk on the CLI)
        name, c = name.rsplit("-c", 1)
        chunk_override = int(c)
    codec_label = "q40" if codec == "q40" else "q80-weights"
    cfg = _model_cfg(name)
    impl = _stage_impl(name)
    if name == "cpu-tiny":
        chunk, n_chunks = 16, 2
    else:
        chunk, n_chunks = 32, 10  # ≥10 timed chunks (ADVICE r02)
    if profile:
        n_chunks = 2  # the split needs one traced chunk, not a full rerun
    if chunk_override:
        # keep the ≥10-timed-chunks evidence standard (ADVICE r02) even for
        # probes: a promoted chunk-size headline must rest on the same
        # sample count as the number it replaces
        chunk, n_chunks = chunk_override, 10
    cfg = cfg.with_(quant_impl=impl)
    # long-context evidence decodes deep in the cache (live prefix ~15.7k),
    # otherwise the "16k" number would really measure a ~350-token prefix
    start = cfg.seq_len - 64 - (n_chunks + 2) * chunk if name.endswith("-long") else 0
    ms = _bench_decode(cfg, chunk=chunk, n_chunks=n_chunks, profile=profile,
                       start_pos=start, batch=batch, kv_quant=kv_quant,
                       codec=codec)
    toks = batch * 1000.0 / ms
    backend = jax.default_backend()
    if kv_quant:
        print(json.dumps({
            "metric": f"{name} {codec_label} greedy decode tok/s with int8 KV cache"
                      + (f" at seq_len {cfg.seq_len}, live prefix ≥{start}"
                         if start else "")
                      + f" (1 TPU chip, {impl})",
            "value": round(toks, 2), "unit": "tok/s", "vs_baseline": None,
            "backend": backend}))
        return
    if batch > 1:
        # the distinct-stream serving lever (Engine.generate_batch): decode
        # is weight-bandwidth-bound, so aggregate tok/s should approach
        # batch× the single-stream rate — the reference cannot batch at all
        # (tasks.cpp:199-210)
        print(json.dumps({
            "metric": f"{name} {codec_label} lockstep batch={batch} aggregate decode "
                      f"tok/s (1 TPU chip, {impl})",
            "value": round(toks, 2), "unit": "tok/s",
            "vs_baseline": _vs_baseline(
                toks, BASELINE_7B_TOKS if name == "llama2-7b" else None),
            "backend": backend}))
        return
    if name == "llama2-7b-long":
        metric = (f"llama2-7b {codec_label} greedy decode tok/s at seq_len 16384, "
                  f"live prefix ≥{start} (1 TPU chip, {impl})")
        vs = None  # reference has no long-context capability to compare
    elif name == "llama3-8b":
        metric = f"llama3-8b {codec_label} greedy decode tok/s (1 TPU chip, {impl})"
        vs = None  # BASELINE.json target is 80 tok/s/chip on v5e-8; the
        # reference's only published Llama-3 numbers are RasPi multi-node
    elif name == "llama2-7b":
        metric = f"llama2-7b {codec_label} greedy decode tok/s (1 TPU chip, {impl})"
        if chunk_override:
            metric += f" [chunk={chunk}]"
        vs = _vs_baseline(toks, BASELINE_7B_TOKS)
    elif name == "llama2-13b":
        metric = f"llama2-13b {codec_label} greedy decode tok/s (1 TPU chip, {impl})"
        vs = _vs_baseline(toks, BASELINE_13B_TOKS)
    elif name == "tinyllama-1.1b":
        metric = f"tinyllama-1.1b {codec_label} greedy decode tok/s (1 TPU chip, {impl})"
        vs = None  # no published reference number for this config
    else:
        metric = "cpu-tiny decode tok/s (CPU backend, toy size: not a device number)"
        vs = None
    print(json.dumps({"metric": metric, "value": round(toks, 2),
                      "unit": "tok/s", "vs_baseline": vs, "backend": backend}))


def _emit(result, extras=None):
    """Write one result line: a single os.write of the full payload, so a
    kill can never truncate it or leave a second line."""
    result.pop("backend", None)
    if extras:
        result["extras"] = extras
    sys.stdout.flush()
    os.write(1, (json.dumps(result) + "\n").encode())


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--attempt":
        run_attempt(sys.argv[2])
    else:
        raise SystemExit("usage: python bench.py --attempt <stage>  "
                         "(stages: see the module docstring)")
