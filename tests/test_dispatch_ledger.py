"""Kernel-dispatch ledger + compile/memory telemetry + live profiling
(docs/OBSERVABILITY.md; obs/dispatch.py).

The acceptance contract this file pins: NO silent degrade path remains —
every Pallas/shard/reduce fallback in q40/q8 lands in a labeled registry
counter and a structured log record, and an injected degrade is visible
in ``/metrics`` (JSON and Prometheus), ``/health``, and the end-of-run
CLI summary in the SAME test.  Plus: recompiles vs executable-cache hits
are counted per engine step family, and ``POST /debug/profile`` answers
a well-formed per-op report or a clean 503.
"""

import json
import logging
import os
import re
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from fixtures import REPO, cpu_env, free_port, run_cli, write_tiny_model, \
    write_tiny_tokenizer

from dllama_tpu import quants
from dllama_tpu.obs import dispatch as obs_dispatch, metrics as obs_metrics
from dllama_tpu.ops import q40

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Each test sees a fresh ledger (the module state is process-global)."""
    obs_dispatch.reset()
    yield
    obs_dispatch.reset()


# --- unit: labeled registry types -----------------------------------------

def test_labeled_counter_json_and_prometheus():
    from dllama_tpu.obs.metrics import Registry
    reg = Registry()
    c = reg.labeled_counter("widget_events", ("kind", "path"), "help")
    c.inc("a", "x")
    c.inc("a", "x", n=2)
    c.inc("b", "y")
    assert c.name == "dllama_widget_events_total"
    assert c.get("a", "x") == 3 and c.get("b", "y") == 1
    assert c.get("never", "seen") == 0
    assert c.total == 4
    assert c.json_value() == {"a/x": 3, "b/y": 1}
    lines = []
    c.render(lines)
    text = "\n".join(lines)
    assert 'dllama_widget_events_total{kind="a",path="x"} 3' in text
    assert 'dllama_widget_events_total{kind="b",path="y"} 1' in text
    with pytest.raises(ValueError):
        c.inc("only-one-label-value")
    c.reset()
    assert c.total == 0 and c.json_value() == {}


def test_labeled_gauge_fn_and_graceful_absence():
    from dllama_tpu.obs.metrics import Registry
    reg = Registry()
    g = reg.labeled_gauge("widget_bytes", "device",
                          fn=lambda: {"0": 5.0, "1": 7.0})
    def rendered():
        lines = []
        g.render(lines)
        return "\n".join(lines)

    assert g.values() == {"0": 5.0, "1": 7.0}
    assert 'dllama_widget_bytes{device="0"} 5' in rendered()
    # a reader that explodes reads as ABSENT (no samples), never as zeros
    g.fn = lambda: 1 / 0
    assert g.values() == {} and g.json_value() == {}
    assert "widget_bytes{" not in rendered()


# --- the degrade funnel ----------------------------------------------------

def test_degrade_logs_once_but_counts_every_occurrence():
    records = []
    h = logging.Handler()
    h.emit = lambda r: records.append(r)
    lg = logging.getLogger("dllama.obs.dispatch")
    lg.addHandler(h)
    old = lg.level
    lg.setLevel(logging.DEBUG)
    before = obs_metrics.Q40_DEGRADE.get("unshardable")
    try:
        for _ in range(3):
            obs_dispatch.record_degrade(
                "q40", "unshardable", warn_key=("col", 96, 64, 4),
                shape=(96, 64), kind="col", tp=4)
    finally:
        lg.removeHandler(h)
        lg.setLevel(old)
    warned = [r for r in records if r.getMessage() == "kernel_degrade"]
    assert len(warned) == 1, "warn-once per (codec, reason, warn_key)"
    assert obs_metrics.Q40_DEGRADE.get("unshardable") == before + 3
    assert obs_dispatch.reasons() == {"q40:unshardable": 3}


def _q40_fixture(n, d, seed=0):
    rng = np.random.RandomState(seed)
    return q40.quantize((rng.randn(n, d) * 0.05).astype(np.float32))


def _unshardable_degrade():
    """One real degrade: a forced-pallas matmul on a tp mesh whose caller
    declared no slicing ``kind`` cannot run per shard and falls back to the
    XLA path through the ledger.  Returns (got, reference)."""
    import jax
    import jax.numpy as jnp
    from dllama_tpu.parallel.mesh import active_mesh, make_mesh
    qt = _q40_fixture(128, 256)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 128), jnp.float32)
    with active_mesh(make_mesh(tp=2, devices=jax.devices()[:2])):
        out = q40.matmul(x, qt, impl="pallas_interpret")
    return out, x.astype(jnp.bfloat16) @ q40.dequantize(qt, jnp.bfloat16)


def test_unshardable_on_a_mesh_degrades_correctly():
    """The degrade is counted, flags the process, and still returns the
    right numbers."""
    before = obs_metrics.Q40_DEGRADE.get("unshardable")
    out, ref = _unshardable_degrade()
    assert obs_metrics.Q40_DEGRADE.get("unshardable") == before + 1
    assert obs_dispatch.degraded() is True
    assert obs_dispatch.dispatches() == {"q40/xla-dequant": 1}
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_dispatch_paths_recorded():
    """Every resolved dispatch lands in the labeled matmul_dispatch family
    (auto on CPU resolves to xla-dequant)."""
    import jax.numpy as jnp
    qt = _q40_fixture(128, 256)
    x = jnp.ones((1, 128), jnp.float32)
    before = obs_metrics.MATMUL_DISPATCH.get("q40", "xla-dequant")
    q40.matmul(x, qt)  # impl="auto"; CPU → xla-dequant
    assert obs_metrics.MATMUL_DISPATCH.get("q40", "xla-dequant") == before + 1
    assert obs_dispatch.dispatches().get("q40/xla-dequant", 0) >= 1
    assert obs_dispatch.degraded() is False  # a fallback by policy, not a degrade
    assert "q40/xla-dequant" in obs_dispatch.summary_line()


@pytest.mark.parametrize("loops", [3, 0])
def test_a_looped_program_records_its_loop_once(loops, caplog):
    """``{codec="loop",path="scan"}``: one a compiled program of a looped model,
    with its passes, layers and cache planes on the debug record; a model that
    runs its layers once records nothing of the kind, and the gauge
    ``model_loop_passes`` says 1 there."""
    import jax
    import jax.numpy as jnp
    from dllama_tpu.models.config import tiny_config, tiny_ouro
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    cfg = tiny_ouro(loops=loops) if loops else tiny_config()
    obs_dispatch.reset()
    with caplog.at_level(logging.DEBUG, logger="dllama.obs.dispatch"):
        eng = Engine(cfg, init_params(cfg, seed=2),
                     mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
        eng.prefill([5, 6, 7])
        eng.decode_one(8)
    sites = obs_dispatch.dispatches()
    assert sites.get("loop/scan", 0) == (2 if loops else 0)  # the prompt's, the token's
    assert obs_metrics.MODEL_LOOP_PASSES.json_value() == (loops or 1)
    recs = [r for r in caplog.records if getattr(r, "codec", "") == "loop"]
    if loops:
        assert {(r.passes, r.layers, r.planes) for r in recs} == {(3, 3, 9)}
        assert obs_metrics.MATMUL_DISPATCH.get("loop", "scan") >= 2
        assert "loop/scan" in obs_dispatch.summary_line()
    else:
        assert not recs
    obs_dispatch.reset()


@pytest.mark.parametrize("rows,n,body", [(1, 256, "grouped-words"),
                                         (1, 96, "grouped-nibbles"),
                                         (16, 256, "sliced-words"), (16, 96, "dot"),
                                         (64, 256, "dot")])
def test_q40_site_records_the_body_and_the_path_share_ignores_it(rows, n, body):
    """A fused Q40 call site says which body contracts its tile (PR 50) and,
    at one row, how the tile's nibbles became the dot's operand (PR 58):
    ``q40_body/grouped-words`` at one row (``grouped-nibbles`` for a toy's tile
    of 96 rows), ``q40_body/sliced-words`` at 16 (PR 62; ``dot`` for the toy's
    tile), ``q40_body/dot`` at 64, beside its
    ``q40/pallas-fused`` record.  The codec is not ``q40``, so the benchmark's
    ``pallas_path_pct`` reader counts the site once, not twice."""
    import importlib.util
    import jax.numpy as jnp
    qt = _q40_fixture(n, 128)
    obs_dispatch.reset()
    q40.matmul(jnp.ones((rows, n), jnp.bfloat16), qt, impl="pallas_interpret")
    sites = obs_dispatch.dispatches()
    assert sites == {"q40/pallas-fused": 1, f"q40_body/{body}": 1}
    assert obs_metrics.MATMUL_DISPATCH.get("q40_body", body) >= 1
    assert f"q40_body/{body}" in obs_dispatch.summary_line()
    spec = importlib.util.spec_from_file_location("pallas_path_pct", os.path.join(
        REPO, "benchmarks", "layer_metrics", "pallas_path_pct.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    after = {"matmul_dispatch": {**sites, "q40/xla-dequant": 1}}
    assert reader.read({"after": after}) == 50.0
    obs_dispatch.reset()


def test_engine_init_degrade_takes_the_ledger_path():
    """The engine-construction degrade — off-TPU tp collectives falling
    back to plain psum (``tp_psum``) — takes the ledger path: labeled
    counter + degraded flag + warn-once structured record, never
    scrollback."""
    import jax
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_config()
    records = []
    h = logging.Handler()
    h.emit = lambda r: records.append(r)
    lg = logging.getLogger("dllama.obs.dispatch")
    lg.addHandler(h)
    old = lg.level
    lg.setLevel(logging.DEBUG)
    try:
        # tp collectives have no RDMA ring off-TPU
        for _ in range(2):
            Engine(cfg, init_params(cfg, seed=4),
                   mesh=make_mesh(tp=2, devices=jax.devices()[:2]))
    finally:
        lg.removeHandler(h)
        lg.setLevel(old)
    assert obs_dispatch.degraded() is True
    assert obs_metrics.Q40_DEGRADE.get("tp_psum") == 2
    assert obs_dispatch.reasons().get("q40:tp_psum") == 2
    warned = [r.__dict__["reason"] for r in records
              if r.getMessage() == "kernel_degrade"]
    assert warned == ["tp_psum"], \
        "one structured record per degrade site, not per engine"


# --- tentpole: engine compile telemetry -----------------------------------

@pytest.fixture(scope="module")
def engine():
    import jax
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    cfg = tiny_config(seq_len=128, vocab_size=300)
    return Engine(cfg, init_params(cfg, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]))


def test_recompile_vs_cache_hit_counting(engine):
    """A fresh step shape is a recompile (observed into the compile-seconds
    histogram); repeating it is a cache hit; the live-executable gauge
    tracks what the engine holds."""
    engine.reset()
    rc0 = obs_metrics.ENGINE_RECOMPILES.value
    ch0 = obs_metrics.ENGINE_CACHE_HITS.value
    hist0 = obs_metrics.ENGINE_COMPILE_S.count
    engine.prefill([1, 2, 3])          # bucket T=16 — may be warm from
    rc1 = obs_metrics.ENGINE_RECOMPILES.value       # earlier module tests
    engine.decode_one(5)               # T=1
    rc2 = obs_metrics.ENGINE_RECOMPILES.value
    ch2 = obs_metrics.ENGINE_CACHE_HITS.value
    engine.decode_one(6)               # T=1 again → pure cache hit
    assert obs_metrics.ENGINE_RECOMPILES.value == rc2
    assert obs_metrics.ENGINE_CACHE_HITS.value == ch2 + 1
    # every recompile observed a first-call wall into the histogram
    assert (obs_metrics.ENGINE_COMPILE_S.count - hist0
            == obs_metrics.ENGINE_RECOMPILES.value - rc0)
    # the gauge equals what this engine holds (step shapes + chunk fns)
    assert obs_metrics.ENGINE_LIVE_EXECUTABLES.value == \
        len(engine._compiled_steps) + len(engine._chunk_fns)
    assert obs_metrics.ENGINE_CACHE_HITS.value > ch0
    assert rc1 >= rc0


def test_chunk_fn_cache_hits(engine):
    engine.reset()
    rc0 = obs_metrics.ENGINE_RECOMPILES.value
    list(engine.generate_stream([1, 2, 3], 8, chunk=4, seed=0))
    rc1 = obs_metrics.ENGINE_RECOMPILES.value
    ch1 = obs_metrics.ENGINE_CACHE_HITS.value
    engine.reset()
    list(engine.generate_stream([1, 2, 3], 8, chunk=4, seed=0))
    # second identical run compiles nothing new and hits the caches
    assert obs_metrics.ENGINE_RECOMPILES.value == rc1
    assert obs_metrics.ENGINE_CACHE_HITS.value > ch1
    assert rc1 > rc0  # the first run did build chunk executables


# --- tentpole: HBM gauges --------------------------------------------------

def test_hbm_gauges_graceful_on_cpu(engine):
    """CPU backends expose no allocator stats: the gauges read as ABSENT
    (empty family, no Prometheus samples), never as fabricated zeros."""
    vals = obs_metrics.HBM_BYTES_IN_USE.values()
    assert isinstance(vals, dict)
    for v in vals.values():     # populated only where memory_stats exists
        assert v >= 0
    if not vals:
        lines = []
        obs_metrics.HBM_BYTES_IN_USE.render(lines)
        assert not any("dllama_hbm_bytes_in_use{" in ln for ln in lines)


# --- acceptance: one injected degrade, visible EVERYWHERE -----------------

@pytest.fixture
def api(engine, tmp_path):
    from dllama_tpu.server.api import ApiState, serve
    from dllama_tpu.tokenizer.bpe import Tokenizer
    tok = Tokenizer(write_tiny_tokenizer(str(tmp_path / "tok.t")))
    state = ApiState(engine, tok, default_temperature=0.0, chunk=2)
    srv = serve(state, host="127.0.0.1", port=free_port(), block=False)
    yield state, f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()


def _get(base, path, accept=None):
    req = urllib.request.Request(base + path,
                                 headers={"Accept": accept} if accept else {})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, r.read()


def test_degrade_visible_in_metrics_health_and_summary(api):
    """THE acceptance test: one real degrade (a forced-pallas matmul that
    cannot shard over the active mesh) must show up in /metrics JSON,
    /metrics Prometheus, /health, and the end-of-run CLI summary line — in
    this one test."""
    _, base = api
    _unshardable_degrade()

    code, raw = _get(base, "/metrics")
    j = json.loads(raw)
    assert code == 200
    assert j["q40_degrade"].get("unshardable", 0) >= 1
    assert any(k.startswith("q40/") for k in j["matmul_dispatch"])

    code, raw = _get(base, "/metrics?format=prometheus")
    text = raw.decode()
    m = re.search(r'dllama_q40_degrade_total\{reason="unshardable"\} (\d+)',
                  text)
    assert m and int(m.group(1)) >= 1
    assert "# TYPE dllama_q40_degrade_total counter" in text
    assert re.search(r'dllama_matmul_dispatch_total\{codec="q40",'
                     r'path="[a-z-]+"\} \d+', text)

    code, raw = _get(base, "/health")
    h = json.loads(raw)
    assert code == 200 and h["degraded"] is True
    assert h["degrade_reasons"].get("q40:unshardable", 0) >= 1

    line = obs_dispatch.summary_line()   # what cmd_inference prints last
    assert "DEGRADED" in line and "q40:unshardable" in line


def test_clean_run_reads_clean(api):
    import jax.numpy as jnp
    _, base = api
    qt = _q40_fixture(128, 256)
    q40.matmul(jnp.ones((1, 128), jnp.float32), qt)  # auto → xla, no degrade
    _, raw = _get(base, "/health")
    h = json.loads(raw)
    assert h["degraded"] is False and h["degrade_reasons"] == {}
    assert obs_dispatch.summary_line().startswith("💡 kernel dispatch: clean")


# --- tentpole: POST /debug/profile ----------------------------------------

def test_debug_profile_well_formed_or_clean_503(api):
    _, base = api
    req = urllib.request.Request(base + "/debug/profile?steps=2&top=4",
                                 data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=240) as r:
            code, body = r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        code, body = e.code, json.loads(e.read())
    if code == 503:
        assert "unavailable" in body["error"]
        return
    assert code == 200
    assert body["steps"] == 2 and body["devices"] >= 1
    assert body["compute_ms"] >= 0 and body["collective_ms"] >= 0
    assert 0 <= body["collective_pct"] <= 100
    assert 1 <= len(body["ops"]) <= 4
    for op in body["ops"]:
        assert op["op"] and op["ms"] >= 0
    # ms sorted descending — the top-K contract
    ms = [op["ms"] for op in body["ops"]]
    assert ms == sorted(ms, reverse=True)


def test_debug_profile_rejected_while_draining(api):
    state, base = api
    state.draining = True
    try:
        req = urllib.request.Request(base + "/debug/profile",
                                     data=b"", method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
    finally:
        state.draining = False


def test_debug_profile_restores_engine_position(api):
    state, base = api
    eng = state.engine
    eng.reset()
    eng.prefill([1, 2, 3])
    pos0 = eng.pos
    req = urllib.request.Request(base + "/debug/profile?steps=1",
                                 data=b"", method="POST")
    try:
        urllib.request.urlopen(req, timeout=240)
    except urllib.error.HTTPError:
        pass  # 503 without xplane tooling — position must STILL be intact
    assert eng.pos == pos0


# --- CLI: end-of-run summary (subprocess, real degrade) -------------------

def test_cli_inference_prints_degraded_summary(tmp_path):
    """`dllama inference` over a Q40 model on a tp=2 mesh off the TPU must
    run to completion on plain psum AND say DEGRADED in its end-of-run
    dispatch summary."""
    m = str(tmp_path / "m.m")
    t = str(tmp_path / "m.t")
    write_tiny_model(m, ftype=quants.Q40)
    write_tiny_tokenizer(t)
    r = run_cli(["inference", "--model", m, "--tokenizer", t,
                 "--prompt", "hello", "--steps", "4", "--max-seq-len", "64",
                 "--workers", "tpu:2"], n_devices=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kernel dispatch: DEGRADED" in r.stdout
    assert "q40:tp_psum" in r.stdout


@pytest.mark.slow
def test_cli_inference_clean_summary(tmp_path):
    m = str(tmp_path / "m.m")
    t = str(tmp_path / "m.t")
    write_tiny_model(m, ftype=quants.Q80)
    write_tiny_tokenizer(t)
    r = run_cli(["inference", "--model", m, "--tokenizer", t,
                 "--prompt", "hello", "--steps", "4", "--max-seq-len", "64"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kernel dispatch: clean" in r.stdout
    assert "DEGRADED" not in r.stdout


# --- one-dispatch decode: steady pure-decode family count ----------------

def test_steady_decode_dispatch_families(monkeypatch):
    """The one-dispatch-decode contract (docs/PERF.md): the ledger
    records once per compiled call site at trace time, so the distinct
    matmul (``q40/``/``q8/``) + attention (``kv_``) families of one
    steady pure-decode trace ARE the per-step device dispatch count.
    Fused (interpret mode on CPU): ≤ 2 — one matmul family plus
    ``paged-fused``.  Unfused gather arm: ≥ 3.  Sampled rows add
    ``sample/sample-dev`` (on-device, excluded from the count)."""
    import jax
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_config(seq_len=64)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=2, kv_pages=17, kv_page_size=8)
    ptab = np.asarray([[1, 2], [3, 4]], np.int32)

    def trace_families(mode, greedy):
        # each (mode, greedy) pair is a fresh engine compile key, so the
        # slot_step below traces (and records) rather than hitting cache
        monkeypatch.setenv("DLLAMA_FUSED_ATTN", mode)
        obs_dispatch.reset()
        temps = np.zeros(2, np.float32) if greedy \
            else np.full(2, 0.8, np.float32)
        eng.slot_step(np.ones((2, 1), np.int32),
                      np.asarray([9, 9], np.int32), np.ones(2, np.int32),
                      temps_np=temps,
                      topps_np=np.full(2, 0.9, np.float32),
                      page_tables_np=ptab)
        d = obs_dispatch.dispatches()
        return {k for k in d if k.startswith(("q40/", "q8/", "kv_"))}, d

    fused, d = trace_families("interp", greedy=True)
    assert len(fused) <= 2, f"fused steady decode traced {sorted(fused)}"
    attn_fused = {k for k in fused if k.startswith("kv_")}
    assert attn_fused == {"kv_dense/paged-fused"}
    assert "sample/sample-dev" not in d  # greedy consumes no coin

    # the weight-matmul family records inside q40's own dispatch site, so
    # it may already be warm in this process — the attention side is what
    # the fused kernel collapses: 1 family vs the gather arm's 2 (3 for
    # int8 pools, whose dequant rides a third record).  1 matmul + these
    # is the ≤2-vs-≥3 per-step contract docs/PERF.md states; bench stage
    # cpu-tiny-fused4 measures it cold-process.
    unfused, _ = trace_families("off", greedy=True)
    attn_unfused = {k for k in unfused if k.startswith("kv_")}
    assert attn_unfused == {"kv_dense/paged-gather", "kv_dense/attn-score"}

    sampled, d = trace_families("interp", greedy=False)
    assert {k for k in sampled if k.startswith("kv_")} == \
        {"kv_dense/paged-fused"}
    assert len(sampled) <= 2
    assert d.get("sample/sample-dev", 0) >= 1  # sampling stayed on device
    assert obs_dispatch.degraded() is False  # interp is a mode, not a degrade


def test_mixed_step_dispatch_families(monkeypatch):
    """A mixed step (a prefill chunk beside decode rows, ``t`` tokens a
    slot) reads the pool through the same one attention family as the
    pure-decode step: ``kv_dense/paged-fused``, recorded with its ``t``; the
    gather form's two families are what the fallback records."""
    import jax
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_config(seq_len=64)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=2, kv_pages=17, kv_page_size=8)
    ptab = np.asarray([[1, 2], [3, 4]], np.int32)

    def trace_families(mode, t):
        monkeypatch.setenv("DLLAMA_FUSED_ATTN", mode)
        obs_dispatch.reset()
        # row 0 feeds a chunk of t prompt tokens, row 1 decodes one
        eng.slot_step(np.ones((2, t), np.int32), np.asarray([0, 5], np.int32),
                      np.asarray([t, 1], np.int32),
                      temps_np=np.zeros(2, np.float32),
                      topps_np=np.full(2, 0.9, np.float32),
                      page_tables_np=ptab)
        return {k for k in obs_dispatch.dispatches() if k.startswith("kv_")}

    try:
        for t in (4, 8):
            assert trace_families("interp", t) == {"kv_dense/paged-fused"}
            assert trace_families("off", t) == {"kv_dense/paged-gather",
                                                "kv_dense/attn-score"}
        assert obs_dispatch.degraded() is False
    finally:
        obs_dispatch.reset()


# --- satellite: fast tier keeps its non-trivial core ----------------------

def test_fast_tier_collects_core_suites():
    """Meta-test: `-m 'not slow'` must keep collecting a non-trivial core —
    the codec tests, the N-shard≡1-shard parity tests, and this ledger
    file.  Guards against a slow-marker sweep quietly emptying tier 1."""
    targets = ["tests/test_quants.py", "tests/test_parallel.py",
               "tests/test_dispatch_ledger.py"]
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", *targets],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    for f in targets:
        n = len(re.findall(re.escape(f) + r"::", r.stdout))
        assert n >= 3, f"fast tier collects only {n} tests from {f}"


@pytest.mark.parametrize("toy", ["falcon_h1", "llama"])
def test_a_state_space_mixer_records_its_three_sites(toy):
    """``{codec="ssm", path="state-read"|"block"|"fold"}`` one a compiled call
    site of a model with a mixer beside attention, and its convolution's
    ``conv/ring``; a model without one records none, and ``ssm_folds`` counts
    the blocks a prompt folded, a layer."""
    import jax
    from dllama_tpu.models.config import tiny_config, tiny_falcon_h1
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    cfg = tiny_falcon_h1() if toy == "falcon_h1" else tiny_config(seq_len=256)
    obs_dispatch.reset()
    before = obs_metrics.SSM_FOLDS.json_value()
    eng = Engine(cfg, init_params(cfg, seed=2),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    eng.prefill(list(range(3, 103)))           # 100 tokens: one block folded
    eng.decode_one(8)
    sites = set(obs_dispatch.dispatches())
    mixer = {"ssm/state-read", "ssm/block", "ssm/fold", "conv/ring"}
    assert (mixer <= sites) if toy == "falcon_h1" else not (mixer & sites)
    assert obs_metrics.SSM_FOLDS.json_value() - before == (
        cfg.n_layers if toy == "falcon_h1" else 0)
    if toy == "falcon_h1":
        assert "ssm/state-read" in obs_dispatch.summary_line()
    obs_dispatch.reset()
