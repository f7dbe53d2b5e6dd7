"""The step named from inside: device scopes, host spans on the profiler's
clock, step counters (ISSUE 24).

* every stage of the forward pass carries a scope of ``ops/scopes.py`` in the
  ``op_name`` metadata of the text XLA compiles (CPU, toy widths), on the
  contiguous, the slot and the paged slot path, and the pool scatter lies
  under ``kv_write``;
* ``obs.trace.span`` feeds the ring and, under ``jax.profiler.start_trace``,
  the profiler's host plane with the same name and arguments: checked on a
  2-slot scheduler and on a one-stream ``generate_stream``;
* ``sched_steps`` / ``sched_step_wall_ms`` count what landed and sum to the
  goodput clock.
"""

import contextlib
import glob
import os
import re
import subprocess
import sys
import time
import unittest.mock

import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.models import transformer as tf
from dllama_tpu.models.config import tiny_config
from dllama_tpu.models.params import init_params
from dllama_tpu.obs import metrics as obs_metrics, trace as obs_trace
from dllama_tpu.ops.scopes import SCOPES, scope
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime import decode_loop as dl
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tiny_config(seq_len=64)
_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?op_name=\"([^\"]+)\"",
                 re.M)
COMMON = {"embed", "norm", "qkv", "rope", "kv_write", "attn", "wo", "w2",
          "head", "sample"}


def scope_of(path: str):
    found = None
    for part in path.split("/"):
        if part in SCOPES:
            found = part
    return found


def compiled_ops(fn, *args) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of every instruction XLA compiled for ``fn``."""
    return _OP.findall(jax.jit(fn).lower(*args).compile().as_text())


def _params(fused: bool):
    p = init_params(CFG, seed=4)
    if fused:  # the layout a quantized load builds: one matrix per projection
        p = dict(p)
        p["wqkv"] = jnp.concatenate([p.pop("wq"), p.pop("wk"), p.pop("wv")], -1)
        p["w13"] = jnp.concatenate([p.pop("w1"), p.pop("w3")], -1)
    return p


def _slot_args(b, t):
    return (jnp.zeros((b, t), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), jnp.int32), jax.random.PRNGKey(0),
            jnp.zeros((b,)), jnp.ones((b,)), jnp.zeros((b,), jnp.int32))


def _lower(path: str):
    fused = path == "contiguous-fused"
    p = _params(fused)
    if path.startswith("contiguous"):
        cache = tf.init_kv_cache(CFG, 1, 64)
        return compiled_ops(
            lambda p, c, tok, pos, key: dl.decode_chunk(
                p, CFG, c, tok, pos, key, steps=2, temperature=0.0, topp=0.9),
            p, cache, jnp.zeros((1,), jnp.int32), jnp.int32(3),
            jax.random.PRNGKey(0))
    if path == "slot":
        cache = tf.init_kv_cache(CFG, 2, 64)
        return compiled_ops(
            lambda p, c, *a: dl.slot_chunk(p, CFG, c, *a, steps=2, greedy=True),
            p, cache, *_slot_args(2, 4))
    pool = tf.init_kv_pool(CFG, 8, 4)
    return compiled_ops(
        lambda p, c, tok, pr, nv, k, tm, tp, tk, pt: dl.slot_chunk(
            p, CFG, c, tok, pr, nv, k, tm, tp, tk, steps=2, greedy=True,
            page_table=pt),
        p, pool, *_slot_args(2, 4), jnp.zeros((2, 16), jnp.int32))


@pytest.mark.parametrize("path,extra", [
    ("contiguous", {"w1", "w3"}), ("contiguous-fused", {"w13"}),
    ("slot", {"w1", "w3"}), ("paged", {"w1", "w3", "page_idx"})])
def test_compiled_step_carries_every_scope_of_its_path(path, extra):
    ops = _lower(path)
    seen = {scope_of(name) for _, name in ops} - {None}
    assert seen == COMMON | extra, (path, sorted(seen ^ (COMMON | extra)))
    # the cache update of every path lies under kv_write, nowhere else
    writes = [name for op, name in ops
              if op in ("scatter", "dynamic-update-slice")
              and "/while/body/" in name and scope_of(name) is not None]
    assert writes and {scope_of(n) for n in writes} <= {"kv_write", "sample"}, \
        writes
    if path == "paged":
        scatters = [name for op, name in ops if op == "scatter"]
        assert scatters and all(scope_of(n) == "kv_write" for n in scatters), \
            scatters


@pytest.mark.parametrize("toy", ["ouro", "grok1", "llama"])
def test_the_norms_that_close_a_branch_are_part_post(toy):
    """Inside scope ``norm`` the norms that close a branch before the residual
    add carry the part ``post`` (Ouro's sandwich norms, Grok-1's two): a looped
    model's step tells its 768 norms into pre and post in ``by-scope.json``.  A
    reader of scope ``norm`` still reads them all, and an arch without such
    norms has no op under the name."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import tiny_ouro
    cfg = {"ouro": tiny_ouro(),
           "grok1": tiny_config(arch=mfile.ARCH_GROK1, n_experts=4,
                                n_active_experts=2, hidden_act=mfile.ACT_GELU),
           "llama": CFG}[toy]
    p = init_params(cfg, seed=4)
    ops = compiled_ops(lambda p, c, tok: tf.forward(p, cfg, tok, c, jnp.int32(3)),
                       p, tf.init_kv_cache(cfg, 1, 64), jnp.zeros((1, 1), jnp.int32))
    post = [name for _, name in ops if "/norm/post/" in name]
    assert bool(post) == (toy != "llama")
    assert all(scope_of(name) == "norm" for name in post)
    assert any(scope_of(n) == "norm" and "/post/" not in n for _, n in ops)
    if toy == "ouro":  # the pass's own norm lies in the outer loop alone
        assert any(n.count("/while/body/") == 1 and scope_of(n) == "norm"
                   for _, n in ops)


def test_scope_set_is_defined_once():
    hits = subprocess.run(
        ["grep", "-rln", "named_scope", os.path.join(REPO, "dllama_tpu"),
         "--include=*.py"], capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(h, REPO) for h in hits] == \
        ["dllama_tpu/ops/scopes.py"]
    assert len(set(SCOPES)) == len(SCOPES)
    with pytest.raises(ValueError):
        scope("ffn")


def test_scopes_change_no_number():
    """A scope is metadata: the same program without its names computes
    the same bits."""
    p = init_params(CFG, seed=4)
    tok = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
    with_names, _ = jax.jit(lambda p, c: tf.forward_last(
        p, CFG, tok, c, jnp.int32(0), jnp.int32(3)))(
            p, tf.init_kv_cache(CFG, 1, 64))
    with unittest.mock.patch.object(tf, "scope",
                                    lambda name: contextlib.nullcontext()):
        bare, _ = jax.jit(lambda p, c: tf.forward_last(
            p, CFG, tok, c, jnp.int32(0), jnp.int32(3)))(
                p, tf.init_kv_cache(CFG, 1, 64))
    assert (with_names == bare).all()


# -- host spans ---------------------------------------------------------------

def _host_events(trace_dir: str) -> list[tuple[str, float, float, dict]]:
    """``(name, start_ns, end_ns, stats)`` of the host plane's program spans."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("sched.", "engine.", "api.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def make_engine(batch=1):
    return Engine(CFG, init_params(CFG, seed=4),
                  mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=batch)


def test_span_feeds_ring_and_profiler_with_late_arguments(tmp_path):
    obs_trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("sched.enqueue", seq=7, rids=["a", "b"]) as sp:
            sp["t"] = 16  # a shape decided half-way
    finally:
        jax.profiler.stop_trace()
    (ev,) = [e for e in _host_events(str(tmp_path)) if e[0] == "sched.enqueue"]
    assert ev[3] == {"seq": 7, "rids": "a;b", "t": 16}
    (rec,) = [s for s in obs_trace.TRACER.snapshot()
              if s["name"] == "sched.enqueue"]
    assert rec["args"] == {"seq": 7, "rids": ["a", "b"], "t": 16}


def test_span_never_imports_jax():
    code = ("import sys; from dllama_tpu.obs import trace; "
            "\nwith trace.span('api.request', path='/x'): pass\n"
            "assert 'jax' not in sys.modules; "
            "assert trace.TRACER.snapshot()[0]['name'] == 'api.request'")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_scheduler_spans_nest_on_the_profilers_clock(tmp_path):
    """A 2-slot scheduler under the profiler: sched.enqueue holds
    engine.slot_enqueue, land_wait and fanout follow with the same seq, the
    ring holds the same spans on one clock, and the step counters count what
    landed."""
    eng = make_engine(2)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=50.0,
                          decode_burst=4)
    try:
        list(sched.submit([5, 9, 2], 4).tokens())  # compile outside the trace
        obs_trace.clear()
        steps0 = obs_metrics.SCHED_STEPS.json_value()
        wall0 = obs_metrics.SCHED_STEP_WALL_MS.total
        comp0 = dict(sched._comp)
        landed0 = sched._n_dispatched
        p0 = time.perf_counter()
        jax.profiler.start_trace(str(tmp_path))
        try:
            tickets = [sched.submit(p, 6) for p in ([5, 9, 2], [7, 3, 11, 4, 6])]
            for t in tickets:
                assert len(list(t.tokens())) == 6
            sched.flush()
        finally:
            jax.profiler.stop_trace()
        p1 = time.perf_counter()
    finally:
        sched.close()
    events = _host_events(str(tmp_path))
    enq = [e for e in events if e[0] == "sched.enqueue"]
    assert enq and all(
        {"seq", "t", "steps", "rows", "prefill_rows", "overlapped"} <= set(e[3])
        for e in enq), enq[:2]
    inner = [e for e in events if e[0] == "engine.slot_enqueue"]
    assert len(inner) == len(enq)
    assert all(any(_inside(i, o) for o in enq) for i in inner)
    for name in ("sched.land_wait", "sched.fanout"):
        seqs = {e[3]["seq"] for e in events if e[0] == name}
        assert seqs == {e[3]["seq"] for e in enq}, name
    assert any(e[0] == "sched.admit" for e in events)
    assert any(e[3].get("prefill_rows") for e in enq)        # a mixed step
    rids = {t.rid for t in tickets}
    assert any(set(str(e[3].get("rids", "")).split(";")) & rids for e in enq)
    # the ring: the same spans, every timestamp on perf_counter
    ring = obs_trace.TRACER.snapshot()
    names = {s["name"] for s in ring}
    assert {"sched.admit", "sched.enqueue", "engine.slot_enqueue",
            "sched.land_wait", "sched.fanout", "sched_step", "sched_admit",
            "sched_retire"} <= names, names
    assert all(p0 - 1.0 <= s["ts"] <= p1 and s["ts"] + s["dur"] <= p1 + 1e-3
               for s in ring), [s for s in ring if not p0 - 1 <= s["ts"] <= p1]
    assert sum(1 for s in ring if s["name"] == "sched.enqueue") == len(enq)
    # counters: one bump per landed dispatch, walls equal to the goodput clock
    steps = {k: v - steps0.get(k, 0)
             for k, v in obs_metrics.SCHED_STEPS.json_value().items()}
    assert steps.get("mixed", 0) >= 1 and steps.get("decode", 0) >= 1
    assert sum(steps.values()) == sched._n_dispatched - landed0 == len(enq)
    wall = obs_metrics.SCHED_STEP_WALL_MS.total - wall0
    clock = sum(sched._comp[k] - comp0[k] for k in ("prefill", "decode", "pad"))
    assert wall == pytest.approx(clock, rel=0.01)


def test_one_stream_spans_carry_their_position(tmp_path):
    eng = make_engine()
    list(eng.generate_stream([5, 9, 2], 14, chunk=4))       # compile first
    eng.reset()
    obs_trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        toks = [t for t, _ in eng.generate_stream([5, 9, 2], 14, chunk=4)]
    finally:
        jax.profiler.stop_trace()
    assert len(toks) == 14
    events = _host_events(str(tmp_path))
    (pre,) = [e for e in events if e[0] == "engine.prefill"]
    assert pre[3]["pos"] == 0 and pre[3]["k"] >= 3
    for name in ("engine.chunk_enqueue", "engine.chunk_fetch"):
        got = sorted((e[3]["pos"], e[3]["k"]) for e in events if e[0] == name)
        assert got == [(3, 4), (7, 4), (11, 2)], (name, got)
    ring = [s for s in obs_trace.TRACER.snapshot()
            if s["name"].startswith("engine.")]
    assert sorted(s["name"] for s in ring) == sorted(e[0] for e in events)
    fetch = [s for s in ring if s["name"] == "engine.chunk_fetch"]
    assert [s["args"] for s in sorted(fetch, key=lambda s: s["ts"])] == \
        [{"pos": 3, "k": 4}, {"pos": 7, "k": 4}, {"pos": 11, "k": 2}]


def test_an_idle_scheduler_goes_quiet():
    """Wake-ups of nothing must not push the requests' spans out of the
    ring: only the first wait of an idle spell is recorded.  The test wakes
    the parked loop itself and counts its wake-ups; the clock only bounds a
    hang."""
    eng = make_engine(2)
    sched = SlotScheduler(eng, prefill_chunk=4)

    def wake():
        seen, deadline = sched._park_wakeups, time.monotonic() + 60
        while sched._park_wakeups == seen:
            assert time.monotonic() < deadline, "the parked loop never woke"
            with sched._cond:
                sched._cond.notify_all()
            time.sleep(0.01)

    try:
        list(sched.submit([5, 9, 2], 2).tokens())
        sched.flush()
        # the first wait of the idle spell is the one that is recorded, when
        # it ends: let it end before the ring is cleared
        wake()
        obs_trace.clear()
        wake()
        wake()
        assert not [s for s in obs_trace.TRACER.snapshot()
                    if s["name"] in ("sched.idle", "sched.admit")]
    finally:
        sched.close()


def test_a_periods_mixer_layers_carry_falcons_parts_and_the_shared_mlp_its_own():
    """Granite-4.0-H: the mixer is a layer KIND of a period and runs through the
    function Falcon-H1's blocks call, so its ops carry the same parts (``ssm`` of
    ``qkv`` / ``wo``, ``conv`` / ``state`` / ``recent`` of ``attn``, ``conv`` /
    ``recent`` / ``fold`` of ``kv_write``) and the readers that sum them
    (``serve_ssm_ms_per_step``) find them; the period's attention layer keeps the
    bare ``qkv`` / ``wo`` and the part ``full`` of ``attn``; the shared MLP
    behind every layer's experts is ``moe/shared`` beside ``moe/experts``."""
    from dllama_tpu.models.config import tiny_granite_hybrid
    cfg = tiny_granite_hybrid()
    p = init_params(cfg, seed=4)
    ops = compiled_ops(lambda p, c, tok: tf.forward(p, cfg, tok, c, jnp.int32(3)),
                       p, tf.init_kv_cache(cfg, 1, 64), jnp.zeros((1, 4), jnp.int32))
    names = [name for _, name in ops]
    for part in ("/qkv/ssm/", "/wo/ssm/", "/attn/conv/", "/attn/state/",
                 "/attn/recent/", "/kv_write/conv/", "/kv_write/recent/",
                 "/kv_write/fold/", "/attn/full/", "/moe/shared/", "/moe/experts/",
                 "/moe/router/"):
        assert any(part in n for n in names), part
    for scope in ("qkv", "wo"):  # the attention layer's own, under the bare scope
        assert [n for n in names if scope_of(n) == scope
                and f"/{scope}/ssm/" not in n], scope
    assert not any("/attn/window/" in n or "/qkv/retention/" in n for n in names)


@pytest.mark.parametrize("toy", ["falcon_h1", "brumby", "llama"])
def test_a_mixer_beside_attention_keeps_attentions_scopes_bare(toy):
    """A state-space mixer stands BESIDE attention in one block (Falcon-H1): its
    ops carry the part ``ssm`` inside ``qkv`` and ``wo`` and, inside ``attn`` and
    ``kv_write``, the parts a retention layer and a convolution layer have
    (``state``, ``recent``, ``fold``, ``conv``); attention's own ops of the same
    layer keep the bare scopes, so a reader can tell the two mixers apart.  A
    retention model has the shared parts and no ``ssm``; Llama has none."""
    from dllama_tpu.models.config import tiny_brumby, tiny_falcon_h1
    cfg = {"falcon_h1": tiny_falcon_h1(), "brumby": tiny_brumby(),
           "llama": CFG}[toy]
    p = init_params(cfg, seed=4)
    ops = compiled_ops(lambda p, c, tok: tf.forward(p, cfg, tok, c, jnp.int32(3)),
                       p, tf.init_kv_cache(cfg, 1, 64), jnp.zeros((1, 4), jnp.int32))
    names = [name for _, name in ops]
    has = lambda part: any(part in n for n in names)  # noqa: E731
    assert has("/qkv/ssm/") == has("/wo/ssm/") == has("/attn/conv/") \
        == (toy == "falcon_h1")
    assert has("/attn/state/") == has("/kv_write/fold/") == (toy != "llama")
    assert has("/qkv/retention/") == (toy == "brumby")
    if toy == "falcon_h1":
        for scope in ("qkv", "attn", "kv_write", "wo"):
            own = [n for n in names if scope_of(n) == scope
                   and not any(f"/{scope}/{part}/" in n for part in (
                       "ssm", "conv", "state", "recent", "fold"))]
            assert own, scope                 # attention's own, under the bare scope
