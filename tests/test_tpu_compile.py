"""Compile the main-path kernels for a described (not attached) TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached, so a Mosaic lowering failure at the real
Llama-2-7B widths costs no chip time.  Nothing runs: these tests say
nothing about values or speed (chip_smoke.py checks values on the chip).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
every test file.  All TPU compiles live in this one file for the same
reason, and run in the test's own process.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dllama_tpu.models import packing
from dllama_tpu.ops import attention as att
from dllama_tpu.ops import q40
from dllama_tpu.ops.scopes import SCOPES

# Llama-2-7B: (name, input dim n, output dim d, layer-stacked)
SHAPES_7B = [("wqkv", 4096, 12288, True), ("wo", 4096, 4096, True),
             ("w13", 4096, 22016, True), ("w2", 11008, 4096, True),
             ("wcls", 4096, 32000, False)]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _q40_shapes(n, d, rows, stacked, sharding):
    np_ = q40.padded_n(n)
    lead = (2,) if stacked else ()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    return (s((rows, np_), jnp.bfloat16), s((*lead, np_ // 2, d), jnp.uint8),
            s((*lead, np_ // 32, d), jnp.uint16))


# 256 and 2048: the row-blocked form (one block; two of 1024)
@pytest.mark.parametrize("rows", [1, 8, 256, 2048])
@pytest.mark.parametrize("name,n,d,stacked", SHAPES_7B,
                         ids=[s[0] for s in SHAPES_7B])
def test_q40_matmul_compiles_at_7b_shapes(one_chip, name, n, d, stacked, rows):
    x, qp, sc = _q40_shapes(n, d, rows, stacked, one_chip)
    assert q40._tile_n_legal(x.shape[1], q40._tiles(x.shape[1], d)[0])
    if stacked:
        layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = jax.jit(q40._pallas_matmul_stacked).lower(
            x, qp, sc, layer).compile()
    else:
        compiled = jax.jit(q40._pallas_matmul).lower(x, qp, sc).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's family is its name in the trace (docs/OBSERVABILITY.md)
    assert ("q40_mm_stacked" if stacked else "q40_mm") in text


# OLMoE-1B-7B's expert matmuls (hidden 2048, expert width 1024, 64 experts of
# 16 layers): gate / up from the shared activation, down from one an expert
@pytest.mark.parametrize("rows", [16, 256])
@pytest.mark.parametrize("name,n,d,per_expert", [
    ("gate", 2048, 1024, False), ("up", 2048, 1024, False),
    ("down", 1024, 2048, True)], ids=["gate", "up", "down"])
def test_q40_experts_matmul_compiles_at_olmoe_shapes(one_chip, name, n, d,
                                                     per_expert, rows):
    L, E = 16, 64
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    x = s(((E,) if per_expert else ()) + (rows, n), jnp.bfloat16)
    compiled = jax.jit(
        lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=E)).lower(
        x, s((L * E, n // 2, d), jnp.uint8), s((L * E, n // 32, d), jnp.uint16),
        s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "q40_mm_experts" in text
    assert f"f32[{E},{rows},{d}]" in text


# (rows, query heads, kv heads, pages a slot, head size): Llama-2-7B, and the
# four served geometries (Mistral-7B's GQA at 64 pages, OLMoE's MHA at 128,
# LFM2's heads of 64 two to a row of the pool, Ouro's 8 slots of 48 pages at
# OLMoE's heads).  Tokens a slot: the
# pure-decode step's one, a verify block of spec_k + 1 = 5, the mixed step's
# chunk of 16, and the widest block the rule takes (32 for Mistral and OLMoE;
# 8 for Llama-2-7B, whose 32 kv heads make a chunk 4096 keys; 64 for LFM2,
# whose token has 4 rows of keys in the pool)
@pytest.mark.parametrize("t", [1, 5, 16, "widest"])
@pytest.mark.parametrize("b,hq,hkv,maxp,dh", [
    (4, 32, 32, 64, 128), (16, 32, 8, 64, 128), (16, 16, 16, 128, 128),
    (16, 32, 8, 128, 64), (8, 16, 16, 48, 128)],
    ids=["7b", "mistral-7b", "olmoe-1b-7b", "lfm2-24b-a2b", "ouro-2.6b"])
def test_fused_paged_attention_compiles_at_served_geometry(one_chip, monkeypatch,
                                                           b, hq, hkv, maxp, dh,
                                                           t):
    """The walk compiles for the v5e with what it carries across the grid's
    steps (two chunk buffers a pool, two DMA semaphores, one SMEM word: the
    buffer the next slot's first chunk went to), at the widest score tile
    too, and copying ahead for the next slot added no copy site: the kernel
    holds the two a kernel that starts cold at every slot holds, a chunk's
    pages of both pools each (the copies are unrolled where the kernel is
    traced: a third site is seconds of every start, PERF.md §6, PR 48)."""
    ps = 16
    n_pages = 1 + b * maxp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kvr, row = att.pool_rows(hkv, dh)
    choice = lambda t: att._fused_choice(t, hq, hkv, dh, False, ps, maxp, row)[0]  # noqa: E731
    widest = att._SCORE_TILE_MAX // (hq * att._WALK_PAGES * ps * kvr)
    if t == "widest":
        t = widest
        assert choice(t) and not choice(t + 1)
    elif t > widest:
        # the rule keeps this width on the gather form here
        assert not choice(t)
        return
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = s((2, n_pages, ps, kvr, row), jnp.bfloat16)
    ops, text = _kernel_ops(att.fused_paged_attention, (
        s((b, hq, t, dh), jnp.bfloat16), pool, pool, s((), jnp.int32),
        s((b, maxp), jnp.int32), s((b,), jnp.int32)))
    assert "tpu_custom_call" in text and "paged_attn_fused" in text
    copies = 2 * att._WALK_PAGES     # a chunk's pages, of two pools
    assert ops["tpu.enqueue_dma"] == 2 * copies, ops  # the primer; the fold's
    assert ops["tpu.wait_dma2"] == copies, ops
    # the pool goes to the kernel as it lies: nothing of its extent is made
    # (a folded pool's page is reshaped to (ps * rows, 128): a bitcast)
    assert f"bf16[2,{n_pages},{ps},{kvr},{row}]" in text
    assert not re.search(rf"= bf16\[2,{n_pages},[\d,]+\]\S* "
                         r"(?!parameter|bitcast)[\w\-]+\(", text)


def scope_of(path):
    """An op's scope: the innermost scope name of its ``op_name`` path, as the
    benchmark's readers take it (a packed region nests ``w2/cond/.../norm``)."""
    return ([c for c in path.split("/") if c in SCOPES] or [None])[-1]


def _slot_step_text(one_chip, cfg, params, b, t, n_pages, max_pages, ps=16):
    """Compiled text of one paged slot step of (b, t) tokens for the
    described chip."""
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.runtime.decode_loop import slot_chunk

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    shape = jax.eval_shape(lambda: tf.init_kv_pool(cfg, n_pages, ps)).k.shape
    pool = tf.KVCache(*(s(shape, jnp.bfloat16),) * 2)
    vec = lambda dt: s((b,), dt)  # noqa: E731
    return jax.jit(
        lambda p, c, tok, pr, nv, k, tm, tp, tk, pt: slot_chunk(
            p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
            page_table=pt), donate_argnums=(1,)).lower(
        params, pool, s((b, t), jnp.int32), vec(jnp.int32), vec(jnp.int32),
        s((2,), jnp.uint32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), s((b, max_pages), jnp.int32)).compile().as_text()


def _toy_cfg():
    from dllama_tpu.models.config import tiny_config
    return tiny_config(dim=512, hidden_dim=1024, n_layers=2, n_heads=4,
                       n_kv_heads=4, vocab_size=1024, seq_len=256,
                       dtype=jnp.bfloat16)


# pages of the toy pools below: 2 layers x 4097 pages x (16, Hkv, 128) bf16 is
# over the chip's 128 MiB of VMEM, as every served pool is; a pool that fits
# is prefetched there whole (copy-start/copy-done), which is not the subject
POOL_PAGES = 4097


def _assert_pool_is_only_scattered(text, cfg, n_pages, ps):
    """Of every instruction of the compiled text whose result has the paged
    pool's full shape, the only ones that make a pool are the KV write's
    in-place scatter and the fusion that wraps it: no ``copy``."""

    shape = (f"[{cfg.n_cache_planes},{n_pages},{ps},{cfg.n_kv_heads},"
             f"{cfg.head_size}]")
    ops = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$",
                     text, re.M)
    made = []
    for name, result, op, rest in ops:
        if shape in result and not result.startswith("(") and op not in (
                "parameter", "get-tuple-element", "bitcast"):
            path = re.search(r'op_name="([^"]+)"', rest)
            made.append((name, op, path.group(1) if path else ""))
    assert any(op == "scatter" for _, op, _ in made), made
    assert all(op in ("scatter", "fusion")
               and path.endswith("/kv_write/scatter")
               for _, op, path in made), made


def _dense_toy_params(cfg, one_chip):
    from dllama_tpu.models.params import param_shapes

    return {k: jax.ShapeDtypeStruct(
        shape, jnp.float32 if k.startswith("rms") else jnp.bfloat16,
        sharding=one_chip) for k, shape in param_shapes(cfg).items()}


# Hkv 4 (the toy's) and 8 (Mistral's: the served cell's page is (16, 8, 128))
@pytest.mark.parametrize("hkv", [4, 8])
def test_paged_slot_step_has_no_pool_copy(one_chip, monkeypatch, hkv):
    """A 2-layer paged slot step of 4 slots x 1 token (dense toy weights,
    128-wide heads so the fused attention kernel is chosen) compiled for the
    described chip: its ops carry the program's scopes, the kernel is there,
    and nothing of the pool's full shape is made but the in-place scatter of
    the KV write.  A page is token-major (L, P, ps, Hkv, Dh), so the
    scatter's layout is the pool's resident one; with the offset between the
    head axes XLA copied the whole pool per layer here, and in and out of
    the program (PERF.md §6, PR 27)."""
    import time

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = _toy_cfg().with_(n_heads=hkv, n_kv_heads=hkv, dim=128 * hkv)
    n_pages, ps = POOL_PAGES, 16
    t0 = time.monotonic()
    text = _slot_step_text(one_chip, cfg, _dense_toy_params(cfg, one_chip), 4,
                           1, n_pages, 16)
    assert time.monotonic() - t0 < 30, "too slow for tier-1: drop this test"
    assert "paged_attn_fused" in text
    paths = re.findall(r"op_name=\"([^\"]+)\"", text)
    assert {"qkv", "kv_write", "attn", "wo", "w2", "head"} <= \
        {scope_of(path) for path in paths}
    _assert_pool_is_only_scattered(text, cfg, n_pages, ps)


def test_one_stream_prefill_walks_live_blocks_without_a_slab(one_chip):
    """The one-stream prefill program at the one-stream cells' attention
    shapes (a 32k contiguous cache, a 256-token bucket, 32 query and 8 KV
    heads of 128; 3 toy-width layers, so each stacked cache is over VMEM's
    128 MiB) compiled for the described chip: under ``attn`` it holds the
    live walk's ``while`` and nothing makes an array of a layer slab's
    extent (``[…,32768,128]``) — no chunk-major ``transpose``/``copy`` of
    the layer's K and V, which with the scan over all 32 chunks was 47.8 of
    the 74.8 ms program (PERF.md §6, PR 29)."""

    from dllama_tpu.models import transformer as tf

    cfg = _toy_cfg().with_(n_layers=3, n_heads=32, n_kv_heads=8, dim=4096,
                           seq_len=32768)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    shape = jax.eval_shape(lambda: tf.init_kv_cache(cfg, 1)).k.shape
    assert shape == (3, 1, 8, 32768, 128)
    cache = tf.KVCache(*(s(shape, jnp.bfloat16),) * 2)
    text = jax.jit(
        lambda p, c, tok, pos, last: tf.forward_last(p, cfg, tok, c, pos, last),
        donate_argnums=(1,)).lower(
        _dense_toy_params(cfg, one_chip), cache, s((1, 256), jnp.int32),
        s((), jnp.int32), s((), jnp.int32)).compile().as_text()
    slab = 8 * 32768 * 128  # one layer's K or V
    whiles, made = 0, []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if not m or not re.search(r'op_name="[^"]*/attn/', line):
            continue
        name, result, op = m.groups()
        whiles += op == "while"
        dims = re.match(r"\w+\[([\d,]*)\]", result)  # None for a tuple
        if dims and op not in ("get-tuple-element", "bitcast", "parameter") \
                and np.prod([int(d) for d in dims.group(1).split(",") if d]) >= slab:
            made.append((name, result, op))
    assert whiles, "no while under attn: the live walk is not in the program"
    assert not made, made


def test_mixed_paged_slot_step_has_no_pool_copy(one_chip, monkeypatch):
    """The twin for the served mixed step's form, 16 slots x a 16-token chunk
    (the fused walk at 16 tokens a slot), at Mistral's 8 KV heads: no ``copy``
    of the pool's full shape on the way in or out, nor inside the layer loop,
    and no gathered view of a slot's whole table either."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = _toy_cfg().with_(n_heads=8, n_kv_heads=8, dim=1024)
    n_pages, ps = POOL_PAGES, 16
    text = _slot_step_text(one_chip, cfg, _dense_toy_params(cfg, one_chip), 16,
                           16, n_pages, 8)
    assert "paged_attn_fused" in text
    # the gather form's (B, Hkv, maxp * ps, Dh) view of K or V is gone
    assert f"[16,{cfg.n_kv_heads},{8 * ps},{cfg.head_size}]" not in text
    _assert_pool_is_only_scattered(text, cfg, n_pages, ps)


@pytest.mark.parametrize("t", [1, 16], ids=["pure-decode", "mixed"])
def test_looped_paged_slot_step_has_no_pool_copy(one_chip, monkeypatch, t):
    """A looped model's slot step (2 weight sets x 2 passes = 4 planes, 8 slots
    as its cell has): the cache is the carry of TWO nested loops, the pass
    around the layer, and still nothing of the pool's full shape is made but
    the KV write's in-place scatter: a copy a pass would be the whole 9.87 GB
    pool of ``ouro-2.6b.short-reason`` four times a step.  The fused walk reads
    the plane it is handed."""
    from dllama_tpu.models.config import tiny_ouro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = tiny_ouro(dim=512, hidden_dim=1024, n_layers=2, n_heads=4,
                    n_kv_heads=4, vocab_size=1024, seq_len=256,
                    dtype=jnp.bfloat16, loops=2)
    n_pages, ps = POOL_PAGES, 16
    text = _slot_step_text(one_chip, cfg, _dense_toy_params(cfg, one_chip), 8,
                           t, n_pages, 16)
    assert "paged_attn_fused" in text
    paths = re.findall(r"op_name=\"([^\"]+)\"", text)
    assert any(p.count("/while/body/") == 2 and scope_of(p) == "attn"
               for p in paths)               # the layer's loop inside the pass's
    assert any("/norm/post/" in p for p in paths)
    _assert_pool_is_only_scattered(text, cfg, n_pages, ps)


def test_mixed_slot_step_keeps_q40_on_the_fused_kernel(one_chip, monkeypatch):
    """The served mixed step, 16 slots x a 16-token chunk = 256 rows, over a
    2-layer toy model with Q40 weights, compiled for the described chip:
    every Q40 site is the fused kernel (row-blocked above 128 rows), none the
    XLA path, so no weight is written to HBM as bf16 (a ``convert``-rooted
    fusion under ``w13``/``w2``, 2.67 of 4.62 device seconds before PR 25)."""

    from dllama_tpu.models.params import param_shapes
    from dllama_tpu.obs import dispatch as obs_dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = _toy_cfg()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):  # fused along the output dim, as load_params does
        *lead, n, _ = shapes[0]
        d = sum(sh[-1] for sh in shapes)
        return q40.QTensor(s((*lead, n // 2, d), jnp.uint8),
                           s((*lead, n // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32 if k.startswith("rms") else jnp.bfloat16)
              for k in ("embedding", "rms_att", "rms_ffn", "rms_final")}
    params.update(wqkv=packed(sh["wq"], sh["wk"], sh["wv"]), wo=packed(sh["wo"]),
                  w13=packed(sh["w1"], sh["w3"]), w2=packed(sh["w2"]),
                  wcls=packed(sh["wcls"]))
    obs_dispatch.reset()
    try:
        text = _slot_step_text(one_chip, cfg, params, 16, 16, 129, 8)
        sites = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    assert sites.get("q40/pallas-fused", 0) >= 5, sites
    assert "q40/xla-dequant" not in sites, sites
    assert "q40_mm_stacked" in text and "q40_mm" in text
    # fusions whose root converts, by the scope of the root's op_name
    roots = re.findall(r"^\s*ROOT [^\n]*? (convert)\([^\n]*?op_name=\"([^\"]+)\"",
                       text, re.M)
    dequant = [path for _, path in roots
               if scope_of(path) in ("w13", "w1", "w3", "w2")]
    assert not dequant, dequant


def test_olmoe_decode_slot_step_runs_the_experts_in_three_launches_a_layer(
        one_chip, monkeypatch):
    """The served pure-decode step, 16 slots x 1 token, over a 2-layer model
    with OLMoE's block (64 packed experts, 8 a token; narrower widths),
    compiled for the described chip: the experts are ``q40_mm_experts``, three
    call sites in the layer loop's body, the strategy recorded is
    ``all-experts``, and no ``while`` (the scan over experts) or
    ``q40_mm_stacked`` launch is left under ``moe``."""

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import param_shapes
    from dllama_tpu.obs import dispatch as obs_dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = tiny_config(arch=mfile.ARCH_OLMOE, dim=512, hidden_dim=256,
                      n_layers=2, n_heads=4, n_kv_heads=4, n_experts=64,
                      n_active_experts=8, vocab_size=1024, seq_len=256,
                      dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(shape):
        *lead, n, d = shape
        return q40.QTensor(s((*lead, n // 2, d), jnp.uint8),
                           s((*lead, n // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    dense = ("embedding", "router", "rms_att", "rms_ffn", "rms_final",
             "q_norm", "k_norm")
    params = {k: s(sh[k], jnp.bfloat16 if k in ("embedding", "router")
                   else jnp.float32) for k in dense}
    params.update({k: packed(sh[k]) for k in sh if k not in dense})
    obs_dispatch.reset()
    try:
        text = _slot_step_text(one_chip, cfg, params, 16, 1, 129, 8)
        sites = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    assert sites.get("moe/all-experts") == 1 and "moe/scan" not in sites, sites
    assert "q40/xla-dequant" not in sites, sites
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?"
                     r"op_name=\"([^\"]+)\"", text, re.M)
    under_moe = [(op, path) for op, path in ops if "/moe/" in path]
    assert under_moe and not [o for o in under_moe if o[0] == "while"], under_moe
    calls = [path for op, path in under_moe
             if op == "custom-call" and "pallas_call" in path]
    assert len(calls) == 3 and all("q40_mm_experts" in c for c in calls), calls


# DeepSeek-V2's expert matmuls (hidden 5120, expert width 1536, 160 experts of
# 4 expert layers): gate / up are two d tiles of 768 under reduction tiles of
# 1280, down's 1536 input columns are stored as 1536 and reduced in one step
# against d tiles of 640 (q40._tiles, q40.padded_n)
@pytest.mark.parametrize("rows", [16, 256])
@pytest.mark.parametrize("name,n,d,per_expert", [
    ("gate", 5120, 1536, False), ("up", 5120, 1536, False),
    ("down", 1536, 5120, True)], ids=["gate", "up", "down"])
def test_q40_experts_matmul_compiles_at_deepseek_v2_shapes(one_chip, name, n, d,
                                                           per_expert, rows):
    L, E = 4, 160
    np_ = q40.padded_n(n)
    assert np_ == n and q40._tile_n_legal(np_, q40._tiles(np_, d)[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    x = s(((E,) if per_expert else ()) + (rows, np_), jnp.bfloat16)
    compiled = jax.jit(
        lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=E)).lower(
        x, s((L * E, np_ // 2, d), jnp.uint8), s((L * E, np_ // 32, d), jnp.uint16),
        s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "q40_mm_experts" in text
    assert f"f32[{E},{rows},{d}]" in text


# One decoded row's chosen experts (``q40_mm_chosen``): SmallThinker's 6 of 64
# (gate / up two reduction steps of 1280 x 768, down three d tiles of 896) and
# DeepSeek-V2's 6 of 160, the planes a traced vector
@pytest.mark.parametrize("model,n,d,experts,layers,per_expert", [
    ("smallthinker", 2560, 768, 64, 52, False),
    ("smallthinker", 768, 2560, 64, 52, True),
    ("deepseek-v2", 5120, 1536, 160, 4, False),
    ("deepseek-v2", 1536, 5120, 160, 4, True)],
    ids=["smallthinker-gate", "smallthinker-down", "deepseek-v2-gate",
         "deepseek-v2-down"])
def test_q40_chosen_experts_matmul_compiles_at_one_row(one_chip, model, n, d,
                                                       experts, layers, per_expert):
    k = 6
    assert q40.padded_n(n) == n and q40._tile_n_legal(n, q40._tiles(n, d)[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda x, qp, sc, layer, chosen: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=experts, chosen=chosen)).lower(
        s(((k,) if per_expert else ()) + (1, n), jnp.bfloat16),
        s((layers * experts, n // 2, d), jnp.uint8),
        s((layers * experts, n // 32, d), jnp.uint16),
        s((), jnp.int32), s((k,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "q40_mm_chosen" in text
    assert "q40_mm_experts" not in text and f"f32[{k},1,{d}]" in text


# DeepSeek-V2's MLA projections: the fused down-projections from x (1536 + 576
# = 2112 outputs: three d tiles of 768, the last ragged), q's up-projection
# (1536 inputs, stored as 1536), wo (16384 inputs), the shared expert's down
# (3072 inputs)
@pytest.mark.parametrize("name,n,d", [
    ("wqkv_a", 5120, 2112), ("wq_b", 1536, 24576), ("wo", 16384, 5120),
    ("shared_w2", 3072, 5120)], ids=lambda v: str(v))
def test_q40_matmul_compiles_at_deepseek_v2_shapes(one_chip, name, n, d):
    x, qp, sc = _q40_shapes(n, d, 16, True, one_chip)
    assert q40._tile_n_legal(x.shape[1], q40._tiles(x.shape[1], d)[0])
    text = jax.jit(q40._pallas_matmul_stacked).lower(
        x, qp, sc, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text and "q40_mm_stacked" in text


# One tp=4 shard of Yi-34B's attention projections, as the kernel under
# shard_map sees them: q (7168 x 1792: d tiles of 896), k / v (7168 x 256: four
# reduction steps of 1792), wo (1792 x 7168: the whole axis in one step
# against d tiles of 512, not seven steps of 256).  128 rows: the mesh's cap
@pytest.mark.parametrize("rows", [1, 128])
@pytest.mark.parametrize("name,n,d,tiles", [
    ("wq", 7168, 1792, (1024, 896)), ("wkv", 7168, 256, (1792, 256)),
    ("wo", 1792, 7168, (1792, 512))], ids=["wq", "wkv", "wo"])
def test_q40_matmul_compiles_at_yi_34b_shard_shapes(one_chip, name, n, d, tiles, rows):
    assert q40._tiles(n, d) == tiles and q40._tile_n_legal(n, tiles[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    text = jax.jit(q40._pallas_matmul_stacked).lower(
        s((rows, n), jnp.bfloat16), s((2, n // 2, d), jnp.uint8),
        s((2, n // 32, d), jnp.uint16), s((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "q40_mm_stacked" in text


@pytest.mark.parametrize("t", [1, 16], ids=["pure-decode", "mixed"])
def test_deepseek_v2_slot_steps_compile_over_a_latent_pool(one_chip, monkeypatch, t):
    """The served steps of DeepSeek-V2's block (16 slots x 1 token and x a
    16-token chunk) over a 3-layer model (a dense layer and two expert layers,
    160 packed experts in 8 groups, narrower widths, the published 512 + 64
    latent row) and a latent pool of 2056 pages, compiled for the described
    chip: the attention is the absorbed form, the experts are three
    ``q40_mm_experts`` launches in the expert segment's loop body, no Q40 site
    takes the XLA path, and the only pool in the program is the two latent
    planes ``(L, P, 16, 512)`` and ``(L, P, 16, 64)``: nothing per head."""

    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import tiny_deepseek2
    from dllama_tpu.models.params import param_shapes
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.decode_loop import slot_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = tiny_deepseek2(
        dim=512, hidden_dim=1024, n_heads=8, n_kv_heads=8, n_experts=160,
        vocab_size=1024, seq_len=2048, q_lora_rank=256, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_hidden_dim=256, rope_orig_seq_len=4096, dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh
              if k.startswith("rms") or k.endswith("_norm")}
    params.update({k: s(sh[k], jnp.bfloat16) for k in ("embedding", "router", "wkv_b")})
    params.update(
        wqkv_a=packed(sh["wq_a"], sh["wkv_a"]), w13=packed(sh["w1"], sh["w3"]),
        shared_w13=packed(sh["shared_w1"], sh["shared_w3"]),
        **{k: packed(sh[k]) for k in ("wq_b", "wo", "w2", "up", "gate", "down",
                                      "shared_w2", "wcls")})
    b, n_pages, ps, maxp = 16, 2056, 16, 128
    pool = tf.KVCache(s((cfg.n_layers, n_pages, ps, 512), jnp.bfloat16),
                      s((cfg.n_layers, n_pages, ps, 64), jnp.bfloat16))
    vec = lambda dt: s((b,), dt)  # noqa: E731
    obs_dispatch.reset()
    try:
        text = jax.jit(
            lambda p, c, tok, pr, nv, k, tm, tp, tk, pt: slot_chunk(
                p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
                page_table=pt), donate_argnums=(1,)).lower(
            params, pool, s((b, t), jnp.int32), vec(jnp.int32), vec(jnp.int32),
            s((2,), jnp.uint32), vec(jnp.float32), vec(jnp.float32),
            vec(jnp.int32), s((b, maxp), jnp.int32)).compile().as_text()
        sites = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    # the mixed step holds one body of the experts a row bucket (64
    # and every row: models/packing.py), the pure-decode step the one it had
    bodies = len(packing.buckets(b * t)) if t > 1 else 1
    # 16 rows: every expert over every row; a mixed step's 64 or 256: a row to
    # its own experts (PR 53)
    strategy = "moe/grouped" if t > 1 else "moe/all-experts"
    assert sites.get(strategy) == bodies and "moe/scan" not in sites, sites
    assert sites.get("attn/mla-absorbed") == 2 and "attn/mla-expanded" not in sites, sites
    assert "q40/xla-dequant" not in sites, sites
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?"
                     r"op_name=\"([^\"]+)\"", text, re.M)
    calls = [path for op, path in ops if op == "custom-call" and "pallas_call" in path
             and "/moe/" in path and "/experts/" in path]
    launch = "q40_mm_grouped" if t > 1 else "q40_mm_experts"
    assert len(calls) == 3 * bodies and all(launch in c for c in calls), calls
    assert any("/attn/latent/" in path for _, path in ops)
    assert any("/attn/absorb/" in path for _, path in ops)
    assert any("/moe/shared/" in path for _, path in ops)
    # the latent plane is never copied whole (a 576-wide plane was, in and out
    # of every step: PERF.md §6, PR 33); the 64-wide plane of the rotated key,
    # a ninth of the bytes, still is
    latent = f"bf16[{cfg.n_layers},{n_pages},{ps},512]"
    assert latent in text
    assert not re.search(r"= " + re.escape(latent) + r"\S* copy\(", text)
    # nothing per head is kept: no array with the heads' expanded K or V over
    # the pool's tokens
    assert not re.search(rf"\[\d+,{n_pages},{ps},8,\d+\]", text)


@pytest.mark.parametrize("name,n,d,reduce", [
    ("wo", 4096, 4096, "q40_ring"), ("w2", 11008, 4096, "q40_ring"),
    # Yi-34B: 1792 contracted columns a chip, one reduction step (q40._tiles);
    # 7168 % 256 == 0, so the rule (q40._fused_reduce_ok) takes the ring here too
    ("wo-yi", 7168, 7168, "q40_ring"), ("w2-yi", 20480, 7168, "q40_ring")])
def test_tp4_col_matmul_compiles_on_described_mesh(topo, monkeypatch, name,
                                                   n, d, reduce):
    """tp=4 col-sharded matmul + its reduce on a mesh of the described
    devices: the per-shard kernel must survive shard_map partitioning."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 1, 1, 4),
                ("dp", "sp", "ep", "tp"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np_ = q40.padded_n(n)
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    x = jax.ShapeDtypeStruct((1, np_), jnp.bfloat16, sharding=sh(P(None, "tp")))
    qp = jax.ShapeDtypeStruct((2, np_ // 2, d), jnp.uint8,
                              sharding=sh(P(None, "tp", None)))
    sc = jax.ShapeDtypeStruct((2, np_ // 32, d), jnp.uint16,
                              sharding=sh(P(None, "tp", None)))
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P()))
    assert q40._tile_n_legal(np_ // 4, q40._tiles(np_ // 4, d)[0])

    def f(x, qp, sc, layer):
        return q40._sharded_matmul(x, qp, sc, layer, "col", mesh, False)

    from dllama_tpu.obs import dispatch as obs_dispatch
    obs_dispatch.reset()
    try:
        text = jax.jit(f).lower(x, qp, sc, layer).compile().as_text()
        degraded = obs_dispatch.reasons()
    finally:
        obs_dispatch.reset()
    assert "tpu_custom_call" in text
    assert "q40_mm_stacked" in text and reduce in text
    assert ("q40_ring" in text) == (reduce == "q40_ring")
    assert not degraded, degraded  # either reduce is the rule's own choice


def test_ring_reduce_compiles_at_prefill_rows(topo, monkeypatch):
    """The ring at 128 rows of 4096: ``tp`` comm slots a direction."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 1, 1, 4),
                ("dp", "sp", "ep", "tp"))
    x = jax.ShapeDtypeStruct((128, 4096), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    f = jax.shard_map(lambda x: q40._tp_ring_allreduce(x, 4), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    assert "q40_ring" in jax.jit(f).lower(x).compile().as_text()


def _smallthinker_programs(one_chip, monkeypatch, n_layers=4):
    """SmallThinker's published widths (hidden 2560, 28/4 heads of 128, 64
    experts of 768, vocab 151936, window 4096, a 16384-position cache) at
    ``n_layers`` layers: the config, abstract packed params and cache."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import param_shapes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = ModelConfig(
        arch=mfile.ARCH_SMALLTHINKER, dim=2560, hidden_dim=768,
        n_layers=n_layers, n_heads=28, n_kv_heads=4, n_experts=64,
        n_active_experts=6, vocab_size=151936, seq_len=16384,
        hidden_act=mfile.ACT_RELU, rope_theta=1.5e6, norm_eps=1e-6,
        head_dim=128, window=4096, window_period=4, dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh if k.startswith("rms")}
    params.update({k: s(sh[k], jnp.bfloat16) for k in ("embedding", "router")})
    params.update(wqkv=packed(sh["wq"], sh["wk"], sh["wv"]),
                  **{k: packed(sh[k]) for k in ("wo", "up", "gate", "down", "wcls")})
    shapes = jax.eval_shape(lambda: tf.init_kv_cache(cfg, 1))
    cache = tf.KVCache(**{n: s(a.shape, a.dtype)
                          for n, a in shapes.planes().items()})
    return cfg, params, cache, s


def _prompt_rows_go_to_their_own_experts(prefill: str, moe_layers: int, product: str):
    """A prompt's compiled call (PR 53): three ``q40_mm_grouped`` launches an
    expert layer and no ``q40_mm_experts``, and no float32 array of every
    expert's result for every row (``product``, the ``(E, rows, d)`` shape the
    weighted sum over all experts read)."""
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?"
                     r"op_name=\"([^\"]+)\"", prefill, re.M)
    # (the grouped block is one jitted function: its name stands in the path)
    calls = [path for op, path in ops if op == "custom-call" and "pallas_call" in path
             and "/moe/" in path and "/experts/" in path]
    assert len(calls) == moe_layers * 3, calls
    assert all("q40_mm_grouped" in c for c in calls), calls
    assert "q40_mm_experts" not in prefill and product not in prefill


def test_smallthinker_cell_programs_compile_at_published_widths(one_chip, monkeypatch):
    """The programs of ``smallthinker-21b-a3b.long-stream`` for the described
    chip, one period of layers: the 512-row prefill chunk (``grouped``, PR 53:
    three ``q40_mm_grouped`` launches a layer over blocks of 64 rows that
    share an expert, and no array over all 64 experts' rows; the window
    layers' ring walk, the full layer's live walk) and the 16-step decode chunk (``select-chosen``:
    three ``q40_mm_chosen`` launches a layer over the row's 6 experts).  No Q40
    site takes the XLA path, the rings
    are 4608 positions beside full planes of 16384, and neither kind of plane
    is copied whole."""

    from dllama_tpu.models import transformer as tf
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.decode_loop import decode_chunk

    cfg, params, cache, s = _smallthinker_programs(one_chip, monkeypatch)
    assert cfg.prefill_chunk() == 512
    assert cache.k.shape == (1, 1, 4, 16384, 128) and cache.wk.shape == (3, 1, 4, 4608, 128)
    obs_dispatch.reset()
    try:
        prefill = jax.jit(
            lambda p, c, tok, pos, last: tf.forward_last(p, cfg, tok, c, pos, last),
            donate_argnums=(1,)).lower(
            params, cache, s((1, 512), jnp.int32), s((), jnp.int32),
            s((), jnp.int32)).compile().as_text()
        sites_prefill = obs_dispatch.dispatches()
        obs_dispatch.reset()
        decode = jax.jit(
            lambda p, c, tok, pos, k: decode_chunk(
                p, cfg, c, tok, pos, k, steps=16, temperature=0.0, topp=0.9),
            donate_argnums=(1,)).lower(
            params, cache, s((1,), jnp.int32), s((), jnp.int32),
            s((2,), jnp.uint32)).compile().as_text()
        sites_decode = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    assert sites_prefill.get("moe/grouped") == 4 and "moe/all-experts" not in sites_prefill
    assert sites_decode.get("moe/select-chosen") == 4, sites_decode
    assert "moe/select" not in sites_decode
    _prompt_rows_go_to_their_own_experts(prefill, 4, "f32[64,512,2560]")
    for sites in (sites_prefill, sites_decode):
        assert sites.get("attn/window-walk") == 3 and sites.get("attn/live-walk") == 1
        assert "q40/xla-dequant" not in sites, sites
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?"
                     r"op_name=\"([^\"]+)\"", decode, re.M)
    calls = [path for op, path in ops if op == "custom-call"
             and "pallas_call" in path and "/moe/experts/" in path]
    assert len(calls) == 4 * 3 and all("q40_mm_chosen" in c for c in calls), calls
    for text in (prefill, decode):
        assert any("/attn/window/" in path for _, path in re.findall(
            r"(\w+)\(.*?op_name=\"([^\"]+)\"", text))
        for plane in ("bf16[1,1,4,16384,128]", "bf16[3,1,4,4608,128]"):
            assert plane in text
            assert not re.search(r"= \(?" + re.escape(plane) + r"\S* copy(-start)?\(", text), plane


# K-EXAONE's share (PR 40): the attention projections at a query width of 8192
# on a hidden size of 6144, the dense layer's 18432, the 19200-row head (the
# last d tile ragged), and the 16 HELD experts of a layer in one launch
@pytest.mark.parametrize("name,n,d", [
    ("wqkv", 6144, 10240), ("wo", 8192, 6144), ("w13", 6144, 36864),
    ("w2", 18432, 6144), ("shared_w13", 6144, 4096)], ids=lambda v: str(v))
def test_q40_matmul_compiles_at_k_exaone_shapes(one_chip, name, n, d):
    x, qp, sc = _q40_shapes(n, d, 16, True, one_chip)
    assert x.shape[1] == n and q40._tiles(n, d) == (1024, 1024)
    text = jax.jit(q40._pallas_matmul_stacked).lower(
        x, qp, sc, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text and "q40_mm_stacked" in text


def test_q40_head_compiles_at_k_exaones_vocabulary_share(one_chip):
    x, qp, sc = _q40_shapes(6144, 19200, 16, False, one_chip)
    text = jax.jit(q40._pallas_matmul).lower(x, qp, sc).compile().as_text()
    assert "tpu_custom_call" in text and "f32[16,19200]" in text


@pytest.mark.parametrize("rows", [16, 256])
@pytest.mark.parametrize("name,n,d,per_expert", [
    ("gate", 6144, 2048, False), ("down", 2048, 6144, True)], ids=["gate", "down"])
def test_q40_experts_matmul_compiles_over_k_exaones_held_experts(
        one_chip, name, n, d, per_expert, rows):
    L, held = 23, 16
    assert q40.padded_n(n) == n and q40._tiles(n, d) == (1024, 1024)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    x = s(((held,) if per_expert else ()) + (rows, n), jnp.bfloat16)
    text = jax.jit(
        lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=held)).lower(
        x, s((L * held, n // 2, d), jnp.uint8), s((L * held, n // 32, d), jnp.uint16),
        s((), jnp.int32)).compile().as_text()
    assert "q40_mm_experts" in text and f"f32[{held},{rows},{d}]" in text


def _k_exaone_programs(one_chip, monkeypatch, n_layers=8, slots=16, pages=1025):
    """K-EXAONE's published widths (hidden 6144, 64/8 heads of 128, a dense
    layer of 18432, 128 router outputs of which 16 held, experts of 2048, a
    19200-row vocabulary, window 128) at ``n_layers`` layers on the paged
    engine's cache: the config, abstract packed params, the pool per kind."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import param_shapes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = ModelConfig(
        arch=mfile.ARCH_EXAONE_MOE, dim=6144, hidden_dim=18432,
        n_layers=n_layers, n_heads=64, n_kv_heads=8, n_experts=128,
        n_active_experts=8, vocab_size=19200, seq_len=262144,
        hidden_act=mfile.ACT_SILU, rope_theta=1e6, norm_eps=1e-5, head_dim=128,
        window=128, window_period=4, window_full_at=3, moe_hidden_dim=2048,
        n_shared_experts=1, n_groups=1, topk_groups=1, n_dense_layers=1,
        routed_scale=2.5, experts_held=16, first_expert=0, dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh
              if k.startswith("rms") or k.endswith("_norm") or k == "router_bias"}
    params.update({k: s(sh[k], jnp.bfloat16) for k in ("embedding", "router")})
    params.update(
        wqkv=packed(sh["wq"], sh["wk"], sh["wv"]), w13=packed(sh["w1"], sh["w3"]),
        shared_w13=packed(sh["shared_w1"], sh["shared_w3"]),
        **{k: packed(sh[k]) for k in ("wo", "w2", "up", "gate", "down",
                                      "shared_w2", "wcls")})
    shapes = jax.eval_shape(lambda: tf.init_kv_pool(
        cfg, pages, 16, slots=slots, max_pages=6144 // 16))
    cache = tf.KVCache(**{n: s(a.shape, a.dtype)
                          for n, a in shapes.planes().items()})
    return cfg, params, cache, s


@pytest.mark.parametrize("t", [1, 16], ids=["pure-decode", "mixed"])
def test_k_exaone_slot_steps_compile_over_the_pool_per_kind(one_chip, monkeypatch, t):
    """The two step programs of ``k-exaone-236b-a23b.long-decode`` for the
    described chip, two periods of layers: the full layers walk their own pool
    with the fused kernel (at ``t`` = 16 the score tile is exactly
    ``_SCORE_TILE_MAX``), the window layers read a ring of ten pages a slot,
    the 16 held experts are three launches a layer, no Q40 site takes the XLA
    path, and neither the pool nor the window planes are copied whole."""

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.runtime.decode_loop import slot_chunk

    cfg, params, cache, s = _k_exaone_programs(one_chip, monkeypatch)
    assert cfg.prefill_chunk() == 1024
    assert cache.k.shape == (2, 1025, 16, 8, 128)          # 2 full layers' pool
    assert cache.wk.shape == (6, 16 * 10, 16, 8, 128)      # 6 window layers' rings
    assert att._fused_choice(t, 64, 8, 128, ps=16, maxp=384) == (True, False)
    assert 64 * 16 * 8 * 16 * 8 == att._SCORE_TILE_MAX
    b = 16
    obs_dispatch.reset()
    try:
        text = jax.jit(
            lambda p, c, tok, pr, nv, k, tm, tp, tk, ptab: slot_chunk(
                p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
                page_table=ptab), donate_argnums=(1,)).lower(
            params, cache, s((b, t), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.int32), s((2,), jnp.uint32), s((b,), jnp.float32),
            s((b,), jnp.float32), s((b,), jnp.int32),
            s((b, 384), jnp.int32)).compile().as_text()
        sites = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    # the first period is unrolled (its first layer is dense), the second
    # scanned; the mixed step holds one body of the experts a row bucket
    bodies = len(packing.buckets(b * t)) if t > 1 else 1
    strategy, launch = (("moe/grouped", "q40_mm_grouped") if t > 1
                        else ("moe/all-experts", "q40_mm_experts"))   # PR 53
    assert sites.get(strategy) == (3 + 4) * bodies, sites
    assert sites.get("kv_dense/paged-fused") == 2 and sites.get("kv_dense/window-ring") == 6
    assert "q40/xla-dequant" not in sites and "kv_dense/paged-gather" not in sites, sites
    assert "paged_attn_fused" in text and launch in text
    for plane in ("bf16[2,1025,16,8,128]", "bf16[6,160,16,8,128]"):
        assert plane in text
        assert not re.search(r"= \(?" + re.escape(plane) + r"\S* copy(-start)?\(", text), plane


# ---------------------------------------------------------------------------
# A kernel's cache key is its program (PR 46)
# ---------------------------------------------------------------------------
# The persistent compile cache hashes a Mosaic kernel's serialized module as
# it is.  By default that payload names the file, function, line and column
# of the ten innermost Python frames that traced the kernel;
# hostenv.kernels_without_frames() leaves them out, so the bytes below must
# not move with a line shift, another checkout path or another call stack.
KERNEL_FAMILIES = ["q40_mm", "q40_mm_stacked", "q40_mm_experts",
                   "q40_mm_chosen", "q40_mm_grouped", "q8_mm", "q8_mm_stacked",
                   "paged_attn_fused-t1", "paged_attn_fused-t16", "q40_ring"]
KERNEL_FILES = ("q40", "q8", "attention")


def _ops_copies(tmp_path, monkeypatch, blank_line):
    """``ops/q40.py``, ``q8.py`` and ``attention.py`` copied under another
    absolute path, with a blank line on top of each if asked (every line of
    every kernel moves down), and loaded as siblings of the real modules."""
    import importlib.util
    import sys

    where = tmp_path / "elsewhere" / "dllama_tpu" / "ops"
    where.mkdir(parents=True)
    mods = {}
    for stem in KERNEL_FILES:
        with open(q40.__file__.replace("q40.py", stem + ".py")) as f:
            src = f.read()
        (where / f"{stem}.py").write_text(("\n" if blank_line else "") + src)
        name = f"dllama_tpu.ops._elsewhere_{stem}"
        spec = importlib.util.spec_from_file_location(name, where / f"{stem}.py")
        mods[stem] = importlib.util.module_from_spec(spec)
        monkeypatch.setitem(sys.modules, name, mods[stem])
        spec.loader.exec_module(mods[stem])
    return mods


def _family_launch(family, ops, topo):
    """One launch of ``family`` out of the modules ``ops`` and the shapes it
    is lowered at, on the described chip (the ring: on a tp=4 mesh of it)."""
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    n, d, layers, experts, k = 1024, 1024, 2, 4, 2
    mq, m8, ma = ops["q40"], ops["q8"], ops["attention"]
    x1, layer = s((1, n), jnp.bfloat16), s((), jnp.int32)
    planes = lambda lead: (s((*lead, n // 2, d), jnp.uint8),  # noqa: E731
                           s((*lead, n // 32, d), jnp.uint16))
    if family == "q40_mm":
        return mq._pallas_matmul, (x1, *planes(()))
    if family == "q40_mm_stacked":
        return mq._pallas_matmul_stacked, (x1, *planes((layers,)), layer)
    if family == "q40_mm_experts":
        return (lambda x, qp, sc, l: mq._pallas_matmul_experts(
            x, qp, sc, l, experts=experts),
            (s((16, n), jnp.bfloat16), *planes((layers * experts,)), layer))
    if family == "q40_mm_chosen":
        return (lambda x, qp, sc, l, c: mq._pallas_matmul_experts(
            x, qp, sc, l, experts=experts, chosen=c),
            (x1, *planes((layers * experts,)), layer, s((k,), jnp.int32)))
    if family == "q40_mm_grouped":
        return (lambda x, qp, sc, l, c, u: mq._pallas_matmul_experts(
            x, qp, sc, l, experts=experts, chosen=c, used=u),
            (s((3, 16, n), jnp.bfloat16), *planes((layers * experts,)), layer,
             s((3,), jnp.int32), layer))
    if family == "q8_mm":
        return m8._pallas_matmul, (x1, s((n, d), jnp.int8),
                                   s((n // 32, d), jnp.uint16))
    if family == "q8_mm_stacked":
        return m8._pallas_matmul_stacked, (
            x1, s((layers, n, d), jnp.int8), s((layers, n // 32, d), jnp.uint16),
            layer)
    if family.startswith("paged_attn_fused"):
        t = int(family.rsplit("t", 1)[1])
        b, hq, hkv, dh, ps, maxp = 4, 8, 2, 128, 16, 16
        pool = s((layers, 1 + b * maxp, ps, hkv, dh), jnp.bfloat16)
        return ma.fused_paged_attention, (
            s((b, hq, t, dh), jnp.bfloat16), pool, pool, layer,
            s((b, maxp), jnp.int32), s((b,), jnp.int32))
    assert family == "q40_ring"
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 1, 1, 4),
                ("dp", "sp", "ep", "tp"))
    ring = jax.shard_map(lambda x: mq._tp_ring_allreduce(x, 4), mesh=mesh,
                         in_specs=P(), out_specs=P(), check_vma=False)
    return ring, (jax.ShapeDtypeStruct((8, 1024), jnp.float32,
                                       sharding=NamedSharding(mesh, P())),)


def _kernel_body(fn, shapes, frames_between=0) -> bytes:
    """The serialized Mosaic module of the one kernel ``fn`` launches, as it
    goes into the custom call's ``backend_config``: what the cache hashes.
    ``fn`` is wrapped anew on every call (a lowering is cached by its traced
    function), in ``frames_between`` more Python frames if asked."""
    from fixtures import kernel_bodies

    def launch(*xs, depth=frames_between):
        return launch(*xs, depth=depth - 1) if depth else fn(*xs)

    (body,) = kernel_bodies(
        jax.jit(lambda *xs: launch(*xs)).lower(*shapes).as_text())
    return body


@pytest.mark.parametrize("moved", ["line-shift", "other-path", "other-stack"])
@pytest.mark.parametrize("family", KERNEL_FAMILIES)
def test_kernel_key_ignores_where_the_python_stands(topo, tmp_path, monkeypatch,
                                                    family, moved):
    from dllama_tpu import hostenv
    from dllama_tpu.ops import q8

    monkeypatch.delenv("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", raising=False)
    limit = jax.config.jax_traceback_in_locations_limit
    hostenv.kernels_without_frames()  # what every entry point and Engine call
    try:
        here = {"q40": q40, "q8": q8, "attention": att}
        want = _kernel_body(*_family_launch(family, here, topo))
        assert b".py" not in want and len(want) > 1000
        if moved == "other-stack":
            got = _kernel_body(*_family_launch(family, here, topo),
                               frames_between=3)
        else:
            there = _ops_copies(tmp_path, monkeypatch, moved == "line-shift")
            got = _kernel_body(*_family_launch(family, there, topo))
        assert got == want
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


# ---------------------------------------------------------------------------
# LFM2 (PR 47): hidden 2048, the conv operator's 2048 -> 6144 and 2048 -> 2048,
# the fused q/k/v of heads of 64 (2048 -> 3072), a dense FFN of 11776, the
# 65536-row head; 64 experts of 1536 a layer in one launch and 4 chosen of them
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [1, 16, 256])
@pytest.mark.parametrize("name,n,d", [
    ("conv_in", 2048, 6144), ("conv_out", 2048, 2048), ("wqkv", 2048, 3072),
    ("w13", 2048, 23552), ("w2", 11776, 2048)], ids=lambda v: str(v))
def test_q40_matmul_compiles_at_lfm2_shapes(one_chip, name, n, d, rows):
    x, qp, sc = _q40_shapes(n, d, rows, True, one_chip)
    assert x.shape[1] == q40.padded_n(n)
    text = jax.jit(q40._pallas_matmul_stacked).lower(
        x, qp, sc, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text and "q40_mm_stacked" in text


def test_q40_head_compiles_at_lfm2s_vocabulary(one_chip):
    x, qp, sc = _q40_shapes(2048, 65536, 16, False, one_chip)
    text = jax.jit(q40._pallas_matmul).lower(x, qp, sc).compile().as_text()
    assert "tpu_custom_call" in text and "f32[16,65536]" in text


@pytest.mark.parametrize("rows", [1, 16, 256])
@pytest.mark.parametrize("name,n,d,per_expert", [
    ("gate", 2048, 1536, False), ("down", 1536, 2048, True)], ids=["gate", "down"])
def test_q40_experts_matmuls_compile_at_lfm2_shapes(one_chip, name, n, d,
                                                    per_expert, rows):
    """16 and 256 rows: all 64 experts of a layer in one launch
    (``all-experts``); 1 row: the row's 4 chosen (``select-chosen``)."""
    L, E, k = 30, 64, 4
    assert q40.padded_n(n) == n and q40._tile_n_legal(n, q40._tiles(n, d)[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    planes = (s((L * E, n // 2, d), jnp.uint8), s((L * E, n // 32, d), jnp.uint16),
              s((), jnp.int32))
    if rows == 1:
        text = jax.jit(
            lambda x, qp, sc, layer, chosen: q40._pallas_matmul_experts(
                x, qp, sc, layer, experts=E, chosen=chosen)).lower(
            s(((k,) if per_expert else ()) + (1, n), jnp.bfloat16), *planes,
            s((k,), jnp.int32)).compile().as_text()
        assert "q40_mm_chosen" in text and f"f32[{k},1,{d}]" in text
        return
    text = jax.jit(
        lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=E)).lower(
        s(((E,) if per_expert else ()) + (rows, n), jnp.bfloat16), *planes
    ).compile().as_text()
    assert "q40_mm_experts" in text and f"f32[{E},{rows},{d}]" in text


def _lfm2_programs(one_chip, monkeypatch, paged: bool, n_layers=8, slots=16,
                   pages=2056):
    """LFM2-24B-A2B's published widths (hidden 2048, 32/8 heads of 64, two
    dense layers of 11776, 64 experts of 1536 of which 4 a token, 65536 rows,
    3 taps, periods conv, conv, attention, conv) at ``n_layers`` layers: the
    config, abstract packed params, and the served cell's pool with the slots'
    state (``paged``) or the one-stream cell's cache of 32768 positions."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import param_shapes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = ModelConfig(
        arch=mfile.ARCH_LFM2_MOE, dim=2048, hidden_dim=11776, n_layers=n_layers,
        n_heads=32, n_kv_heads=8, n_experts=64, n_active_experts=4,
        vocab_size=65536, seq_len=2048 if paged else 32768,
        hidden_act=mfile.ACT_SILU, rope_theta=1e6, norm_eps=1e-5, head_dim=64,
        window_period=4, window_full_at=2, conv_taps=3, moe_hidden_dim=1536,
        n_dense_layers=2, routed_scale=1.0, dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh
              if k.startswith("rms") or k.endswith("_norm")
              or k in ("router_bias", "conv_taps")}
    params.update({k: s(sh[k], jnp.bfloat16) for k in ("embedding", "router")})
    params.update(
        wqkv=packed(sh["wq"], sh["wk"], sh["wv"]), w13=packed(sh["w1"], sh["w3"]),
        **{k: packed(sh[k]) for k in ("wo", "w2", "conv_in", "conv_out", "up",
                                      "gate", "down", "wcls")})
    shapes = jax.eval_shape(
        (lambda: tf.init_kv_pool(cfg, pages, 16, slots=slots, max_pages=128))
        if paged else (lambda: tf.init_kv_cache(cfg, 1)))
    cache = tf.KVCache(**{n: s(a.shape, a.dtype)
                          for n, a in shapes.planes().items()})
    return cfg, params, cache, s


def _no_whole_copy(text, planes):
    for plane in planes:
        assert plane in text, plane
        assert not re.search(r"= \(?" + re.escape(plane) + r"\S* copy(-start)?\(", text), plane


@pytest.mark.parametrize("t,n_layers", [(1, 8), (16, 32)], ids=["pure-decode", "mixed"])
def test_lfm2_slot_steps_compile_with_the_state_beside_the_pool(one_chip,
                                                                monkeypatch, t, n_layers):
    """The two step programs of ``lfm2-24b-a2b.decode-heavy`` for the
    described chip, the pure-decode step at two periods of layers and the
    mixed step at the cell's eight (24 conv layers' state, 100 MB: a state of
    two periods, 25 MB, the compiler keeps in VMEM for a mixed step since the
    ``(E, rows, .)`` temporaries went, PR 53, one copy in and one back, which
    is its placement and not the subject): the conv operators over the slots'
    state ring (one ``conv/ring`` site each a body), the attention layers'
    paged read the fused walk over the folded pool (heads of 64 two to a row
    of 128 lanes: ``_fused_choice`` takes a pool whose rows fill whole lanes,
    and not one that keeps such a head a row), 64 experts in three launches a
    layer, no Q40 site on the XLA path, and neither the pool nor the state
    copied whole, nor a slot's table gathered."""
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import conv
    from dllama_tpu.runtime.decode_loop import slot_chunk

    # a pool over VMEM's 128 MiB, as the cell's is at its eight attention
    # layers (one that fits is prefetched there whole, which is not the subject)
    cfg, params, cache, s = _lfm2_programs(one_chip, monkeypatch, paged=True,
                                           n_layers=n_layers, pages=POOL_PAGES)
    n_att, n_conv = n_layers // 4, n_layers - n_layers // 4
    assert cfg.prefill_chunk() == 1024
    # the attention layers' pool, heads of 64 two to a row of 128 lanes: as
    # (2, 4097, 16, 8, 64) the chip's compact layout puts the pages minor-most
    # and both planes are copied whole, twice a step
    assert cache.k.shape == (n_att, POOL_PAGES, 16, 4, 128)
    assert cache.cz.shape == (n_conv, 16, 1, conv.RING, 2048)  # the slots' state
    assert att._fused_choice(t, 32, 8, 64, ps=16, maxp=128, row=128) == (True, False)
    assert att._fused_choice(t, 32, 8, 64, ps=16, maxp=128) == (False, False)
    b = 16
    obs_dispatch.reset()
    try:
        text = jax.jit(
            lambda p, c, tok, pr, nv, k, tm, tp, tk, ptab: slot_chunk(
                p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
                page_table=ptab), donate_argnums=(1,)).lower(
            params, cache, s((b, t), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.int32), s((2,), jnp.uint32), s((b,), jnp.float32),
            s((b,), jnp.float32), s((b,), jnp.int32),
            s((b, 128), jnp.int32)).compile().as_text()
        sites = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    # the first period is unrolled (its first two layers are dense), the second
    # scanned: 3 + 3 conv sites, 1 + 1 attention sites, 2 + 4 expert layers'
    bodies = len(packing.buckets(b * t)) if t > 1 else 1
    assert sites.get("conv/ring") == 6, sites
    assert sites.get("kv_dense/paged-fused") == 2, sites
    strategy, launch = (("moe/grouped", "q40_mm_grouped") if t > 1
                        else ("moe/all-experts", "q40_mm_experts"))   # PR 53
    assert sites.get(strategy) == (2 + 4) * bodies, sites
    assert "q40/xla-dequant" not in sites and "kv_dense/paged-gather" not in sites, sites
    assert launch in text and "q40_mm_stacked" in text
    # one launch at each attention layer's site (the unrolled period's and the
    # scanned body's), and no (B, Hkv, maxp * ps, Dh) view of a slot's table
    assert len(re.findall(r"custom-call\(.*paged_attn_fused", text)) == 2
    assert "[16,8,2048,64]" not in text
    for part in ("qkv/conv", "kv_write/conv", "attn/conv", "wo/conv"):
        assert part in text, part
    _no_whole_copy(text, (f"bf16[{n_att},{POOL_PAGES},16,4,128]",
                          f"bf16[{n_conv},16,1,{conv.RING},2048]"))
    # the walk's view of a page, (ps * rows, 128), is a bitcast of the pool
    assert not re.search(rf"= bf16\[{n_att},{POOL_PAGES},64,128\]\S* (?!bitcast)", text)


def test_lfm2_one_stream_programs_compile_at_published_widths(one_chip, monkeypatch):
    """The programs of ``lfm2-24b-a2b.single-stream`` for the described chip,
    two periods of layers: the 256-row bucket of a prompt (wider than the
    state ring: the rows that end at the prompt's last token are written;
    its experts ``grouped``, PR 53: blocks of 32 rows that share an expert) and
    the 16-step decode chunk (``select-chosen``: three ``q40_mm_chosen``
    launches an expert layer over the row's 4 experts)."""

    from dllama_tpu.models import transformer as tf
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import conv
    from dllama_tpu.runtime.decode_loop import decode_chunk

    cfg, params, cache, s = _lfm2_programs(one_chip, monkeypatch, paged=False)
    assert cache.k.shape == (2, 1, 8, 32768, 64)
    assert cache.cz.shape == (6, 1, 1, conv.RING, 2048)
    obs_dispatch.reset()
    try:
        prefill = jax.jit(
            lambda p, c, tok, pos, last: tf.forward_last(p, cfg, tok, c, pos, last),
            donate_argnums=(1,)).lower(
            params, cache, s((1, 256), jnp.int32), s((), jnp.int32),
            s((), jnp.int32)).compile().as_text()
        sites_prefill = obs_dispatch.dispatches()
        obs_dispatch.reset()
        decode = jax.jit(
            lambda p, c, tok, pos, k: decode_chunk(
                p, cfg, c, tok, pos, k, steps=16, temperature=0.0, topp=0.9),
            donate_argnums=(1,)).lower(
            params, cache, s((1,), jnp.int32), s((), jnp.int32),
            s((2,), jnp.uint32)).compile().as_text()
        sites_decode = obs_dispatch.dispatches()
    finally:
        obs_dispatch.reset()
    assert sites_prefill.get("moe/grouped") == 6 and "moe/all-experts" not in sites_prefill
    assert sites_decode.get("moe/select-chosen") == 6, sites_decode
    _prompt_rows_go_to_their_own_experts(prefill, 6, "f32[64,256,2048]")
    for sites in (sites_prefill, sites_decode):
        assert sites.get("conv/ring") == 6, sites
        assert "q40/xla-dequant" not in sites, sites
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?"
                     r"op_name=\"([^\"]+)\"", decode, re.M)
    calls = [path for op, path in ops if op == "custom-call"
             and "pallas_call" in path and "/moe/experts/" in path]
    assert len(calls) == 6 * 3 and all("q40_mm_chosen" in c for c in calls), calls
    conv_calls = [path for op, path in ops if op == "custom-call"
                  and "pallas_call" in path and "/conv/" in path]
    assert len(conv_calls) == 6 * 2, conv_calls     # W_in and W_out a layer
    for text in (prefill, decode):
        _no_whole_copy(text, ("bf16[2,1,8,32768,64]",
                              f"bf16[6,1,1,{conv.RING},2048]"))


# ---------------------------------------------------------------------------
# The Q40 body by rows (PR 50): one row contracts the raw nibbles a quantization
# block at a time, more rows the dequantized tile.  The lowered kernel (the
# Mosaic module in the custom call) says which.
# ---------------------------------------------------------------------------
def _kernel_ops(fn, shapes):
    """Op counts of the one kernel ``fn`` launches, and its compiled text."""
    import collections

    from fixtures import kernel_bodies
    from jax._src.lib.mlir import ir

    lowered = jax.jit(fn).lower(*shapes)
    (body,) = kernel_bodies(lowered.as_text())
    ops = collections.Counter()

    def walk(op):
        ops[op.operation.name.removeprefix("stable_mosaic.")] += 1
        for region in op.operation.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner)

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        walk(ir.Module.parse(body).operation)
    return ops, lowered.compile().as_text()


def _launch(form, n, d, rows, sharding, experts=0, layers=2, k=0):
    """(fn, shapes) of one launch: ``flat``, ``stacked``, ``chosen`` (k of
    ``experts``, one shared activation) or ``chosen-x`` (one an expert)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    if form == "flat":
        return q40._pallas_matmul, (
            s((rows, n), jnp.bfloat16), s((n // 2, d), jnp.uint8),
            s((n // 32, d), jnp.uint16))
    if form == "stacked":
        return q40._pallas_matmul_stacked, (
            s((rows, n), jnp.bfloat16), s((layers, n // 2, d), jnp.uint8),
            s((layers, n // 32, d), jnp.uint16), s((), jnp.int32))
    planes = (s((layers * experts, n // 2, d), jnp.uint8),
              s((layers * experts, n // 32, d), jnp.uint16), s((), jnp.int32))
    if form in ("experts", "experts-x"):  # every expert of the layer
        return (lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=experts)), (
            s(((experts,) if form == "experts-x" else ()) + (rows, n), jnp.bfloat16),
            *planes)
    if form == "grouped":  # k blocks of rows, each with its plane (PR 53)
        return (lambda x, qp, sc, layer, chosen, used: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=experts, chosen=chosen, used=used)), (
            s((k, rows, n), jnp.bfloat16), *planes, s((k,), jnp.int32),
            s((), jnp.int32))
    return (lambda x, qp, sc, layer, chosen: q40._pallas_matmul_experts(
        x, qp, sc, layer, experts=experts, chosen=chosen)), (
        s(((k,) if form == "chosen-x" else ()) + (rows, n), jnp.bfloat16),
        *planes, s((k,), jnp.int32))


# Mistral-7B's five matmuls, a Yi-34B tp=4 shard's (w1 and w3, which a mesh
# launches unfused: 7168 x 5120 each; wo: the whole 1792 rows a step; w2: 5120
# in four steps of 1280), a row's chosen experts of SmallThinker (6 of 64) and
# LFM2 (4 of 64), and DeepSeek-V2's expert width (1408: one tile of 44
# quantization blocks, no multiple of eight)
ONE_ROW_LAUNCHES = [
    ("mistral-qkv", "stacked", 4096, 6144, {}),
    ("mistral-wo", "stacked", 4096, 4096, {}),
    ("mistral-w13", "stacked", 4096, 28672, {}),
    ("mistral-w2", "stacked", 14336, 4096, {}),
    ("mistral-head", "flat", 4096, 32768, {}),
    ("yi-shard-w13", "stacked", 7168, 5120, {}),
    ("yi-shard-wo", "stacked", 1792, 7168, {}),
    ("yi-shard-w2", "stacked", 5120, 7168, {}),
    ("smallthinker-gate", "chosen", 2560, 768, dict(experts=64, layers=4, k=6)),
    ("smallthinker-down", "chosen-x", 768, 2560, dict(experts=64, layers=4, k=6)),
    ("lfm2-gate", "chosen", 2048, 1536, dict(experts=64, layers=4, k=4)),
    ("lfm2-down", "chosen-x", 1536, 2048, dict(experts=64, layers=4, k=4)),
    ("deepseek-down", "chosen-x", 1408, 2048, dict(experts=64, layers=2, k=6)),
]


@pytest.mark.parametrize("name,form,n,d,kw", ONE_ROW_LAUNCHES,
                         ids=[c[0] for c in ONE_ROW_LAUNCHES])
def test_one_row_q40_body_lowers_for_the_v5e_with_no_op_a_weight_but_the_unpack(
        one_chip, name, form, n, d, kw):
    """At one row the packed tile goes to one dot against a block-diagonal left
    operand as 32-bit words of two bf16 ``16 + v`` (PR 58): the kernel compiles
    for the chip, lane gather included, and its Mosaic module holds one matmul,
    NO extension and NO integer-to-float conversion of the tile (the scales'
    mantissas alone are converted, where the dot body converts two planes
    too), five bitcasts beside the scales' (the tile to words, four results to
    bf16), one lane gather over the vregs of ``x``, ONE cast to bf16 (the left operand:
    no weight is rounded), ONE subtraction (the bias on the block partials) and
    as many multiplies as the dot body (the bias's 24 and the scale, on the
    block partials, where that body scales each plane)."""
    ops, text = _kernel_ops(*_launch(form, n, d, 1, one_chip, **kw))
    tile_n = q40._tiles(q40.padded_n(n), d)[0]
    assert q40._nibbles_as(tile_n) == "words"
    assert "tpu_custom_call" in text
    assert ops["tpu.matmul"] == 1
    assert ops["arith.sitofp"] == DOT_BODY_OPS["arith.sitofp"] - 2
    # (a grouped launch extends its one-bit ``live`` predicate)
    assert ops["arith.extui"] == DOT_BODY_OPS["arith.extui"] - 1 + (form == "grouped")
    assert ops["tpu.bitcast"] == DOT_BODY_OPS["tpu.bitcast"] + 5
    assert ops["tpu.dynamic_gather"] == 1
    assert ops["arith.truncf"] == 1 and ops["arith.subf"] == 1
    assert ops["arith.mulf"] == DOT_BODY_OPS["arith.mulf"]
    assert not ops["tpu.transpose"] and not ops["arith.divsi"]


# The served cells' launches of 2 to SLICED_MAX_ROWS rows (PR 62): K-EXAONE's
# held experts, its widest dense matmuls and its head's share at 16 rows,
# LFM2's and DeepSeek-V2's experts (a whole-axis tile of 1536 rows: 12
# slices), Brumby's and Ouro's at 8 (6144 rows stored for 5632), Mistral's w13
# at 2 and 3 rows (a verify window: the block's rows padded to a sublane
# group), a grouped launch's blocks of 16 rows (LFM2's 128-row bucket, a packed
# mixed step of OLMoE's)
SLICED_LAUNCHES = [
    ("k-exaone-gate", "experts", 6144, 2048, 16, dict(experts=16)),
    ("k-exaone-down", "experts-x", 2048, 6144, 16, dict(experts=16)),
    ("k-exaone-w13", "stacked", 6144, 36864, 16, {}),
    ("lfm2-gate", "experts", 2048, 1536, 16, dict(experts=64)),
    ("deepseek-down", "experts-x", 1536, 5120, 16, dict(experts=160)),
    ("olmoe-down", "experts-x", 1024, 2048, 16, dict(experts=64)),
    ("brumby-w2", "stacked", 17408, 5120, 8, {}),
    ("ouro-w2", "stacked", 6144, 2048, 8, {}),
    ("k-exaone-w2", "stacked", 18432, 6144, 16, {}),
    ("k-exaone-head", "flat", 6144, 19200, 16, {}),
    ("mistral-w13-2", "stacked", 4096, 28672, 2, {}),
    ("mistral-w13-3", "stacked", 4096, 28672, 3, {}),
    ("lfm2-grouped-16", "grouped", 2048, 1536, 16, dict(experts=64, k=80)),
    ("olmoe-grouped-16", "grouped", 1024, 2048, 16, dict(experts=64, k=95)),
]


@pytest.mark.parametrize("name,form,n,d,rows,kw", SLICED_LAUNCHES,
                         ids=[c[0] for c in SLICED_LAUNCHES])
def test_sliced_q40_body_lowers_for_the_v5e_with_no_op_a_weight_but_the_unpack(
        one_chip, name, form, n, d, rows, kw):
    """At 2 to SLICED_MAX_ROWS rows the packed tile goes to the dot as the
    one-row body's 32-bit words, a 128-row slice at a time (PR 62): the kernel
    compiles for the chip at the served cells' shapes, and its Mosaic module
    holds ONE matmul, batched over the slices of the tile (no copy of the body
    a slice), NO extension and NO integer-to-float
    conversion of the tile (the scales' mantissas alone are converted), the
    one-row body's five bitcasts beside the scales', one lane gather over the
    slices of ``x``, ONE cast to bf16 (the left operand: no weight is
    rounded) and ONE subtraction (the bias, on the block partials)."""
    ops, text = _kernel_ops(*_launch(form, n, d, rows, one_chip, **kw))
    tile_n = q40._tiles(q40.padded_n(n), d)[0]
    assert q40._body(rows, tile_n) == "sliced"
    assert "tpu_custom_call" in text
    assert ops["tpu.matmul"] == 1
    assert ops["arith.sitofp"] == DOT_BODY_OPS["arith.sitofp"] - 2
    # (a grouped launch extends its one-bit ``live`` predicate)
    assert ops["arith.extui"] == DOT_BODY_OPS["arith.extui"] - 1 + (form == "grouped")
    assert ops["tpu.bitcast"] == DOT_BODY_OPS["tpu.bitcast"] + 5
    assert ops["tpu.dynamic_gather"] == 1
    assert ops["arith.truncf"] == 1 and ops["arith.subf"] == 1
    assert not ops["tpu.transpose"] and not ops["arith.divsi"]


# the 64-row stacked launch at Mistral's w13 (a packed mixed step's rows) as PR
# 41 left the 16-row one (and as the parent of PR 50 lowers it): every op of the
# dot body with its count
DOT_BODY_OPS = {
    "builtin.module": 1, "func.func": 5, "func.return": 5, "arith.constant": 50,
    "vector.load": 8, "vector.shape_cast": 7, "arith.extui": 5,
    "vector.broadcast": 19, "arith.shrsi": 3, "arith.shli": 3, "arith.andi": 3,
    "arith.addi": 1, "arith.ori": 2, "tpu.bitcast": 1, "arith.cmpi": 8,
    "arith.select": 2, "arith.sitofp": 3, "arith.mulf": 4, "arith.subf": 2,
    "arith.truncf": 2, "tpu.concatenate": 1, "tpu.matmul": 1, "scf.if": 3,
    "tpu.vector_store": 3, "scf.yield": 6, "arith.addf": 1, "memref.load": 2,
}


@pytest.mark.parametrize("form,kw", [
    ("stacked", {}), ("chosen", dict(experts=4, layers=2, k=2))])
def test_a_sixty_four_row_q40_launch_keeps_its_one_matmul_and_no_other_op(one_chip,
                                                                         form, kw):
    """Above SLICED_MAX_ROWS rows the body is PR 41's, op for op: one
    matmul, two casts to bf16, two subtractions (a bias a plane)."""
    ops, text = _kernel_ops(*_launch(form, 4096, 28672, 64, one_chip, **kw))
    assert q40._body(64, 1024) == "dot"
    assert "tpu_custom_call" in text
    # the chosen launch squeezes an expert axis off its output block and reads
    # its planes from a vector: index plumbing, no work of the body
    plumbing = {"arith.constant", "vector.shape_cast", "arith.index_cast"} \
        if form == "chosen" else set()
    assert {k: v for k, v in ops.items() if k not in plumbing} == \
        {k: v for k, v in DOT_BODY_OPS.items() if k not in plumbing}


def _only_writes_make_a_plane(made, planes, text, launches, t, parked=()):
    """``made``: the ops of a compiled slot program whose result has the shape
    of a plane too large for VMEM.  Nothing makes one but the cache's writes:
    in-place windows, the scatter into pages, their fusions, and at one token
    a row the launch that puts every row's token into a ring
    (``window.ring_put``, PR 66: no window of the ``x`` ring or of the
    convolution's is left), all under the scope ``kv_write``, the launches
    under its parts ``recent`` and ``conv`` and in no program of more tokens a
    row; and no plane of the mixer, small or large, is moved into VMEM whole
    (XLA did that to a 75 MB ring around a launch that aliased it, in and out
    a layer, until the launch asked for VMEM's scope itself).  The rings' rows
    are READ by one launch a mixer layer at one token a row and by none in a
    program of more (``ssm.recent_walk``, PR 67), which takes the ``B`` and ``x``
    planes as the ring puts leave them: nothing parks an array whole in the
    layer body (no ``ConcatBitcast``, no ``slice-start``) but ONE of what
    ``parked`` names (Granite's parent parked two stacked scale planes a layer,
    23.6 MB; one is left, whichever XLA chooses for this body, whatever scope
    the launch asks for) and nothing copies ``rk``, ``rv`` or ``rg`` (the fold's
    loop wants ``rg`` with the positions minor and the launch took it with the
    heads minor: a copy of the whole 9.4 MB plane a layer, until the launch
    took the layer's ``dt`` as a slice)."""
    assert all(op in ("dynamic-update-slice", "scatter", "fusion") or (
        op == "custom-call" and t == 1 and path.endswith("/ring_put/pallas_call"))
        for ops_ in made.values() for op, path in ops_), made
    assert all("/kv_write/" in p for ops_ in made.values() for _, p in ops_), made
    assert {k for k in launches if k.endswith("/ring_put")} == (
        {"kv_write/recent/ring_put", "kv_write/conv/ring_put"} if t == 1 else set())
    walks = {k for k in launches if k.endswith("/ssm_recent_walk")}
    if t == 1:
        assert {op for n in ("rv", "cz") for op, _ in made[n]} == {"custom-call"}
        assert len(walks) == 1 and all(
            k.endswith("attn/recent/jit(recent_walk)/ssm_recent_walk")
            for k in walks), walks
        found = set(re.findall(
            r"= (\w+\[[\d,]+\])\S* custom-call\([^\n]*ConcatBitcast", text))
        assert found <= set(parked) and len(found) <= 1, found
        assert ("slice-start" in text) == bool(found)
    else:
        assert "ring_put/pallas_call" not in text
        assert "ssm_recent_walk" not in text
    for n in ("rs", "rk", "rv", "rg", "cz"):
        shape = ",".join(map(str, planes[n].shape))
        assert not re.search(rf"\[{shape}\]\{{[^}}]*S\(1\)\}}", text), n
    for n in ("rk", "rv", "rg"):
        shape = ",".join(map(str, planes[n].shape))
        assert not re.search(rf"\[{shape}\]\S* copy\(", text), n


@pytest.mark.parametrize("t", [1, 16], ids=["pure-decode", "mixed"])
def test_falcon_h1_cell_programs_compile_with_a_state_beside_the_pool(
        one_chip, monkeypatch, t):
    """The slot programs of ``falcon-h1-34b.chat-wide`` for the described chip at
    the published widths, the cell's 18 blocks, 32 slots and 2080 pages: ONE
    layer of one slot owns pages and a state matrix and rings.  The fused page
    walk takes five query heads a kv head; the mixer's two projections are
    launches of their own under the part ``ssm``; the state is made by the
    fold's in-place update alone, the pure-decode step puts its tokens into
    a ring in one launch (PR 66; the ``dt`` ring of 32 heads in one fused
    update), and NO plane of the cache is copied whole (a
    convolution ring read before it was written was, twice a layer: 27 GB a
    mixed step; so was the ``x`` ring while nothing ordered the fold before the
    ring writes: 43 GB) and no layer's slice of the state or of the ``x`` ring
    is copied out in front of its product (134 MB and 34 MB a layer: the
    temporaries stay under 0.2 GB); arguments and temporaries stay under the
    13.5 GB at which two checks ran out of memory."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import SSM_F32, param_shapes
    from dllama_tpu.runtime.decode_loop import slot_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    layers, b = 18, 32
    cfg = ModelConfig(
        arch=mfile.ARCH_FALCON_H1, dim=5120, hidden_dim=21504, n_layers=layers,
        n_heads=20, n_kv_heads=4, n_experts=0, n_active_experts=0,
        vocab_size=261120, seq_len=1024, hidden_act=mfile.ACT_SILU,
        rope_theta=1e11, norm_eps=1e-5, head_dim=128, ssm_heads=32,
        ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_conv=4,
        mup_embedding=5.657, mup_head=0.0078125, mup_attn_out=0.0375,
        mup_ssm_in=0.25, mup_ssm_out=0.0884, mup_key=0.011, mup_gate=0.1768,
        mup_down=0.01116, mup_z=0.3536, mup_x=0.25, mup_b=0.1768, mup_c=0.5,
        mup_dt=0.3536, dtype=jnp.bfloat16)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh
              if k.startswith("rms") or k in SSM_F32}
    params["embedding"] = s(sh["embedding"], jnp.bfloat16)
    params.update(wqkv=packed(sh["wq"], sh["wk"], sh["wv"]),
                  w13=packed(sh["w1"], sh["w3"]),
                  **{k: packed(sh[k]) for k in ("wo", "w2", "ssm_in", "ssm_out",
                                                "wcls")})
    planes = jax.eval_shape(lambda: tf.init_kv_pool(
        cfg, 2080, 16, slots=b, max_pages=64)).planes()
    cache = tf.KVCache(**{n: s(a.shape, a.dtype) for n, a in planes.items()})
    vec = lambda dt: s((b,), dt)  # noqa: E731
    compiled = jax.jit(
        lambda p, c, tok, pr, nv, k, tm, tp, tk, pt: slot_chunk(
            p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
            page_table=pt), donate_argnums=(1,)).lower(
        params, cache, s((b, t), jnp.int32), vec(jnp.int32), vec(jnp.int32),
        s((2,), jnp.uint32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), s((b, 64), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    assert mem.temp_size_in_bytes < (0.01e9 if t == 1 else 0.2e9)
    text = compiled.as_text()
    kernels = re.findall(r'op_name="[^"]*closed_call/([^"]*)/pallas_call"', text)
    assert "attn/paged_attn_fused" in kernels
    launches = {k.replace("cond/branch_0_fun/", "").replace("cond/branch_1_fun/", "")
                for k in kernels}
    assert {k for k in launches if "/ssm/" in k} == {
        f"{sc}/ssm/jit(_pallas_matmul_stacked)/q40_mm_stacked" if t == 1 else
        f"{sc}/{sc}/ssm/jit(_pallas_matmul_stacked)/q40_mm_stacked"
        for sc in ("qkv", "wo")}
    ops = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$",
                     text, re.M)
    type_of = {jnp.dtype(jnp.bfloat16): "bf16", jnp.dtype(jnp.float32): "f32",
               jnp.dtype(jnp.int32): "s32"}
    # the planes over the chip's 128 MiB of VMEM (a plane that fits, ``rk`` and
    # ``rg`` here, may be prefetched there whole: POOL_PAGES' comment)
    whole = {f"{type_of[jnp.dtype(a.dtype)]}[{','.join(map(str, a.shape))}]": n
             for n, a in planes.items()
             if a.size * jnp.dtype(a.dtype).itemsize > 128 << 20}
    assert set(whole.values()) == {"v", "rs", "rv", "cz"}   # k has v's shape
    made = {}
    for name, result, op, rest in ops:
        plane = whole.get(result.split("{")[0])
        if plane and op not in ("parameter", "get-tuple-element", "bitcast",
                                "while", "call", "conditional"):
            path = re.search(r'op_name="([^"]+)"', rest)
            made.setdefault(plane, []).append((op, path.group(1) if path else ""))
    _only_writes_make_a_plane(made, planes, text, launches, t)
    assert {p for _, p in made["rs"]} == {
        "jit(<lambda>)/while/body/closed_call/kv_write/fold/while/body/"
        "dynamic_update_slice"}


# ---- Granite-4.0-H-Small: a state OR pages a layer, 72 experts of 768 -----------

@pytest.mark.parametrize("rows", [16, 256])
@pytest.mark.parametrize("name,n,d,per_expert", [
    ("gate", 4096, 768, False), ("down", 768, 4096, True)], ids=["gate", "down"])
def test_q40_experts_matmul_compiles_at_granites_72_experts_of_768(
        one_chip, name, n, d, per_expert, rows):
    """The narrowest expert in the benchmark (K-EXAONE 2048, OLMoE 1024), 72 = 9
    x 8 of them a layer: the tile rule finds whole tiles at 768 and the launch
    over every expert compiles at a pure-decode step's rows and at a chunk's."""
    L, E = 20, 72
    assert q40.padded_n(n) == n
    tn, td = q40._tiles(n, d)
    assert n % tn == 0 and d % td == 0 and td % 128 == 0
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    x = s(((E,) if per_expert else ()) + (rows, n), jnp.bfloat16)
    text = jax.jit(
        lambda x, qp, sc, layer: q40._pallas_matmul_experts(
            x, qp, sc, layer, experts=E)).lower(
        x, s((L * E, n // 2, d), jnp.uint8), s((L * E, n // 32, d), jnp.uint16),
        s((), jnp.int32)).compile().as_text()
    assert "q40_mm_experts" in text and f"f32[{E},{rows},{d}]" in text


@pytest.mark.parametrize("t", [1, 16], ids=["pure-decode", "mixed"])
def test_granite_cell_programs_compile_with_a_state_or_pages_a_layer(
        one_chip, monkeypatch, t):
    """The slot programs of ``granite-4.0-h-small.decode-heavy`` for the
    described chip at the published widths, the cell's 20 layers, 16 slots and
    2056 pages: 18 layers of a slot own a state matrix (128 heads of 64, ONE
    group, 128 rows) with its rings, 2 own pages, none both.  The mixer's read,
    fold and write compile at that geometry through the function Falcon-H1's
    blocks call; its two projections are launches of their own under the part
    ``ssm``, the experts at 72 of width 768 take ``all-experts`` at 16 rows
    and ``grouped`` past them with the shared MLP beside them under
    ``moe/shared``; the fused page walk takes the two attention layers; NO
    plane of the cache is copied whole and the state is made by the fold's
    in-place update alone; arguments and temporaries stay under the 13.5 GB at
    which two checks ran out of memory."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import SSM_F32, param_shapes
    from dllama_tpu.runtime.decode_loop import slot_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    layers, b = 20, 16
    cfg = ModelConfig(
        arch=mfile.ARCH_GRANITE_HYBRID, dim=4096, hidden_dim=1536,
        n_layers=layers, n_heads=32, n_kv_heads=8, n_experts=72,
        n_active_experts=10, vocab_size=100352, seq_len=2048,
        hidden_act=mfile.ACT_SILU, rope_theta=10000.0, norm_eps=1e-5,
        head_dim=128, window_period=10, window_full_at=5, moe_hidden_dim=768,
        n_shared_experts=2, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        ssm_groups=1, ssm_conv=4, mup_embedding=12.0, mup_head=0.0625,
        mup_key=0.0884, mup_attn_out=0.22, mup_ssm_out=0.22, mup_down=0.22,
        dtype=jnp.bfloat16)
    assert (cfg.n_ssm_layers, cfg.n_full_layers) == (18, 2)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def packed(*shapes):
        *lead, n, _ = shapes[0]
        d, np_ = sum(sh[-1] for sh in shapes), q40.padded_n(n)
        return q40.QTensor(s((*lead, np_ // 2, d), jnp.uint8),
                           s((*lead, np_ // 32, d), jnp.uint16), (n, d))

    sh = param_shapes(cfg)
    params = {k: s(sh[k], jnp.float32) for k in sh
              if k.startswith("rms") or k in SSM_F32}
    params["embedding"] = s(sh["embedding"], jnp.bfloat16)
    params["router"] = s(sh["router"], jnp.bfloat16)
    params.update(wqkv=packed(sh["wq"], sh["wk"], sh["wv"]),
                  shared_w13=packed(sh["shared_w1"], sh["shared_w3"]),
                  **{k: packed(sh[k]) for k in (
                      "wo", "ssm_in", "ssm_out", "up", "gate", "down",
                      "shared_w2", "wcls")})
    planes = jax.eval_shape(lambda: tf.init_kv_pool(
        cfg, 2056, 16, slots=b, max_pages=128)).planes()
    assert planes["rs"].shape[0] == 18 and planes["k"].shape[0] == 2
    cache = tf.KVCache(**{n: s(a.shape, a.dtype) for n, a in planes.items()})
    vec = lambda dt: s((b,), dt)  # noqa: E731
    compiled = jax.jit(
        lambda p, c, tok, pr, nv, k, tm, tp, tk, pt: slot_chunk(
            p, cfg, c, tok, pr, nv, k, tm, tp, tk, steps=1, greedy=True,
            page_table=pt), donate_argnums=(1,)).lower(
        params, cache, s((b, t), jnp.int32), vec(jnp.int32), vec(jnp.int32),
        s((2,), jnp.uint32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), s((b, 128), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    assert mem.temp_size_in_bytes < (0.05e9 if t == 1 else 0.6e9)
    text = compiled.as_text()
    kernels = re.findall(r'op_name="[^"]*closed_call/([^"]*)/pallas_call"', text)
    launches = {k.replace("cond/branch_0_fun/", "").replace("cond/branch_1_fun/", "")
                for k in kernels}
    assert "attn/full/paged_attn_fused" in launches
    assert {k for k in launches if "/ssm/" in k} == {
        f"{sc}/ssm/jit(_pallas_matmul_stacked)/q40_mm_stacked" if t == 1 else
        f"{sc}/{sc}/ssm/jit(_pallas_matmul_stacked)/q40_mm_stacked"
        for sc in ("qkv", "wo")}
    assert any("moe/shared/" in k for k in launches)
    assert any(("q40_mm_experts" if t == 1 else "q40_mm_grouped") in k
               for k in launches if "moe/" in k)
    ops = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$",
                     text, re.M)
    type_of = {jnp.dtype(jnp.bfloat16): "bf16", jnp.dtype(jnp.float32): "f32",
               jnp.dtype(jnp.int32): "s32"}
    whole = {f"{type_of[jnp.dtype(a.dtype)]}[{','.join(map(str, a.shape))}]": n
             for n, a in planes.items()
             if a.size * jnp.dtype(a.dtype).itemsize > 128 << 20}
    assert set(whole.values()) == {"v", "rs", "rv", "cz"}   # k has v's shape
    made = {}
    for name, result, op, rest in ops:
        plane = whole.get(result.split("{")[0])
        if plane and op not in ("parameter", "get-tuple-element", "bitcast",
                                "while", "call", "conditional"):
            path = re.search(r'op_name="([^"]+)"', rest)
            made.setdefault(plane, []).append((op, path.group(1) if path else ""))
    _only_writes_make_a_plane(made, planes, text, launches, t,
                              parked=("u16[20,48,4096]", "u16[20,128,3072]"))
    # the state's plane: the fold's update in place, alone or fused with the
    # block's product (a copy of the 1.2 GB plane would show in the temporaries)
    assert all("/kv_write/fold/while/body/" in p for _, p in made["rs"]), made["rs"]
