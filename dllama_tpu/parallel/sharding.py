"""Sharding specs: the TP slice layout as `NamedSharding` PartitionSpecs.

This module is the direct TPU-native port of the reference's slicing math
(`/root/reference/src/commands.cpp:8-105`):

* ``RowMatmulSlice`` (split the *output* dim: wq/wk/wv, w1/w3, MoE up/gate/
  down, transformer.cpp:287-289,300-301,319-321) → shard the weight's
  output axis on ``tp``; activations come out head/hidden-sharded with NO
  communication (the reference's broadcast of the replicated input,
  syncUnitBuffer tasks.cpp:44-65, is free here because the input is already
  replicated on every chip).
* ``ColMatmulSlice`` (split the *input* dim: wo, w2,
  transformer.cpp:290,320) → shard the weight's input axis on ``tp``; XLA
  inserts one all-reduce for the partial sums, replacing the reference's
  gather-to-root + merge + re-broadcast round trip
  (llama2-tasks.cpp:115-131,153-156).
* ``KvCacheSlice`` (commands.cpp:94-99) → shard the cache's kv-head axis.
* ``MultiHeadAttSlice``/``RopeSlice`` (commands.cpp:72-92,101-105) → free:
  head-sharded q/k/v make per-head attention and RoPE local by
  construction.

The reference's constraints carry over: ``nSlices ≤ nKvHeads``
(transformer.cpp:88-91) is checked in :func:`check_tp_constraint`; the 2^n
node-count restriction disappears (any divisor of the head counts works).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..obs import memory as obs_memory, metrics as obs_metrics, \
    trace as obs_trace
from ..models.params import param_shapes

REPL = P()


def valid_tp_degrees(cfg: ModelConfig) -> list[int]:
    """Every tensor-parallel degree this model accepts: divisors of both
    head counts and of hidden_dim, capped at nKvHeads (a shard owns whole
    KV heads, so no degree past that can be legal)."""
    return [d for d in range(1, cfg.n_kv_heads + 1)
            if cfg.n_heads % d == 0 and cfg.n_kv_heads % d == 0
            and cfg.hidden_dim % d == 0]


def check_tp_constraint(cfg: ModelConfig, tp: int) -> None:
    """Reference parity: cannot split across more nodes than KV heads
    (transformer.cpp:88-91).  Head counts must divide evenly because a
    shard owns whole heads (MultiHeadAttSlice asserts nHeads % nSlices == 0,
    commands.cpp:101-105).  Every rejection names the degrees that WOULD
    work, so the operator's next command can be right, not just different."""
    valid = valid_tp_degrees(cfg)
    hint = f"valid tp degrees for this model: {valid}"
    if tp > cfg.n_kv_heads:
        raise ValueError(
            f"tensor-parallel degree {tp} exceeds nKvHeads={cfg.n_kv_heads} "
            "(reference: 'This version does not support more nodes than the "
            f"number of KV heads', transformer.cpp:88-91); {hint}")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(f"head counts ({cfg.n_heads}/{cfg.n_kv_heads}) not "
                         f"divisible by tp={tp}; {hint}")
    if cfg.hidden_dim % tp:
        raise ValueError(f"hidden_dim {cfg.hidden_dim} not divisible by "
                         f"tp={tp}; {hint}")


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """PartitionSpec per parameter (layer-stacked layouts from params.py)."""
    specs = {
        "embedding": REPL,                   # root-owned in the reference; replicated here
        "wq": P(None, None, "tp"),           # RowMatmulSlice: out dim = heads
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wqkv": P(None, None, "tp"),         # fused q|k|v (quantized load): the concat
                                             # axis is shard-mixed, so GSPMD reshards at
                                             # the split — correct, but unfused layouts
                                             # are preferred for tp>1
        "wo": P(None, "tp", None),           # ColMatmulSlice: in dim = heads
        "w13": P(None, None, "tp"),
        "rms_att": REPL,
        "rms_ffn": REPL,
        "rms_final": REPL,
        "wcls": P(None, "tp"),               # vocab-sharded logits; gathered on host fetch
    }
    if cfg.qk_norm:
        # whole-projection norms: replicated, like every norm vector; the
        # mean over a tp-sharded q or k is GSPMD's all-reduce
        specs.update({"q_norm": REPL, "k_norm": REPL})
    if cfg.is_moe:
        specs.update({
            "router": REPL,                  # root-computed in the reference (grok1-tasks.cpp:59)
            # dense-TP MoE: hidden dim sliced on tp (transformer.cpp:
            # 299-317); the expert axis additionally shards over ep — a
            # no-op on the default ep=1 mesh, the beyond-reference
            # expert-parallel layout when ep>1
            "up": P(None, "ep", None, "tp"),
            "gate": P(None, "ep", None, "tp"),
            "down": P(None, "ep", "tp", None),
        })
    else:
        specs.update({
            "w1": P(None, None, "tp"),
            "w2": P(None, "tp", None),
            "w3": P(None, None, "tp"),
        })
    if cfg.post_block_norms:
        specs.update({"rms_moe": REPL, "rms_ffn2": REPL})
    names = param_shapes(cfg)
    if any(k.one_device for k in cfg.cache_kinds) and not set(names) <= set(specs):
        # one device (the engine refuses a tp / sp / ep mesh for such a cache)
        # and a stack the slicing above has no line for: every stack whole,
        # under its fused name too.  SmallThinker and Ouro, whose stacks all
        # have a line, keep it: an argument's spec is part of a program's text
        # and of its key in the compile cache even where one device makes it moot
        return dict.fromkeys((*names, "wqkv", "wqkv_a", "w13", "shared_w13"), REPL)
    return specs


def kv_cache_spec(seq_axis: str | None = None) -> P:
    """Cache (L, B, Hkv, S, Dh): kv-head axis on tp (KvCacheSlice,
    commands.cpp:94-99); optionally the seq axis on ``sp`` for
    sequence-parallel long context."""
    return P(None, "dp", "tp", seq_axis, None)


def kv_cache_sharding(mesh: Mesh, seq_axis: str | None = None,
                      latent: bool = False) -> NamedSharding:
    if latent:  # two planes (L, B, S, ·): no head axis to put on tp
        return NamedSharding(mesh, P(None, "dp", None, None))
    return NamedSharding(mesh, kv_cache_spec(seq_axis))


def kv_pool_sharding(mesh: Mesh, latent: bool = False) -> NamedSharding:
    """Paged pool (L, P, ps, Hkv, Dh): pages where the contiguous cache has
    its batch, kv heads on ``tp`` at axis 3 (a page is token-major,
    models.transformer.init_kv_pool).  A latent pool's planes are (L, P, ps, ·)."""
    if latent:
        return NamedSharding(mesh, P(None, "dp", None, None))
    return NamedSharding(mesh, P(None, "dp", None, "tp", None))


def place_params(params: dict, cfg: ModelConfig, mesh: Mesh) -> dict:
    """Upload host params onto the mesh with their TP shardings.

    This replaces the reference's weight-distribution phase
    (``loadRoot`` streaming slices over sockets, transformer.cpp:389-404):
    `jax.device_put` slices each array and uploads only each chip's shard.

    Packed Q40 weights (ops/q40.py QTensor) shard with the *same* spec as
    their dense counterpart: the block-local nibble layout keeps every
    32-row quantization block on one shard, so slicing the packed array's
    row axis at 1/tp is exactly the reference's ``splitWeights`` on the
    quantized bytes (commands.cpp:19-36).  ``jax.device_put`` applies the
    sharding to both pytree leaves (qpacked + scales, whose row counts are
    N/2 and N/32 — both divisible at block granularity).

    The span ``engine.load_place`` and ``engine_load_seconds{phase="place"}``
    (the gauge outlives the span ring); the span's ``rss`` and
    ``host_rss_bytes{phase="placed"}`` are the process's resident set where
    it closes (obs/memory.py).
    """
    with obs_trace.span("engine.load_place", devices=mesh.size,
                        total=obs_metrics.load_seconds("place")) as sp:
        placed = _place_params(params, cfg, mesh)
        sp.update(rss=obs_memory.ACCOUNT.rss("placed"))
        return placed


def _place_params(params: dict, cfg: ModelConfig, mesh: Mesh) -> dict:
    specs = param_specs(cfg)
    out = {}
    for k, v in params.items():
        spec = specs[k]
        # packed-Q40 expert stacks shard the expert axis over ep like their
        # dense counterparts: the fused kernel's expert select decodes the
        # flat index per shard and psums the owner's product
        # (ops/q40.py _sharded_matmul_ep), so quantized MoE weight
        # residency scales 1/ep — what lets packed Grok-1-314B fit its
        # 16-chip plan (tools/memory_plan.py, docs/MEMORY.md)
        if not _spec_divides(v, spec, mesh):
            # e.g. a Q40 scales plane (n/32 rows) that doesn't divide the
            # mesh axis: keep the tensor replicated — q40.matmul makes the
            # matching per-tensor fallback (_tp_shardable) at trace time
            print(f"⚠️  sharding: {k} {jax.tree.leaves(v)[0].shape} does not "
                  f"divide mesh {dict(mesh.shape)} evenly; replicating")
            spec = REPL
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def _spec_divides(v, spec: P, mesh: Mesh) -> bool:
    """True if every leaf of ``v`` shards evenly under ``spec`` on ``mesh``."""
    for leaf in jax.tree.leaves(v):
        for dim, axes in zip(leaf.shape, spec):
            if axes is None:
                continue
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                n = mesh.shape[ax]
                if dim % n:
                    return False
    return True
