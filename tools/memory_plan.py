"""HBM memory planner: will a model fit a mesh, and what is the smallest
mesh that fits?

Computes per-chip bytes for weights (packed Q40 or dense) and the KV
cache under the framework's sharding rules (docs/MEMORY.md; the
reference's RowMatmulSlice/ColMatmulSlice/KvCacheSlice semantics,
commands.cpp:8-105: matmul weights and kv heads shard 1/tp, norms /
embedding / routers replicate, the cache's sequence axis shards 1/sp,
batch 1/dp, experts 1/ep), and searches the (tp, sp) grid for the
smallest mesh that fits a per-chip budget — the planning the reference
leaves to trial-and-error OOM (its only guidance is 'This version does
not support more nodes than the number of KV heads',
transformer.cpp:88-91).

Usage:
    python tools/memory_plan.py llama3-8b --seq 8192 --tp 8
    python tools/memory_plan.py grok-314b --seq 8192 --fit
    python tools/memory_plan.py /path/to/model.m --seq 4096 --fit
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

Q40_BYTES_PER_WEIGHT = 0.5 + 2 / 32   # nibble + f16-bit scale = 0.5625
V5E_HBM = 16e9
# runtime allowance: XLA scratch, the donated-cache double buffer during
# relayout, activation workspaces (decode activations are ~MB-scale)
OVERHEAD = 0.5e9

# (dim, hidden, layers, heads, kv_heads, vocab, experts, active, seq_max)
PRESETS = {
    "tinyllama-1.1b": (2048, 5632, 22, 32, 4, 32000, 0, 0, 2048),
    "llama2-7b": (4096, 11008, 32, 32, 32, 32000, 0, 0, 4096),
    "llama2-13b": (5120, 13824, 40, 40, 40, 32000, 0, 0, 4096),
    "llama2-70b": (8192, 28672, 80, 64, 8, 32000, 0, 0, 4096),
    "llama3-8b": (4096, 14336, 32, 32, 8, 128256, 0, 0, 8192),
    "mixtral-8x7b": (4096, 14336, 32, 32, 8, 32000, 8, 2, 32768),
    "grok-314b": (6144, 32768, 64, 48, 8, 131072, 8, 2, 8192),
}


def _cfg(name_or_path: str):
    from dllama_tpu.models.config import tiny_config

    if os.path.exists(name_or_path):
        from dllama_tpu.io import mfile
        from dllama_tpu.models.config import ModelConfig
        return ModelConfig.from_spec(mfile.read_spec(name_or_path))
    if name_or_path not in PRESETS:
        raise SystemExit(f"unknown model {name_or_path!r}; presets: "
                         f"{', '.join(PRESETS)} (or a .m path)")
    d, f, l, h, hkv, v, e, a, s = PRESETS[name_or_path]
    return tiny_config(dim=d, hidden_dim=f, n_layers=l, n_heads=h,
                       n_kv_heads=hkv, vocab_size=v, n_experts=e,
                       n_active_experts=a, seq_len=s)


def plan(cfg, tp=1, sp=1, dp=1, ep=1, seq_len=None, batch=1,
         kv_bytes=2, quant=True) -> dict:
    """Per-chip byte breakdown for cfg on a tp×sp×dp×ep mesh.

    Besides residency, the plan reports ``decode_read_per_step``: the
    weight bytes one decode step streams from HBM across the WHOLE mesh —
    dense weights once, plus only the ``n_active_experts`` routed experts'
    FFN bytes for MoE (non-owner ep shards read nothing: the lax.cond
    skip in q40._sharded_matmul_ep).  Dividing by aggregate HBM bandwidth
    gives the bandwidth-bound ms/token floor."""
    from dllama_tpu.models.params import param_shapes

    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide nKvHeads={cfg.n_kv_heads} — the mesh "
            "cannot be realized (nSlices ≤ nKvHeads, transformer.cpp:88-91)")
    if cfg.is_moe and cfg.n_experts % ep:
        raise ValueError(f"ep={ep} does not divide nExperts={cfg.n_experts}")
    s = seq_len or cfg.seq_len
    if s % sp:
        raise ValueError(f"sp={sp} does not divide seq_len={s}")
    shapes = param_shapes(cfg)
    w_sharded = 0   # matmul weights: shard 1/tp (and experts 1/ep)
    w_repl = 0      # embedding/norms/router: replicated, bf16(2B)/f32(4B)
    decode_read = 0  # weight bytes one decode step reads, whole mesh
    for k, shp in shapes.items():
        n = 1
        for x in shp:
            n *= x
        if k in ("embedding",):
            w_repl += n * 2
            decode_read += cfg.dim * 2  # one row gathered per token
        elif k.startswith("rms") or k.endswith("_a_norm"):
            w_repl += n * 4
            decode_read += n * 4
        elif k in ("router", "wkv_b"):
            # wkv_b (MLA): dequantized at load, the absorbed form multiplies
            # it head by head
            w_repl += n * 2
            decode_read += n * 2
        else:
            per_w = Q40_BYTES_PER_WEIGHT if quant else 2
            is_expert = k in ("up", "gate", "down")
            div = tp * (ep if is_expert else 1)
            if quant:
                # packed planes pad an input axis that the tile rule cannot
                # cut into healthy tiles (q40.padded_n; up to +9%, e.g.
                # TinyLlama's 5632→6144; DeepSeek-V2's 1536 is stored as it
                # is) — estimate what HBM actually holds, not the logical
                # element count (ADVICE r03)
                from dllama_tpu.ops.q40 import padded_n
                *lead, nin, dout = shp
                n = 1
                for x in lead:
                    n *= x
                n *= padded_n(nin) * dout
            w_sharded += n * per_w / div
            if is_expert:
                # only the routed experts' tiles are streamed, each read
                # exactly once on its owner shard (the ep lax.cond skip)
                decode_read += n * per_w * cfg.n_active_experts / cfg.n_experts
            else:
                decode_read += n * per_w
    # a GQA cache holds 2 x kv heads x head size a token a layer; a latent
    # (MLA) cache one latent and one rotated key, and has no head axis for tp
    # and a windowed model's window layers a ring of the window plus one
    # prefill chunk, whatever ``s`` is (models/windowed.py)
    positions = cfg.n_full_layers * s + (
        cfg.n_window_layers * cfg.window_ring(s) if cfg.window else 0)
    cache = positions * batch * cfg.kv_values_per_token * kv_bytes
    cache /= (1 if cfg.is_mla else tp) * sp * max(dp, 1)  # kv heads /tp, seq /sp, batch /dp
    per_chip = w_sharded + w_repl + cache + OVERHEAD
    return {
        "weights_sharded": w_sharded, "weights_replicated": w_repl,
        "kv_cache": cache, "overhead": OVERHEAD, "per_chip": per_chip,
        "decode_read_per_step": decode_read,
        "fits_v5e": per_chip <= V5E_HBM,
    }


def find_fit(cfg, seq_len=None, budget=V5E_HBM, max_devices=256,
             batch=1, kv_bytes=2, quant=True) -> tuple | None:
    """Smallest (tp, sp, ep) whose per-chip footprint fits ``budget``.

    tp obeys the reference's nSlices ≤ nKvHeads constraint
    (transformer.cpp:88-91) and must divide the kv-head count; sp must
    divide the sequence length; ep (MoE only) must divide the expert
    count.  Returns (tp, sp, ep, plan) or None."""
    s = seq_len or cfg.seq_len
    tps = [t for t in range(1, cfg.n_kv_heads + 1) if cfg.n_kv_heads % t == 0]
    eps = ([e for e in range(1, cfg.n_experts + 1) if cfg.n_experts % e == 0]
           if cfg.is_moe else [1])
    best = None
    for tp in tps:
        for ep in eps:
            for sp in (1, 2, 4, 8, 16, 32):
                n = tp * sp * ep
                if s % sp or n > max_devices:
                    continue
                if best is not None and n >= best[0] * best[1] * best[2]:
                    continue
                p = plan(cfg, tp=tp, sp=sp, ep=ep, seq_len=s, batch=batch,
                         kv_bytes=kv_bytes, quant=quant)
                if p["per_chip"] <= budget:
                    best = (tp, sp, ep, p)
                    break  # larger sp only helps cache; this (tp, ep) fits
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", help="preset name or .m path")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--kv-dtype-bytes", type=float, default=2,
                    help="bytes per cache element: 4 f32, 2 bf16 (default), "
                         "1.03 for the int8 cache (--kv-cache-dtype q8: "
                         "1 B values + 4 B/Dh scales)")
    ap.add_argument("--dense", action="store_true",
                    help="dense bf16 weights instead of packed Q40")
    ap.add_argument("--fit", action="store_true",
                    help="search the smallest (tp, sp) that fits one v5e chip budget")
    args = ap.parse_args()

    cfg = _cfg(args.model)
    s = args.seq or cfg.seq_len
    p = plan(cfg, tp=args.tp, sp=args.sp, dp=args.dp, ep=args.ep,
             seq_len=s, batch=args.batch, kv_bytes=args.kv_dtype_bytes,
             quant=not args.dense)
    print(f"model {args.model}  seq {s}  mesh tp={args.tp} sp={args.sp} "
          f"dp={args.dp} ep={args.ep}")
    for k in ("weights_sharded", "weights_replicated", "kv_cache", "overhead"):
        print(f"  {k:20s} {p[k] / 1e9:8.2f} GB/chip")
    print(f"  {'per_chip':20s} {p['per_chip'] / 1e9:8.2f} GB/chip "
          f"{'✓ fits' if p['fits_v5e'] else '✗ exceeds'} 16 GB v5e")
    if args.fit:
        best = find_fit(cfg, seq_len=s, batch=args.batch,
                        kv_bytes=args.kv_dtype_bytes, quant=not args.dense)
        if best is None:
            print("  no (tp ≤ nKvHeads, sp ≤ 32, ep ≤ nExperts) mesh "
                  "fits a 16 GB chip")
        else:
            tp, sp, ep, bp = best
            print(f"  smallest fitting mesh: tp={tp} sp={sp} ep={ep} "
                  f"({tp * sp * ep} chips, {bp['per_chip'] / 1e9:.2f} GB/chip)")


if __name__ == "__main__":
    main()
