"""HBM memory planner (tools/memory_plan.py): byte math + fit search."""

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from tools.memory_plan import PRESETS, _cfg, find_fit, plan  # noqa: E402


def test_llama2_7b_single_chip_fits():
    cfg = _cfg("llama2-7b")
    p = plan(cfg)
    # ~6.74 G matmul weights × 0.5625 B ≈ 3.7 GB packed
    assert 3.3e9 < p["weights_sharded"] < 4.2e9
    assert p["fits_v5e"]


def test_tp_shards_weights_and_cache():
    cfg = _cfg("llama2-7b")
    p1, p8 = plan(cfg, tp=1), plan(cfg, tp=8)
    assert abs(p8["weights_sharded"] - p1["weights_sharded"] / 8) < 1e6
    assert abs(p8["kv_cache"] - p1["kv_cache"] / 8) < 1e6
    assert p8["weights_replicated"] == p1["weights_replicated"]


def test_sp_shards_cache_only():
    cfg = _cfg("llama3-8b")
    p1, p4 = plan(cfg, sp=1), plan(cfg, sp=4)
    assert abs(p4["kv_cache"] - p1["kv_cache"] / 4) < 1e6
    assert p4["weights_sharded"] == p1["weights_sharded"]


def test_grok_needs_multihost_scale():
    """docs/MEMORY.md's conclusion, as executable math: Grok-1-314B cannot
    fit 8 chips; the smallest fitting mesh is a 16-chip (multi-host on
    v5e-8 hardware) tp×ep layout."""
    cfg = _cfg("grok-314b")
    assert not plan(cfg, tp=8)["fits_v5e"]
    best = find_fit(cfg)
    assert best is not None
    tp, sp, ep, p = best
    assert tp * sp * ep == 16
    assert p["fits_v5e"]


def test_ep_shards_expert_weights():
    cfg = _cfg("mixtral-8x7b")
    p1, p8 = plan(cfg, ep=1), plan(cfg, ep=8)
    # experts dominate mixtral: /8 on experts cuts sharded bytes ~7.7x
    assert p8["weights_sharded"] < p1["weights_sharded"] / 6


def test_cli_runs():
    for model in ("llama2-7b", "grok-314b"):
        r = subprocess.run(
            [sys.executable, "tools/memory_plan.py", model, "--fit"],
            capture_output=True, text=True, timeout=120,
            cwd=REPO)
        assert r.returncode == 0, r.stderr
        assert "per_chip" in r.stdout and "mesh" in r.stdout


def test_presets_all_resolve():
    for name in PRESETS:
        cfg = _cfg(name)
        assert plan(cfg)["per_chip"] > 0


def test_unrealizable_mesh_rejected():
    import pytest
    cfg = _cfg("llama3-8b")  # 8 kv heads
    with pytest.raises(ValueError, match="nKvHeads"):
        plan(cfg, tp=32)


def test_deepseek_v2_two_layer_kinds_and_a_latent_cache():
    """DeepSeek-V2's published widths at the benchmark's 5 layers: the plan
    sums the MLA stacks of every layer, the dense FFN of the first and the
    experts of the other four, and charges the cache 576 values a token a
    layer, not 2 x 128 heads x 40."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import tiny_config

    cfg = tiny_config(
        arch=mfile.ARCH_DEEPSEEK2, dim=5120, hidden_dim=12288, n_layers=5,
        n_heads=128, n_kv_heads=128, n_experts=160, n_active_experts=6,
        vocab_size=102400, seq_len=2048, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_hidden_dim=1536, n_shared_experts=2, n_groups=8, topk_groups=3,
        n_dense_layers=1)
    p = plan(cfg, batch=16)
    assert p["kv_cache"] == 5 * 16 * 2048 * 576 * 2
    q = 18 / 32
    experts = 4 * 160 * 3 * 5120 * 1536 * q
    assert experts < p["weights_sharded"] < experts + 1.0e9
    # the embedding in bf16, and wkv_b dequantized at load (5 x 512 x 32768 x 2)
    assert p["weights_replicated"] > 102400 * 5120 * 2 + 5 * 512 * 32768 * 2
    assert p["fits_v5e"] and 11e9 < p["per_chip"] < 14e9
    # a decode step streams 6 of 160 experts a layer, not all of them
    assert p["decode_read_per_step"] < 0.2 * p["weights_sharded"]


def test_deepseek_v2_plan_counts_no_padded_column():
    """``down`` and ``wq_b`` have 1536 input columns, which the tile rule
    cuts whole (``q40.padded_n(1536) == 1536``): the plan holds every Q40
    matrix of DeepSeek-V2 at its logical size, 0.5625 B a weight, where the
    rule of PRs 28-33 stored those two with 2048 columns (0.94 GB of zeros at
    the benchmark's 5 layers)."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import param_shapes
    from dllama_tpu.ops.q40 import padded_n

    cfg = tiny_config(
        arch=mfile.ARCH_DEEPSEEK2, dim=5120, hidden_dim=12288, n_layers=5,
        n_heads=128, n_kv_heads=128, n_experts=160, n_active_experts=6,
        vocab_size=102400, seq_len=2048, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_hidden_dim=1536, n_shared_experts=2, n_groups=8, topk_groups=3,
        n_dense_layers=1)
    shapes = param_shapes(cfg)
    assert shapes["down"][-2:] == (1536, 5120) and padded_n(1536) == 1536
    dense = ("embedding", "router", "wkv_b")
    logical = sum(int(np.prod(shp)) for k, shp in shapes.items()
                  if k not in dense and not k.startswith("rms")
                  and not k.endswith("_a_norm"))
    p = plan(cfg, batch=16)
    assert p["weights_sharded"] == logical * 18 / 32
    padded = 4 * 160 * 512 * 5120 * 18 / 32   # what 2048 columns for down added
    assert padded > 0.9e9 and p["weights_sharded"] + padded > 10.3e9


def test_smallthinker_cache_is_bounded_by_the_window():
    """SmallThinker's published widths, the cell's 16384 positions: 13 full
    layers hold every position, 39 window layers a ring of 4096 + 512, so the
    cache is 0.80 GB where 52 full layers would hold 1.74; the whole model
    fits one chip; a decode step streams 6 of 64 experts a layer."""
    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import tiny_config

    cfg = tiny_config(
        arch=mfile.ARCH_SMALLTHINKER, dim=2560, hidden_dim=768, n_layers=52,
        n_heads=28, n_kv_heads=4, n_experts=64, n_active_experts=6,
        vocab_size=151936, seq_len=16384, hidden_act=mfile.ACT_RELU,
        head_dim=128, window=4096, window_period=4)
    p = plan(cfg)
    assert p["kv_cache"] == (13 * 16384 + 39 * 4608) * 2048
    assert 0.76e9 < p["kv_cache"] <= 0.81e9 < 52 * 16384 * 2048
    experts = 52 * 64 * 3 * 2560 * 768 * 18 / 32
    assert experts < p["weights_sharded"] < experts + 1.0e9
    assert p["fits_v5e"] and 13e9 < p["per_chip"] < 15.5e9
    assert p["decode_read_per_step"] < 0.2 * p["weights_sharded"]
    # a short cache is not rounded up to a ring
    assert plan(cfg, seq_len=2048)["kv_cache"] == 52 * 2048 * 2048
