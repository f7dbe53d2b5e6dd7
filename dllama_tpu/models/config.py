"""Model configuration.

Bridges the on-disk ``ModelSpec`` (`.m` header, transformer.cpp:12-125) to
the runtime: adds compute dtype and derives the per-arch structural flags
that the reference encodes as three separate hand-built task lists
(`buildLlamaArch` llama2-tasks.cpp:241-298, `buildGrok1Arch`
grok1-tasks.cpp:275-354, `buildMixtralArch` mixtral-tasks.cpp:5-78), and
those of OLMoE (`ARCH_OLMOE`, beyond the reference).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax.numpy as jnp

from ..io import mfile

# Grok-1 scaling constants (grok1-tasks.cpp:13, :272)
GROK_EMBEDDING_SCALE = 78.38367176906169
GROK_LOGIT_SCALE = 0.5773502691896257


@dataclass(frozen=True)
class ModelConfig:
    arch: int
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    n_experts: int
    n_active_experts: int
    vocab_size: int
    seq_len: int
    hidden_act: int
    rope_theta: float
    dtype: jnp.dtype = jnp.float32
    # matmul implementation for Q40-quantized weights: "pallas" (fused
    # kernel, single-chip), "xla" (partitionable emulation, used under TP
    # sharding and on CPU), or "auto" (pallas on TPU for decode-sized
    # inputs, xla otherwise).  Static so each choice compiles its own
    # program.
    quant_impl: str = "auto"
    # static flag set by the engine for a from-scratch prefill on an sp>1
    # mesh: attention runs blockwise ring attention over the fresh
    # sequence-sharded q/k/v (ops/sp_attention.py) instead of the
    # cache-reading one-round combine — O(T/sp) activation memory
    ring_prefill: bool = False

    @property
    def head_size(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_size * self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def rope_interleaved(self) -> bool:
        """Llama uses adjacent-pair RoPE; Grok-1/Mixtral use the rotate-half
        ("Falcon") convention (transformer.cpp:227-231)."""
        return self.arch == mfile.ARCH_LLAMA

    @property
    def add_bos(self) -> bool:
        """Whether prompts get a BOS token (reference: dllama.cpp:27 —
        Grok-1 prompts are encoded without BOS; chat mode always adds it)."""
        return self.arch != mfile.ARCH_GROK1

    @property
    def embedding_scale(self) -> float:
        return GROK_EMBEDDING_SCALE if self.arch == mfile.ARCH_GROK1 else 1.0

    @property
    def logit_scale(self) -> float:
        return GROK_LOGIT_SCALE if self.arch == mfile.ARCH_GROK1 else 1.0

    @property
    def post_block_norms(self) -> bool:
        """Grok-1 normalizes each sub-block's *output* before the residual
        add (grokRmfFfnNorm / grokMoeRmsNormFinal, grok1-tasks.cpp:16-41,
        :245-263); Llama/Mixtral add raw outputs to the residual."""
        return self.arch == mfile.ARCH_GROK1

    @property
    def qk_norm(self) -> bool:
        """OLMoE RMS-normalises the whole q and the whole k projection (one
        weight vector each, ``layers.{i}.q_norm`` / ``k_norm``) before the
        split into heads and before RoPE; no other arch has the vectors."""
        return self.arch == mfile.ARCH_OLMOE

    @property
    def norm_topk_prob(self) -> bool:
        """Mixtral and Grok-1 renormalise the chosen experts' probabilities
        to sum to 1 (grok1-tasks.cpp:60-114); OLMoE (``norm_topk_prob:
        false``) uses them as the softmax over all experts gave them."""
        return self.arch != mfile.ARCH_OLMOE

    @classmethod
    def from_spec(cls, spec: mfile.ModelSpec, dtype=jnp.float32) -> "ModelConfig":
        return cls(
            arch=spec.arch, dim=spec.dim, hidden_dim=spec.hidden_dim,
            n_layers=spec.n_layers, n_heads=spec.n_heads,
            n_kv_heads=spec.n_kv_heads, n_experts=spec.n_experts,
            n_active_experts=spec.n_active_experts, vocab_size=spec.vocab_size,
            seq_len=spec.seq_len, hidden_act=spec.hidden_act,
            rope_theta=spec.rope_theta, dtype=dtype)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


def tiny_config(arch=mfile.ARCH_LLAMA, *, dim=64, hidden_dim=96, n_layers=2,
                n_heads=4, n_kv_heads=2, n_experts=0, n_active_experts=0,
                vocab_size=128, seq_len=64, hidden_act=mfile.ACT_SILU,
                rope_theta=10000.0, dtype=jnp.float32) -> ModelConfig:
    """Small config for tests — the analogue of the reference's hand-sized
    test fixtures (llama2-tasks-test.cpp:528-554)."""
    return ModelConfig(arch=arch, dim=dim, hidden_dim=hidden_dim,
                       n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_kv_heads,
                       n_experts=n_experts, n_active_experts=n_active_experts,
                       vocab_size=vocab_size, seq_len=seq_len,
                       hidden_act=hidden_act, rope_theta=rope_theta, dtype=dtype)
