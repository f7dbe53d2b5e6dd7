"""Core elementwise / normalization / RoPE ops.

TPU-native equivalents of the reference kernel layer
(/root/reference/src/funcs.cpp).  Where the reference hand-slices every op
across a spin-barrier thread pool (funcs.cpp:126-146 etc.), here each op is
a pure jnp function: XLA fuses them into the surrounding matmuls, which is
the TPU analogue of the reference's fusion-by-hand.

Numerics notes (for golden parity):
* rmsnorm epsilon placement matches funcs.cpp:95-124:
  ``1/sqrt(mean(x²) + 1e-5)`` — eps *after* the mean.
* gelu is the tanh approximation (funcs.cpp:488-497).
* RoPE has two conventions, selected per arch (transformer.cpp:227-231):
  - ``llama``: adjacent-pair rotation, angle per pair index within the head
    (LlamaRopeCommand, commands.cpp:160-199)
  - ``neox`` (the reference's "Falcon" rope, used by Grok-1/Mixtral):
    rotate-half within the head (FalconRopeCommand, commands.cpp:201-229)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-5  # funcs.cpp:120


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = RMS_EPS) -> jax.Array:
    """RMS-normalize over the last axis, then scale by ``weight``.

    Matches ``rms`` + ``rmsnorm`` (funcs.cpp:95-146): the sum-of-squares is
    accumulated in f32 regardless of the activation dtype.
    """
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    return (weight.astype(jnp.float32) * (xf * inv)).astype(x.dtype)


def silu(x: jax.Array) -> jax.Array:
    """x · σ(x) (funcs.cpp:499-507)."""
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x: jax.Array) -> jax.Array:
    """tanh-approximated GELU (funcs.cpp:488-497)."""
    xf = x.astype(jnp.float32)
    y = 0.5 * xf * (1.0 + jnp.tanh(0.7978845608028654 * (xf + 0.044715 * xf * xf * xf)))
    return y.astype(x.dtype)


# TransformerHiddenAct (transformer.hpp:45-48); 2 is this format's ReLU
# (io/mfile.py ACT_RELU: SmallThinker's ReGLU experts)
ACTIVATIONS = {0: gelu_tanh, 1: silu, 2: jax.nn.relu}


def rope_angles(positions: jax.Array, head_size: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for positions: shapes ``positions.shape + (head_size/2,)``.

    Frequency ``j`` is ``theta^(-2j/head_size)`` — identical for both
    conventions (commands.cpp:171-172, 216-217); only the pairing differs.
    """
    half = head_size // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_size))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, *, interleaved: bool) -> jax.Array:
    """Rotate ``x`` of shape (..., n_heads, head_size).

    ``cos``/``sin`` have shape (..., head_size/2) and broadcast over heads.

    interleaved=True  → llama convention: pairs (2j, 2j+1)
    interleaved=False → neox/"falcon" convention: pairs (j, j+half)
    """
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    c = cos[..., None, :]
    s = sin[..., None, :]
    if interleaved:
        x0 = xf[..., 0::2]
        x1 = xf[..., 1::2]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        out = jnp.stack([r0, r1], axis=-1).reshape(x.shape)
    else:
        half = x.shape[-1] // 2
        x0 = xf[..., :half]
        x1 = xf[..., half:]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        out = jnp.concatenate([r0, r1], axis=-1)
    return out.astype(orig_dtype)


def softmax_f32(x: jax.Array, axis: int = -1) -> jax.Array:
    """Max-shifted softmax in f32 (funcs.cpp:64-93)."""
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=axis, keepdims=True)
    e = jnp.exp(xf - m)
    return e / jnp.sum(e, axis=axis, keepdims=True)
