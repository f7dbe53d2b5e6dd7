"""What every architecture's plain reference shares: a model file's tensors by
name, read through the benchmark's own reader (``mformat``) and dequantized to
float32 one tensor at a time, and the RMSNorm.  The block itself, in
straightforward float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, is the architecture's own
``last_logits`` (``models/<name>.py``); nothing here knows a block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import mformat

RMS_EPS = 1e-5


@jax.jit
def deq(blocks):  # (n_blocks, 18) uint8 -> (n_blocks, 32) float32
    scale = jax.lax.bitcast_convert_type(blocks[:, :2], jnp.float16)
    q = blocks[:, 2:]
    vals = jnp.concatenate([(q & 0xF).astype(jnp.int8) - 8,
                            (q >> 4).astype(jnp.int8) - 8], axis=1)
    return vals.astype(jnp.float32) * scale.astype(jnp.float32)[:, None]


def rms(x, w):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)


class Tensors:
    """The tensors of the file at ``model_path`` as ``plan`` lays them out."""

    def __init__(self, model_path: str, plan: list[tuple]):
        self.plan = {t[0]: t for t in plan}
        self.mm = np.memmap(model_path, np.uint8, "r")

    def raw(self, name: str) -> np.ndarray:
        _, _, _, off, nbytes = self.plan[name]
        return np.asarray(self.mm[off:off + nbytes])

    def weight(self, name: str):
        """A Q40 matrix as float32 ``(d_out, n_in)`` on the device."""
        return deq(jnp.asarray(self.raw(name).reshape(-1, mformat.Q40_BLOCK))
                   ).reshape(self.plan[name][1])

    def vec(self, name: str):
        return jnp.asarray(self.raw(name).view(np.float32))

    def rows(self, name: str, ids: np.ndarray) -> np.ndarray:
        """Rows ``ids`` of an f32 table (the embedding), from the host."""
        _, shp, _, off, nbytes = self.plan[name]
        return self.mm[off:off + nbytes].view(np.float32).reshape(shp)[ids]
