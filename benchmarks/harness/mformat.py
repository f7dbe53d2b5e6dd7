"""The distributed-llama ``.m`` model file as the benchmark writes and reads
it: the benchmark's own copy of the format (header of (key, value) i32 pairs,
then tensors in a fixed order), so that neither the synthesizer nor the plain
reference goes through the loader under test.  What is here is what every
architecture shares; which header values and which tensors a file has is its
architecture's own ``header`` and ``plan`` (``models/<name>.py``).

Q40: blocks of 32 values = one f16 scale + 16 bytes; value ``i`` of a block is
the low nibble of byte ``i``, value ``i + 16`` the high nibble, and a value is
``(nibble - 8) * scale``.  A matmul weight is ``(d_out, n_in)`` row-major, its
blocks running along ``n_in``.
"""

from __future__ import annotations

import os
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MAGIC = 0xA00ABCD
F32, Q40 = 0, 2
Q40_BLOCK = 18
HEADER_KEYS = ("version", "arch", "dim", "hidden_dim", "n_layers", "n_heads",
               "n_kv_heads", "n_experts", "n_active_experts", "vocab_size",
               "seq_len", "hidden_act", "rope_theta", "weights_ftype")


def pack_header(vals: dict) -> bytes:
    """The header's bytes from a value for every key of ``HEADER_KEYS``."""
    data = b"".join(struct.pack("<ii", k, int(vals[name]))
                    for k, name in enumerate(HEADER_KEYS))
    return struct.pack("<ii", MAGIC, 8 + len(data)) + data


def lay_out(names: list[tuple[str, tuple, int]],
            start: int) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every (name, shape, ftype), in
    file order from ``start`` (the header's length)."""
    out, pos = [], start
    for name, shp, ft in names:
        n = int(np.prod(shp))
        nbytes = 4 * n if ft == F32 else n // 32 * Q40_BLOCK
        out.append((name, shp, ft, pos, nbytes))
        pos += nbytes
    return out


def read_header(path: str) -> dict:
    with open(path, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        if magic != MAGIC:
            raise ValueError(f"{path}: not a v2 .m file")
        kv = struct.unpack(f"<{(size - 8) // 4}i", f.read(size - 8))
    return {HEADER_KEYS[k]: v for k, v in zip(kv[::2], kv[1::2])}


def _tensor_bytes(seed: int, index: int, shp: tuple, ftype: int,
                  dead_rows: int = 0) -> np.ndarray:
    """Tensor ``index``'s bytes from (seed, index) alone: Q40 with scales in
    [0.004, 0.012) and nibbles uniform over 1..15 but for 8 twice as likely
    (values -7..7 with mean 0: a weight matrix whose mean is not 0 has one
    dominant direction, and then every prompt yields the same token); f32
    embeddings N(0, 0.02); norm weights 1 + N(0, 0.02) so activations keep a
    healthy scale.  The first ``dead_rows`` output rows get scale 0."""
    rng = np.random.default_rng([seed, index])
    n = int(np.prod(shp))
    if ftype == F32:
        x = rng.standard_normal(n, np.float32) * np.float32(0.02)
        return (x + np.float32(1.0) if len(shp) == 1 else x).view(np.uint8)
    blocks = n // 32
    arr = np.empty((blocks, Q40_BLOCK), np.uint8)
    scales = 0.004 + 0.008 * rng.random(blocks, np.float32)
    arr[:, :2] = scales.astype(np.float16)[:, None].view(np.uint8)
    q = rng.integers(0, 1 << 63, blocks * 2, np.int64).view(np.uint8)
    q |= ((q & 0x0F) == 0).view(np.uint8) << 3   # nibble 0 -> 8 (value 0)
    q |= ((q & 0xF0) == 0).view(np.uint8) << 7
    arr[:, 2:] = q.reshape(blocks, 16)
    arr[:dead_rows * (shp[-1] // 32), :2] = 0
    return arr.reshape(-1)


def synthesize(path: str, model, shape: dict, seed: int, workers: int = 8) -> None:
    """Write the model that ``model`` (the architecture's module) lays out for
    ``shape`` at packed size, tensors made in parallel (numpy's generators
    release the GIL) and written at their offsets."""
    t0 = time.time()
    tensors = model.plan(shape)
    part = path + ".part"
    fd = os.open(part, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
    try:
        os.pwrite(fd, model.header(shape), 0)

        def one(i: int) -> None:
            name, shp, ft, off, nbytes = tensors[i]
            # the output head never picks <unk>, <s> or </s>: every request
            # runs to its max_tokens, so the work of a run is fixed by its seed
            buf = _tensor_bytes(seed, i, shp, ft, 3 if name == "wcls" else 0)
            if buf.nbytes != nbytes:
                raise ValueError(f"tensor {i}: {buf.nbytes} B for {nbytes} B")
            mv = memoryview(buf)
            for lo in range(0, nbytes, 1 << 30):  # pwrite caps near 2 GiB
                os.pwrite(fd, mv[lo:lo + (1 << 30)], off + lo)

        with ThreadPoolExecutor(workers) as ex:
            list(ex.map(one, range(len(tensors))))
    finally:
        os.close(fd)
    os.replace(part, path)
    print(f"benchmark: wrote {path} ({os.path.getsize(path) / 1e9:.2f} GB in "
          f"{time.time() - t0:.0f} s)", file=sys.stderr)


def dequantize(raw: np.ndarray, shp: tuple, ftype: int) -> np.ndarray:
    """Plain numpy: a tensor's file bytes to float32 in its logical shape."""
    if ftype == F32:
        return raw.view(np.float32).reshape(shp)
    b = raw.reshape(-1, Q40_BLOCK)
    scale = b[:, :2].copy().view(np.float16).astype(np.float32)
    q = b[:, 2:]
    vals = np.concatenate([(q & 0xF).astype(np.int8) - 8,
                           (q >> 4).astype(np.int8) - 8], axis=1)
    return (vals.astype(np.float32) * scale).reshape(shp)
