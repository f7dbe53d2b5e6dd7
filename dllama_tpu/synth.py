"""Synthetic models at real shapes: named configs plus seeded `.m`/`.t`
files written at packed size, for the chip smoke test and the tests' fixtures
(the benchmark writes its own: `benchmarks/harness/mformat.py`).

No real model download exists in the environments this runs in, and a
smoke or timing run needs the operator surface (file → loader → Engine),
not a zero-buffer bypass.  Weights are random, made from a seed; widths
are the published ones, depth may be cut by the caller.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from . import quants
from .io import mfile, tfile

CHATML_JINJA = "{% for message in messages %}<|im_start|>...jinja...{% endfor %}"


# name → widths, depth, context and compute dtype.  Plain data, so the
# synthesizer (and chip_smoke.py's parent, which must never import JAX)
# reads it without touching the runtime.
MODEL_SHAPES = {
    # README.md measurement target shapes
    "llama2-7b": dict(dim=4096, hidden_dim=11008, n_layers=32, n_heads=32,
                      n_kv_heads=32, vocab_size=32000, seq_len=1024,
                      dtype="bfloat16"),
    # long-context variant: a 16k cache (2×4.3 GB bf16) next to the ~4 GB
    # packed weights — decode stays fast only because attention reads the
    # live prefix, not the whole cache (ops/attention.py
    # live_gqa_attention)
    "llama2-7b-long": dict(dim=4096, hidden_dim=11008, n_layers=32,
                           n_heads=32, n_kv_heads=32, vocab_size=32000,
                           seq_len=16384, dtype="bfloat16"),
    # the BASELINE.json north-star config (≥80 tok/s/chip on v5e-8): GQA
    # (8 kv heads) + 128k vocab — the wcls matmul alone is ~295 MB packed,
    # so this also exercises the kernel's widest output shape
    "llama3-8b": dict(dim=4096, hidden_dim=14336, n_layers=32, n_heads=32,
                      n_kv_heads=8, vocab_size=128256, seq_len=2048,
                      rope_theta=500000.0, dtype="bfloat16"),
    # 13B Q40 packs to ~7.3 GB — fits one v5e chip's 16 GB HBM next to its
    # bf16 cache
    "llama2-13b": dict(dim=5120, hidden_dim=13824, n_layers=40, n_heads=40,
                       n_kv_heads=40, vocab_size=32000, seq_len=1024,
                       dtype="bfloat16"),
    # launch.py:7
    "tinyllama-1.1b": dict(dim=2048, hidden_dim=5632, n_layers=22,
                           n_heads=32, n_kv_heads=4, vocab_size=32000,
                           seq_len=2048, dtype="bfloat16"),
    # the first mixture-of-experts model run on the chip (benchmarks/configs/
    # olmoe-1b-7b.json has the published keys): 64 experts of 1024, 8 a
    # token, MHA 16 x 128; "arch" is a name of mfile.ARCH_NAMES
    "olmoe-1b-7b": dict(arch="olmoe", dim=2048, hidden_dim=1024, n_layers=16,
                        n_heads=16, n_kv_heads=16, n_experts=64,
                        n_active_experts=8, vocab_size=50304, seq_len=4096,
                        dtype="bfloat16"),
    "cpu-tiny-olmoe": dict(arch="olmoe", dim=256, hidden_dim=128, n_layers=2,
                           n_heads=8, n_kv_heads=8, n_experts=64,
                           n_active_experts=8, vocab_size=4096, seq_len=256,
                           dtype="float32"),
    # Granite-4.0-H-Small (ibm-granite/granite-4.0-h-small): mixer layers 9 : 1
    # with position-free attention layers, 72 experts top-10 and a shared MLP;
    # the header keys past the fourteen ride in the shape (``_ext``)
    "granite-4.0-h-small": dict(
        arch="granitemoehybrid", dim=4096, hidden_dim=1536, n_layers=40,
        n_heads=32, n_kv_heads=8, n_experts=72, n_active_experts=10,
        vocab_size=100352, seq_len=2048, dtype="bfloat16", norm_eps=1e-5,
        head_dim=128, window_period=10, window_full_at=5, moe_hidden_dim=768,
        n_shared_experts=2, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        ssm_groups=1, ssm_conv=4, mup_embedding=12.0, mup_head=0.0625,
        mup_key=0.0078125 * 128 ** 0.5, mup_attn_out=0.22, mup_ssm_out=0.22,
        mup_down=0.22),
    "cpu-tiny-granite": dict(
        arch="granitemoehybrid", dim=128, hidden_dim=64, n_layers=5,
        n_heads=8, n_kv_heads=2, n_experts=12, n_active_experts=3,
        vocab_size=300, seq_len=256, dtype="float32", norm_eps=1e-5,
        head_dim=16, window_period=5, window_full_at=2, moe_hidden_dim=32,
        n_shared_experts=2, ssm_heads=4, ssm_head_dim=64, ssm_state=32,
        ssm_groups=1, ssm_conv=4, mup_embedding=12.0, mup_head=0.0625,
        mup_key=0.3, mup_attn_out=0.22, mup_ssm_out=0.22, mup_down=0.22),
    "cpu-tiny": dict(dim=512, hidden_dim=1408, n_layers=4, n_heads=8,
                     n_kv_heads=8, vocab_size=4096, seq_len=256,
                     dtype="float32"),
}


def model_shape(name: str) -> dict:
    if name not in MODEL_SHAPES:
        raise ValueError(name)
    return dict(MODEL_SHAPES[name])


def _arch_id(shape: dict) -> int:
    return {v: k for k, v in mfile.ARCH_NAMES.items()}[shape.get("arch", "llama")]


def _ext(shape: dict) -> dict:
    """The header keys past the fourteen that a shape states (none for the
    archs that have none)."""
    return {name: shape[name] for _, name, _ in mfile.ALL_EXT_KEYS if name in shape}


def model_cfg(name: str):
    """The runtime's ``ModelConfig`` for a named shape (imports JAX)."""
    import jax.numpy as jnp

    from .models.config import tiny_config
    shape = model_shape(name)
    return tiny_config(**dict(shape, dtype=getattr(jnp, shape["dtype"]),
                              arch=_arch_id(shape)))


def write_synth_tokenizer(path, vocab_size=300) -> tfile.TokenizerData:
    """Vocab: 3 specials (+ 256 byte tokens when it fits) + a few words;
    chatml template.  Small vocab sizes skip the byte-fallback pieces."""
    vocab = [b"<unk>", b"<s>", b"</s>"]
    words = [b" ", b"a", b"b", b"e", b"h", b"i", b"l", b"o", b"he", b"ll",
             b"hell", b"hello", b"hi", b" hi", b" hello",
             b"<|im_end|>", b"<|im_start|>"]
    if vocab_size >= 3 + 256 + len(words):
        vocab += [f"<0x{i:02X}>".encode() for i in range(256)]
    vocab += words
    if len(vocab) > vocab_size:
        raise ValueError(f"vocab_size {vocab_size} too small for fixture")
    while len(vocab) < vocab_size:
        vocab.append(f"<extra_{len(vocab)}>".encode())
    scores = [float(len(v)) if v in words else 0.0 for v in vocab]
    t = tfile.TokenizerData(
        vocab=vocab, scores=scores, bos_id=1, eos_id=2,
        chat_eos_id=vocab.index(b"<|im_end|>"),
        chat_template=CHATML_JINJA, chat_stop=None)
    tfile.write_tfile(path, t)
    return t


def synth_model_files(name: str, dirpath: str, n_layers: int | None = None,
                      seed: int = 0) -> tuple[str, str]:
    """Synthesize a full-width Q40 `.m` (+ matching `.t`) at packed size:
    seeded random nibbles and per-block f16 scales written via
    MFileWriter.write_raw with no f32 transit; norm weights sit near 1 so
    activations and logits keep a healthy scale through every layer.
    ``n_layers`` cuts depth (never width).  Existing files are reused."""
    shape = model_shape(name)
    if n_layers is not None:
        shape["n_layers"] = n_layers
    spec = mfile.ModelSpec(
        arch=_arch_id(shape), dim=shape["dim"], hidden_dim=shape["hidden_dim"],
        n_layers=shape["n_layers"], n_heads=shape["n_heads"],
        n_kv_heads=shape["n_kv_heads"], n_experts=shape.get("n_experts", 0),
        n_active_experts=shape.get("n_active_experts", 0),
        vocab_size=shape["vocab_size"], seq_len=shape["seq_len"],
        hidden_act=mfile.ACT_SILU,
        rope_theta=shape.get("rope_theta", 10000.0),
        weights_ftype=quants.Q40, **_ext(shape))
    stem = f"{name}-L{spec.n_layers}-s{seed}-synth"
    mpath = os.path.join(dirpath, stem + ".m")
    tpath = os.path.join(dirpath, stem + ".t")
    if not os.path.exists(tpath):
        write_synth_tokenizer(tpath, vocab_size=spec.vocab_size)
    if os.path.exists(mpath):
        return mpath, tpath
    rng = np.random.default_rng(seed)
    t0 = time.time()
    with mfile.MFileWriter(mpath + ".part", spec) as w:
        for tinfo in w.plan:
            if tinfo.ftype == quants.Q40:
                blocks = int(np.prod(tinfo.shape)) // 32
                arr = np.empty((blocks, quants.Q40_BLOCK_BYTES), np.uint8)
                scales = (0.004 + 0.008 * rng.random(blocks, np.float32))
                arr[:, :2] = scales.astype(np.float16)[:, None].view(np.uint8)
                arr[:, 2:] = rng.integers(
                    0, 1 << 63, blocks * 2, np.int64).view(np.uint8).reshape(
                        blocks, 16)
                w.write_raw(tinfo.name, arr)
            else:  # f32 norms/embedding in Q40 plans
                x = rng.standard_normal(tinfo.shape, np.float32) * 0.02
                w.write_tensor(tinfo.name, x + 1.0 if x.ndim == 1 else x)
    os.replace(mpath + ".part", mpath)
    print(f"synth: wrote {mpath} ({os.path.getsize(mpath) / 1e9:.2f} GB in "
          f"{time.time() - t0:.0f}s)", file=sys.stderr)
    return mpath, tpath
