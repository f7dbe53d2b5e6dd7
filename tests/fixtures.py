"""Shared fixture builders: tiny `.m`/`.t` files usable end-to-end
(CLI/API subprocess tests) — the analogue of the reference's generated
xorshift weight fixtures (llama2-tasks-test.cpp:556-562)."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.synth import write_synth_tokenizer as write_tiny_tokenizer  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (Hkv, page size): one geometry where the two differ and one where they
# coincide, so a paged reader or writer with the page's axes swapped cannot
# pass by symmetry
PAGE_GEOMETRIES = [(2, 8), (4, 4)]
PAGE_GEOMETRY_IDS = ["hkv2-ps8", "hkv4-ps4"]


def pool_from_logical(kv, table, n_pages: int, ps: int) -> np.ndarray:
    """Place logical head-major KV ``(L, B, Hkv, S, last)`` into a paged pool
    ``(L, n_pages, ps, Hkv, last)`` through ``table`` (B, S // ps): position
    ``p`` of row ``r`` goes to ``pool[:, table[r, p // ps], p % ps]``.  This
    is what the pool's axis order *means*, written with none of the program's
    readers or writers, so a test that compares against it pins the order
    itself (rows' pages must be distinct)."""
    kv, table = np.asarray(kv), np.asarray(table)
    nl, b, hkv, s, last = kv.shape
    pool = np.zeros((nl, n_pages, ps, hkv, last), kv.dtype)
    for r in range(b):
        for p in range(s):
            pool[:, table[r, p // ps], p % ps] = kv[:, r, :, p]
    return pool


def write_tiny_model(path, *, arch=mfile.ARCH_LLAMA, ftype=quants.Q80,
                     vocab_size=300, n_experts=0, seq_len=128, seed=0,
                     dim=64, hidden_dim=96, n_kv_heads=2) -> mfile.ModelSpec:
    spec = mfile.ModelSpec(
        arch=arch, dim=dim, hidden_dim=hidden_dim, n_layers=2, n_heads=4,
        n_kv_heads=n_kv_heads,
        n_experts=n_experts, n_active_experts=2 if n_experts else 0,
        vocab_size=vocab_size, seq_len=seq_len, hidden_act=mfile.ACT_SILU,
        rope_theta=10000.0, weights_ftype=ftype)
    rng = np.random.RandomState(seed)
    with mfile.MFileWriter(path, spec) as w:
        for t in w.plan:
            w.write_tensor(t.name, (rng.randn(*t.shape) * 0.05).astype(np.float32))
    return spec


def bf16_exact_scales(tree):
    """``tree`` with the f16 scales of its Q40 tensors cut to four significant
    bits, so that every weight ``(v − 8) · s`` (four bits times four) is exact
    in bf16.  The dot body and the XLA path round each weight to bf16, the
    one-row body rounds none (PR 50): on such weights the rounding changes
    nothing and all three compute the same function up to the order of their
    sums, so a test may hold a decoded row on the fused kernel against the XLA
    path, or one shard against several, as tightly as it holds many rows."""
    import dataclasses

    import jax

    from dllama_tpu.ops import q40

    def is_q40(t):
        return isinstance(t, q40.QTensor)

    return jax.tree.map(
        lambda t: dataclasses.replace(t, scales=t.scales & np.uint16(0xFF80))
        if is_q40(t) else t, tree, is_leaf=is_q40)


def kernel_bodies(lowered_text: str) -> list[bytes]:
    """The serialized Mosaic module of every Pallas kernel in a TPU
    lowering's StableHLO text (``custom_call_config.body`` of each
    ``tpu_custom_call``, base64-decoded): the bytes JAX's persistent compile
    cache hashes for a kernel."""
    import base64
    import re
    return [base64.b64decode(b) for b in re.findall(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text)]


def free_port() -> int:
    """An OS-assigned free TCP port (shared by every server-spawning test)."""
    import socket
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def cpu_env(n_devices: int = 1) -> dict:
    """Subprocess env that actually selects the CPU backend (shared recipe,
    see dllama_tpu/hostenv.py)."""
    from dllama_tpu.hostenv import forced_cpu_env

    env = forced_cpu_env(n_devices)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_cli(args: list[str], *, input_text: str | None = None, n_devices: int = 1,
            timeout: int = 240, env: dict | None = None) -> subprocess.CompletedProcess:
    """``env`` overlays extra variables (e.g. DLLAMA_FAULTS) on the
    forced-CPU base environment."""
    full_env = cpu_env(n_devices)
    if env:
        full_env.update(env)
    return subprocess.run(
        [sys.executable, "-m", "dllama_tpu", *args], cwd=REPO, env=full_env,
        input=input_text, capture_output=True, text=True, timeout=timeout)
