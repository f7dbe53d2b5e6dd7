"""The stage names of the forward pass as the device trace shows them.

Every path (contiguous, slot, paged, tp) wraps the work of a stage in
``scope(<name>)``; XLA keeps the name in each op's ``op_name`` metadata and
the profiler in the op's ``tf_op`` stat, so a trace can be split by stage
whatever instruction numbers the compile gave (docs/OBSERVABILITY.md,
"Device scopes").  A scope changes metadata only: no executable, cache key
or number moves.  Which weight a Pallas matmul read is told by the scope;
its family by the kernel's ``name=`` (``q40_mm``, ``q40_mm_stacked``,
``q40_mm_experts``, ``q40_mm_chosen``, ``q40_mm_grouped``, ``q40_ring``,
``q8_mm``, ``q8_mm_stacked``, ``paged_attn_fused``).

The tuple is an interface: the benchmark's readers
(``benchmarks/layer_metrics/_scopes.py``) hold a copy and a test compares
the two.  An op whose name path carries none of these is ``unscoped``.

Inside ``moe`` the work is split further by :func:`part` into ``router``,
``experts``, ``combine`` and (DeepSeek-V2) ``shared``; inside ``qkv`` MLA's
``q_lora`` / ``kv_lora``; inside ``attn`` MLA's ``absorb`` / ``latent`` /
``expand`` and a windowed model's ``window`` / ``full`` by layer kind;
inside ``qkv`` also K-EXAONE's ``qk_norm``; and a gated short-convolution
layer (LFM2, ``ops/conv.py``) is part ``conv`` of the four scopes it passes
through, ``qkv`` (``W_in`` and the ``B * X`` gate), ``kv_write`` (the state's
write), ``attn`` (the state's read, the taps, the ``C`` gate) and ``wo``
(``W_out``), so that a reader of a scope still reads a whole layer and a
reader of the part reads the operator alone; a retention layer (Brumby,
``ops/retention.py``) is Llama's block with, inside ``qkv``, the gate's
``retention``, inside ``kv_write`` the ring's ``recent`` and the state's ``fold``
(not ``absorb``, which is MLA's), and inside ``attn`` the state's read ``state``
and the ring's rows ``recent``; a state-space mixer (Falcon-H1, ``ops/ssm.py``)
stands BESIDE attention in one block, so attention's own ops keep the bare
scopes and the mixer's carry ``ssm`` inside ``qkv`` (``W_in``, the ``dt``
projection, softplus) and ``wo`` (the gate, the grouped norm, ``W_out``), and
inside ``attn`` / ``kv_write`` the names above where the meaning is the same
(``conv``, ``state``, ``recent``, ``fold``);
inside ``norm`` the norms that close a branch before the residual add (Grok-1's
and Ouro's sandwich norms) are ``post``, the others keep the bare scope
(``PARTS``, by scope): plain sub-names, not scopes.  An
op's path then ends ``.../moe/experts/...`` and a reader that knows only
``SCOPES`` still files it under ``moe``; ``by-scope.json``'s op table
carries the whole path for the finer split.  Which strategy a compiled call
site of ``moe_ffn`` took is in the dispatch ledger, not in a name:
``dllama_matmul_dispatch_total{codec="moe", path="select"|"select-chosen"|
"all-experts"|"scan"|"unrolled"|"dense"}`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import jax

SCOPES = (
    "embed",     # token embedding lookup and its scale
    "norm",      # every rmsnorm
    "qkv",       # wqkv (or wq/wk/wv) projection and its split
    "rope",      # angles, rotation, the head-major transposes
    "kv_write",  # cache / pool update, the int8 quantize that feeds it
    "page_idx",  # paged write indices, once per slot step
    "attn",      # scores, softmax, values; gather or fused paged kernel
    "wo",        # output projection and the residual add it feeds
    "w13",       # fused gate+up projection and the activation product
    "w1",        # gate projection where not fused
    "w3",        # up projection where not fused, and the product
    "w2",        # down projection and the residual add it feeds
    "moe",       # router, expert matmuls, combine
    "head",      # last-position gather, output matmul, logit scale
    "sample",    # device sampler, key split, token feedback of a burst
)


# sub-names by the scope they split; an arch that lacks the work lacks the name
# (no dense-attention program has ``q_lora``, OLMoE's ``moe`` has no ``shared``)
PARTS = {
    "norm": (
        "post",      # a norm that CLOSES a branch before the residual add (Grok-1, Ouro)
    ),
    "qkv": (
        "q_lora",    # MLA: q's latent norm and the up-projection to the heads
        "kv_lora",   # MLA: the down-projection(s) from x, the latent's norm
        "qk_norm",   # K-EXAONE: the RMSNorm of each head of q and of k
        "conv",      # a short-convolution layer's W_in and its B * X gate
        "retention", # a retention layer's gate: W_g and its logsigmoid
        "ssm",       # a state-space mixer's W_in, dt projection and softplus
    ),
    "kv_write": (
        "conv",      # a short-convolution layer's state write (the ring of z)
        "recent",    # a retention layer's write of its ring of recent k, v, log-gate
        "fold",      # a retention layer's fold of the ring's oldest block into the state
    ),
    "attn": (
        "absorb",    # MLA absorbed form: W_uk into the query, W_uv out of the result
        "latent",    # MLA absorbed form: the walk over latent rows
        "expand",    # MLA expanded form: a block's rows through W_kvb, in the walk
        "window",    # a sliding-window layer's read (a row's ring, or a slot's ring of pages)
        "full",      # a periodic model's full layer's read
        "conv",      # a short-convolution layer's state read, taps and C gate
        "state",     # a retention layer's read of its state matrix and sum
        "recent",    # a retention layer's attention form over its ring's rows
    ),
    "wo": (
        "conv",      # a short-convolution layer's W_out
        "ssm",       # a state-space mixer's gate, grouped norm and W_out
    ),
    "moe": (
        "router",    # router logits, softmax, (groups,) top-k, the dense weight table
        "experts",   # the expert matmuls of every strategy, a scan's bookkeeping
        "combine",   # the weighted sum over experts, the cast to the activation dtype
        "shared",    # the shared expert every row takes, and its add
    ),
}
_ALL_PARTS = frozenset(n for names in PARTS.values() for n in names)


def part(name: str):
    """``jax.named_scope(name)`` for a sub-name of :data:`PARTS`."""
    if name not in _ALL_PARTS:
        raise ValueError(f"{name!r} is not a part of {PARTS}")
    return jax.named_scope(name)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a scope of {SCOPES}")
    return jax.named_scope(name)
