"""The Mixtral-style sparse mixture-of-experts decoder as the yardstick knows
it (``harness/models.py`` has the interface): the block the program already
loads as ``ARCH_MIXTRAL``.  No configuration of ``BENCHMARK.json`` names this
module yet; ``benchmarks/tests/test_models_program.py`` holds it against the
program and against ``tests/reference_impl.py`` on the CPU.

Architecture: the dense block's attention (pre-norm, RMSNorm eps 1e-5,
grouped-query attention, causal mask) with rotate-half RoPE; the feed-forward
is ``n_experts`` SwiGLU experts ``down(silu(gate x) * up x)`` of which each
token takes ``n_active_experts``: router logits ``moe_router x``, softmax over
*all* experts, the top k, their probabilities renormalised to sum to 1
(mixtral-tasks.cpp / grok1-tasks.cpp:60-114, as ``models/transformer.py
moe_ffn`` cites them); final RMSNorm and an untied output head.

File layout (``dllama_tpu/io/mfile.py tensor_plan``): in a layer, after ``wo``,
``moe_router`` (n_experts, dim), then for each expert ``up``, ``gate``, ``down``;
then the two norms.  The router is stored in the weights' type (Q40), like
every matrix.
"""

from __future__ import annotations

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_MIXTRAL = 0xABCD02
# toy widths for --rehearse; the number of experts and of experts a token stay
REHEARSE = dict(dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=4,
                vocab_size=2048)


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from a Mixtral-style ``config.json``'s keys;
    ``intermediate_size`` is one expert's width."""
    shp = dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
               n_layers=config["num_hidden_layers"],
               n_heads=config["num_attention_heads"],
               n_kv_heads=config["num_key_value_heads"],
               n_experts=config["num_local_experts"],
               n_active_experts=config["num_experts_per_tok"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=config["rope_theta"])
    if config.get("head_dim", shp["dim"] // shp["n_heads"]) != shp["dim"] // shp["n_heads"]:
        raise SystemExit("head_dim is not hidden_size / num_attention_heads")
    if not 0 < shp["n_active_experts"] <= shp["n_experts"]:
        raise SystemExit("num_experts_per_tok is not in 1..num_local_experts")
    return shp


def header(shape: dict) -> bytes:
    return mformat.pack_header(dict(
        shape, version=1, arch=ARCH_MIXTRAL, hidden_act=1, weights_ftype=Q40,
        rope_theta=int(shape["rope_theta"])))


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, hid, voc = shape["dim"], shape["hidden_dim"], shape["vocab_size"]
    kv = dim // shape["n_heads"] * shape["n_kv_heads"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (dim, dim), Q40), (p + "wk", (kv, dim), Q40),
                  (p + "wv", (kv, dim), Q40), (p + "wo", (dim, dim), Q40),
                  (p + "moe_router", (shape["n_experts"], dim), Q40)]
        for e in range(shape["n_experts"]):
            q = f"{p}experts.{e}."
            names += [(q + "up", (hid, dim), Q40), (q + "gate", (hid, dim), Q40),
                      (q + "down", (dim, hid), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: no kernels, no cache, no batching tricks, weights
    read from the same ``.m`` file the server loads, one tensor at a time; every
    expert runs over every row and a row's unchosen experts get weight 0.
    Departures from the published Mixtral: none in the block (rotate-half RoPE
    pairs lanes (j, j + head/2), as the published model and the program's
    ``ARCH_MIXTRAL`` path do); the router is read from its Q40 bytes, as the
    file stores every matrix, where the published model keeps it unquantised.
    """
    import jax
    import jax.numpy as jnp

    from harness import reference
    from harness.reference import rms

    hd = mformat.read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in (
        "dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads", "n_experts",
        "n_active_experts", "vocab_size", "seq_len", "rope_theta")}))
    dim, hq, hkv = hd["dim"], hd["n_heads"], hd["n_kv_heads"]
    n_exp, k_act = hd["n_experts"], hd["n_active_experts"]
    dh = dim // hq
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def attention(x, wq, wk, wv, wo, g):
        b, t, _ = x.shape
        xb = rms(x, g)
        q = (xb @ wq.T).reshape(b, t, hq, dh)
        k = (xb @ wk.T).reshape(b, t, hkv, dh)
        v = (xb @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        q, k = rope(q, jnp.cos(ang), jnp.sin(ang)), rope(k, jnp.cos(ang), jnp.sin(ang))
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dh)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        att = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        return x + att.reshape(b, t, hq * dh) @ wo.T

    @jax.jit
    def route(x, g, router):
        """The normed rows and each row's weight for every expert (B, T, E):
        its renormalised probability for the k chosen, 0 for the others."""
        xb = rms(x, g)
        probs = jax.nn.softmax(xb @ router.T, -1)
        top, idx = jax.lax.top_k(probs, k_act)
        top = top / jnp.sum(top, -1, keepdims=True)
        return xb, jnp.sum(jax.nn.one_hot(idx, n_exp) * top[..., None], -2)

    @jax.jit
    def expert(acc, xb, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.silu(xb @ gate.T) * (xb @ up.T)) @ down.T)

    @jax.jit
    def head(x_last, g, wcls):
        return rms(x_last, g) @ wcls.T

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attention(x, w.weight(p + "wq"), w.weight(p + "wk"),
                          w.weight(p + "wv"), w.weight(p + "wo"), w.vec(p + "rms_att"))
            xb, shares = route(x, w.vec(p + "rms_ffn"), w.weight(p + "moe_router"))
            for e in range(n_exp):
                q = f"{p}experts.{e}."
                x = expert(x, xb, shares[..., e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
        logits = head(x[:, -1], w.vec("rms_final"), w.weight("wcls"))
        return np.asarray(logits, np.float32)


def _sizes(cfg: dict) -> tuple[int, int, int, int, int]:
    """Values of: a layer's attention matrices, a layer's router, one expert,
    the head; and the layers."""
    dim, hid = cfg["hidden_size"], cfg["intermediate_size"]
    kv = dim // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    return (2 * dim * dim + 2 * dim * kv, cfg["num_local_experts"] * dim,
            3 * dim * hid, cfg["vocab_size"] * dim, cfg["num_hidden_layers"])


def _experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each taking k
    of E: the expectation under uniform, independent routing,
    ``E (1 - (1 - k/E)^rows)``: k at one row, towards E as the rows grow."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) a step of ``rows`` rows streams, per
    chip: attention, router and head once, and the experts its rows hit."""
    att, router, one_expert, head, layers = _sizes(cfg)
    values = layers * (att + router + _experts_read(cfg, rows) * one_expert) + head
    return values * 18 / 32 / chips


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * dh
            * elem_bytes / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights its rows hit once,
    plus the live context of every row (``live_context_tokens`` summed over
    rows)."""
    return (weight_bytes(cfg, chips, rows)
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    attention, the router, its k experts and the head."""
    att, router, one_expert, head, layers = _sizes(cfg)
    mat = layers * (att + router + cfg["num_experts_per_tok"] * one_expert) + head
    scores = 2 * layers * cfg["hidden_size"]  # heads x head size: q.k and p.v
    return 2.0 * (mat * rows + scores * live_context_tokens) / chips
