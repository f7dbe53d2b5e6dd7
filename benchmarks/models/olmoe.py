"""The OLMoE decoder as the yardstick knows it (``harness/models.py`` has the
interface): the block the program loads as ``ARCH_OLMOE`` (0xABCD03).

Architecture (allenai/OLMoE-1B-7B-0125-Instruct, ``model_type: olmoe``), the
equations ``last_logits`` follows::

    n   = RMSNorm_att(x)
    q   = RMSNorm_q(Wq n),  k = RMSNorm_k(Wk n),  v = Wv n
          one RMSNorm over the WHOLE projection (all heads together, a weight
          of hidden_size / of the KV width), before the split into heads and
          before RoPE
    h   = x + Wo Attn(RoPE(heads(q)), RoPE(heads(k)), heads(v))
          rotate-half RoPE (lanes j, j + head/2), causal softmax, 1/sqrt(head)
    m   = RMSNorm_ffn(h)
    p   = softmax over ALL router logits (float32)
    out = h + sum over e in top-k(p) of p_e W_down,e(silu(W_gate,e m) * W_up,e m)
          the k chosen p_e are used AS THEY ARE: no renormalisation
          (``norm_topk_prob: false``)
    logits = W_cls RMSNorm_final(out)

RMSNorm eps 1e-5, no biases, no q/k/v clipping, unscaled RoPE, untied head.

File layout (``dllama_tpu/io/mfile.py tensor_plan`` for this arch id): in a
layer, after ``wo``: ``q_norm`` (dim,) and ``k_norm`` (kv width,) in float32,
then ``moe_router`` (n_experts, dim), then for each expert ``up``, ``gate``,
``down``; then the two block norms.  The router is stored in the weights' type
(Q40), like every matrix.  The header has the format's fourteen keys and no
other: what separates this block from Mixtral's is the arch id alone.
"""

from __future__ import annotations

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_OLMOE = 0xABCD03
# toy widths for --rehearse; the 64 experts and the 8 a token stay
REHEARSE = dict(dim=256, hidden_dim=128, n_layers=2, n_heads=8, n_kv_heads=8,
                vocab_size=2048)
# A position is margin-steady where its routing margin (``routing_margins``)
# exceeds this at every layer: there float32 and the served precision choose
# the same experts, and the logits are held to the dense limits
# (tools/check_routing.py on the chip, tests/test_models_olmoe.py on the CPU).
MARGIN_STEADY = 0.015
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta")


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from an OLMoE ``config.json``'s keys;
    ``intermediate_size`` is one expert's width.  Refuses the settings the
    block above does not have (they would be computed silently wrong)."""
    if config.get("norm_topk_prob", False):
        raise SystemExit("norm_topk_prob is true: this block uses the top-k "
                         "probabilities unnormalised")
    if config.get("clip_qkv") is not None:
        raise SystemExit("clip_qkv is set: this block does not clip q, k, v")
    if config.get("attention_bias", False):
        raise SystemExit("attention_bias is true: this block has no biases")
    if config.get("rope_scaling") is not None:
        raise SystemExit("rope_scaling is set: this block's RoPE is unscaled")
    shp = dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
               n_layers=config["num_hidden_layers"],
               n_heads=config["num_attention_heads"],
               n_kv_heads=config["num_key_value_heads"],
               n_experts=config["num_experts"],
               n_active_experts=config["num_experts_per_tok"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=config["rope_theta"])
    if config.get("head_dim", shp["dim"] // shp["n_heads"]) != shp["dim"] // shp["n_heads"]:
        raise SystemExit("head_dim is not hidden_size / num_attention_heads")
    if not 0 < shp["n_active_experts"] < shp["n_experts"]:
        raise SystemExit("num_experts_per_tok is not in 1..num_experts - 1")
    return shp


def header(shape: dict) -> bytes:
    return mformat.pack_header(dict(
        shape, version=1, arch=ARCH_OLMOE, hidden_act=1, weights_ftype=Q40,
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
                  (p + "q_norm", (dim,), F32), (p + "k_norm", (kv,), F32),
                  (p + "moe_router", (shape["n_experts"], dim), Q40)]
        for e in range(shape["n_experts"]):
            q = f"{p}experts.{e}."
            names += [(q + "up", (hid, dim), Q40), (q + "gate", (hid, dim), Q40),
                      (q + "down", (dim, hid), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def _forward(model_path: str, prompts: list[list[int]], every_position: bool):
    """``(logits, margins)``: float32 logits, ``(n, vocab)`` after each
    prompt's last token or ``(n, T, vocab)`` at every position, and the
    routing margin ``(n, T, layers)``: ``log(p_k / p_(k+1))`` of the sorted
    router probabilities (the gap between the last chosen expert's router
    logit and the first unchosen one's) over the standard deviation of the
    row's router logits.  In that unit it compares with the relative error
    of the activations that feed the router: an error of ``eps`` of their
    size moves a router logit by about ``eps`` standard deviations."""
    import jax
    import jax.numpy as jnp

    from harness import reference
    from harness.reference import rms

    hd = mformat.read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in SHAPE_KEYS}))
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
    def attention(x, wq, wk, wv, wo, g, gq, gk):
        b, t, _ = x.shape
        xb = rms(x, g)
        q = rms(xb @ wq.T, gq).reshape(b, t, hq, dh)
        k = rms(xb @ wk.T, gk).reshape(b, t, hkv, dh)
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
        """The normed rows, each row's weight for every expert (B, T, E): its
        probability, as the softmax gave it, for the k chosen, 0 for the
        others; and the row's margin."""
        xb = rms(x, g)
        scores = xb @ router.T
        probs = jax.nn.softmax(scores, -1)
        top, idx = jax.lax.top_k(probs, k_act + 1)
        margin = ((jnp.log(top[..., k_act - 1]) - jnp.log(top[..., k_act]))
                  / jnp.std(scores, -1))
        shares = jnp.sum(jax.nn.one_hot(idx[..., :k_act], n_exp)
                         * top[..., :k_act, None], -2)
        return xb, shares, margin

    @jax.jit
    def expert(acc, xb, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.silu(xb @ gate.T) * (xb @ up.T)) @ down.T)

    @jax.jit
    def head(x, g, wcls):
        return rms(x, g) @ wcls.T

    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attention(x, w.weight(p + "wq"), w.weight(p + "wk"),
                          w.weight(p + "wv"), w.weight(p + "wo"),
                          w.vec(p + "rms_att"), w.vec(p + "q_norm"),
                          w.vec(p + "k_norm"))
            xb, shares, margin = route(x, w.vec(p + "rms_ffn"),
                                       w.weight(p + "moe_router"))
            margins.append(np.asarray(margin, np.float32))
            for e in range(n_exp):
                q = f"{p}experts.{e}."
                x = expert(x, xb, shares[..., e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
        logits = head(x if every_position else x[:, -1], w.vec("rms_final"),
                      w.weight("wcls"))
        return np.asarray(logits, np.float32), np.stack(margins, -1)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: no kernels, no cache, no batching tricks, weights
    read from the same ``.m`` file the server loads, one tensor at a time; every
    expert runs over every row and a row's unchosen experts get weight 0.
    Departures from the published OLMoE: none in the block (the module's
    docstring has its equations); the router is read from its Q40 bytes, as
    the file stores every matrix, where the published model keeps it
    unquantised.
    """
    return _forward(model_path, prompts, every_position=False)[0]


def routing_margins(model_path: str, prompts: list[list[int]]):
    """``(logits (n, T, vocab), margins (n, T, layers))`` of the same reference
    in one pass over every position (position ``j``'s logits are what
    ``last_logits`` gives for the prompt cut after token ``j``: the mask is
    causal).  For ``tools/check_routing.py`` and the CPU tests: a position
    whose margin is small at some layer is one where rounding may choose
    another expert than float32 does."""
    return _forward(model_path, prompts, every_position=True)


def _sizes(cfg: dict) -> tuple[int, int, int, int, int]:
    """Values of: a layer's attention matrices, a layer's router, one expert,
    the head; and the layers."""
    dim, hid = cfg["hidden_size"], cfg["intermediate_size"]
    kv = dim // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    return (2 * dim * dim + 2 * dim * kv, cfg["num_experts"] * dim,
            3 * dim * hid, cfg["vocab_size"] * dim, cfg["num_hidden_layers"])


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each taking k
    of E: the expectation under uniform, independent routing,
    ``E (1 - (1 - k/E)^rows)``: k at one row, towards E as the rows grow
    (56.4 of 64 at 16 rows of 8)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the expert layers of a step of ``rows`` rows need, per
    chip: every layer's router and the experts its rows hit.  What
    ``serve_moe_roof_pct`` divides by the time under scope ``moe``."""
    _, router, one_expert, _, layers = _sizes(cfg)
    return layers * (router + experts_read(cfg, rows) * one_expert) * 18 / 32 / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) a step of ``rows`` rows streams, per
    chip: attention, router and head once, and the experts its rows hit."""
    att, _, _, head, layers = _sizes(cfg)
    return (layers * att + head) * 18 / 32 / chips + moe_bytes(cfg, chips, rows)


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
