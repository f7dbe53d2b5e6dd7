"""The SmallThinker decoder as the yardstick knows it (``harness/models.py``
has the interface): the block the program loads as ``ARCH_SMALLTHINKER``
(0xABCD05).

Architecture (PowerInfer/SmallThinker-21BA3B-Instruct), the equations
``last_logits`` follows; ``x_l`` is the residual stream entering layer ``l``::

    r_l   = W_router,l x_l           (float32; x_l AS IT ENTERS the layer, before
                                      the attention norm: "router placed before
                                      attention")
    n     = RMSNorm_att,l(x_l)
    q,k,v = W_q n, W_k n, W_v n      n_heads x head_dim query columns (28 x 128 =
                                      3584, not hidden_size), no bias, no q/k norm
    l % period != 0  (window layer): q, k = RoPE(q), RoPE(k), rotate-half lanes
                                      (j, j + head_dim/2), unscaled;
                                      key j visible to query p iff p - W < j <= p
    l % period == 0  (full layer):   no rotation at all (NoPE);
                                      key j visible to query p iff j <= p
    h     = x_l + W_o Attn(q, k, v)  softmax, scale 1/sqrt(head_dim)
    m     = RMSNorm_ffn,l(h)
    S     = the k largest of r_l
    w_e   = exp(r_l,e) / sum_{e' in S} exp(r_l,e')    softmax over the chosen
    x_l+1 = h + sum_{e in S} w_e W_down,e(relu(W_gate,e m) * W_up,e m)
    logits = W_cls RMSNorm_final(x_L)

Every layer is an expert layer; no shared expert, no dense width.  RMSNorm eps
``rms_norm_eps`` (1e-6), untied head.

File layout (``dllama_tpu/io/mfile.py tensor_plan`` for this arch id):
Mixtral's, at a query width of ``n_heads * head_dim``: in a layer ``wq``
(heads x head_dim, dim), ``wk``, ``wv``, ``wo`` (dim, heads x head_dim),
``moe_router`` (n_experts, dim), each expert's ``up``, ``gate``, ``down``, then
the two block norms.  The header has the format's fourteen keys and four more
(31 the norm's epsilon as f32 bits, 32 ``head_dim``, 33 ``window``, 34
``window_period``); ``mformat.pack_header`` / ``read_header`` stop at key 13,
so this module packs and reads its own.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_SMALLTHINKER = 0xABCD05
ACT_RELU = 2
# toy widths for --rehearse; the 64 experts, the 6 a token, the period of four
# and a head size that is not dim / n_heads stay; the window is shorter than
# the rehearsal's prompts
REHEARSE = dict(dim=256, hidden_dim=64, n_layers=4, n_heads=14, n_kv_heads=2,
                head_dim=32, vocab_size=2048, window=1024, seq_len=16384)
# A position is margin-steady where its routing margin (``routing_margins``)
# exceeds this at every layer.  OLMoE's unit, not its 0.015: at 52 layers the
# least of a position's margins is small almost everywhere (1 of 392 positions
# is over 0.015), and read against the chip's sweep (tools/check_routing.py,
# PERF.md section 6, PR 38) the 44 positions over 0.005 are within 0.101 max /
# 0.023 rms sigma, 347 of the 348 under it within 0.149 / 0.034 (the dense
# limits are 0.2 / 0.04), and the one far position (14.5 sigma: the seeded
# model's other state) has a margin of 0.0022 at layer 49.
MARGIN_STEADY = 0.005
# (key, name, is_float) of the header's pairs past the format's fourteen
EXT_KEYS = ((31, "norm_eps", True), (32, "head_dim", False),
            (33, "window", False), (34, "window_period", False))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta") + tuple(name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time (a 5000-token pass is
# 28 heads x 5000 x 5000 float32 scores a layer otherwise)
QUERY_BLOCK = 1024
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD05 (its format
    module names it).  A text probe, not an import, as ``deepseek_v2.py``'s:
    the yardstick imports nothing of the program.  These files are also laid
    over checkouts older than the architecture (a new cell is tried on the
    parent commit first), which fail here, at once, before a 13.5 GB file is
    written for a loader that would refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd05" in f.read().lower()
    except OSError:
        return False


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from a SmallThinker ``config.json``'s keys.
    Refuses the settings the block above does not have (they would be computed
    silently wrong), and a checkout whose program lacks the arch id."""
    def no(why):
        raise SystemExit(f"smallthinker: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD05 (unknown "
           "architecture): it cannot load a SmallThinker file")
    if not config.get("moe_primary_router_apply_softmax", False):
        no("moe_primary_router_apply_softmax is false: this block softmaxes "
           "the chosen logits (a sigmoid router is not computed)")
    if not config.get("norm_topk_prob", False):
        no("norm_topk_prob is false: this block's chosen weights sum to 1")
    if config.get("moe_num_secondary_experts") or config.get("moe_secondary_ffn_hidden_size"):
        no("secondary experts are set: this block has primary experts only")
    if config.get("rope_scaling") is not None:
        no("rope_scaling is set: this block's RoPE is unscaled")
    if config.get("tie_word_embeddings", False):
        no("the head is tied")
    layers = config["num_hidden_layers"]
    window_layout = list(config["sliding_window_layout"])
    if list(config["rope_layout"]) != window_layout or len(window_layout) != layers:
        no("rope_layout is not sliding_window_layout: this block rotates "
           "exactly its window layers")
    period = window_layout[1:].index(0) + 1 if 0 in window_layout[1:] else 0
    if period < 2 or layers % period or window_layout != (
            [0] + [1] * (period - 1)) * (layers // period):
        no("the layers are not whole periods of one full layer and then "
           "window layers")
    shp = dict(dim=config["hidden_size"], hidden_dim=config["moe_ffn_hidden_size"],
               n_layers=layers, n_heads=config["num_attention_heads"],
               n_kv_heads=config["num_key_value_heads"],
               n_experts=config["moe_num_primary_experts"],
               n_active_experts=config["moe_num_active_primary_experts"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=config["rope_theta"],
               norm_eps=float(config["rms_norm_eps"]),
               head_dim=config["head_dim"],
               window=config["sliding_window_size"], window_period=period)
    if not 0 < shp["n_active_experts"] < shp["n_experts"]:
        no("moe_num_active_primary_experts is not in 1..experts - 1")
    if shp["n_heads"] % shp["n_kv_heads"]:
        no("num_attention_heads is not a multiple of num_key_value_heads")
    return shp


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_SMALLTHINKER, hidden_act=ACT_RELU,
                weights_ftype=Q40, rope_theta=int(shape["rope_theta"]))
    pairs = [(k, int(vals[name])) for k, name in enumerate(mformat.HEADER_KEYS)]
    pairs += [(k, _f32_bits(shape[name]) if is_f else int(shape[name]))
              for k, name, is_f in EXT_KEYS]
    data = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    return struct.pack("<ii", mformat.MAGIC, 8 + len(data)) + data


def read_header(path: str) -> dict:
    """Every key of a file this module wrote, the float decoded."""
    with open(path, "rb") as f:
        magic, size = struct.unpack("<ii", f.read(8))
        if magic != mformat.MAGIC:
            raise ValueError(f"{path}: not a v2 .m file")
        kv = struct.unpack(f"<{(size - 8) // 4}i", f.read(size - 8))
    ext = {k: (name, is_f) for k, name, is_f in EXT_KEYS}
    out = {}
    for k, v in zip(kv[::2], kv[1::2]):
        if k < len(mformat.HEADER_KEYS):
            out[mformat.HEADER_KEYS[k]] = v
        else:
            name, is_f = ext[k]
            out[name] = struct.unpack("<f", struct.pack("<i", v))[0] if is_f else v
    return out


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, hid, voc = shape["dim"], shape["hidden_dim"], shape["vocab_size"]
    qw = shape["n_heads"] * shape["head_dim"]
    kv = shape["n_kv_heads"] * shape["head_dim"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (qw, dim), Q40), (p + "wk", (kv, dim), Q40),
                  (p + "wv", (kv, dim), Q40), (p + "wo", (dim, qw), Q40),
                  (p + "moe_router", (shape["n_experts"], dim), Q40)]
        for e in range(shape["n_experts"]):
            q = f"{p}experts.{e}."
            names += [(q + "up", (hid, dim), Q40), (q + "gate", (hid, dim), Q40),
                      (q + "down", (dim, hid), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def _forward(model_path: str, prompts: list[list[int]], positions):
    """``(logits, margins)``: float32 logits ``(n, len(positions), vocab)`` at
    the token positions ``positions`` (``None``: every position) and the
    routing margin ``(n, T, layers)``: the gap between the last chosen
    expert's router logit and the first unchosen one's, over the standard
    deviation of the row's router logits (``models/olmoe.py`` has why that
    unit)."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in SHAPE_KEYS}))
    dim, hq, hkv, dh = hd["dim"], hd["n_heads"], hd["n_kv_heads"], hd["head_dim"]
    n_exp, k_act, eps = hd["n_experts"], hd["n_active_experts"], hd["norm_eps"]
    window, period = hd["window"], hd["window_period"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def rms(x, g):
        return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def route(x, router):
        """Each row's weight for every expert (B, T, E) from x_l itself: the
        softmax over the k chosen logits, 0 for the others; and its margin."""
        scores = x @ router.T
        top, idx = jax.lax.top_k(scores, k_act + 1)
        margin = (top[..., k_act - 1] - top[..., k_act]) / jnp.std(scores, -1)
        share = jax.nn.softmax(top[..., :k_act], -1)
        return jnp.sum(jax.nn.one_hot(idx[..., :k_act], n_exp)
                       * share[..., None], -2), margin

    def make_attention(windowed: bool):
        @jax.jit
        def attention(x, wq, wk, wv, wo, g):
            b, t, _ = x.shape
            xb = rms(x, g)
            q = (xb @ wq.T).reshape(b, t, hq, dh)
            k = (xb @ wk.T).reshape(b, t, hkv, dh)
            v = (xb @ wv.T).reshape(b, t, hkv, dh)
            if windowed:
                freqs = 1.0 / (float(hd["rope_theta"]) ** (
                    jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
                ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
                q, k = rope(q, jnp.cos(ang), jnp.sin(ang)), rope(k, jnp.cos(ang), jnp.sin(ang))
            k = jnp.repeat(k, hq // hkv, axis=2)
            v = jnp.repeat(v, hq // hkv, axis=2)
            outs = []
            for lo in range(0, t, QUERY_BLOCK):  # query rows in blocks
                hi = min(lo + QUERY_BLOCK, t)
                first = max(lo - window + 1, 0) if windowed else 0
                s = jnp.einsum("bthd,bshd->bhts", q[:, lo:hi], k[:, first:hi]) / np.sqrt(dh)
                qi = jnp.arange(lo, hi)[:, None]
                kj = jnp.arange(first, hi)[None, :]
                mask = kj <= qi
                if windowed:
                    mask = mask & (kj > qi - window)
                s = jnp.where(mask, s, -jnp.inf)
                outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                                       v[:, first:hi]))
            att = jnp.concatenate(outs, 1)
            return x + att.reshape(b, t, hq * dh) @ wo.T
        return attention

    attend = {False: make_attention(False), True: make_attention(True)}

    @jax.jit
    def ffn_norm(x, g):
        return rms(x, g)

    @jax.jit
    def expert(acc, m, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.relu(m @ gate.T) * (m @ up.T)) @ down.T)

    @jax.jit
    def head(x, g, wcls):
        return rms(x, g) @ wcls.T

    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            shares, margin = route(x, w.weight(p + "moe_router"))
            margins.append(np.asarray(margin, np.float32))
            x = attend[i % period != 0](
                x, w.weight(p + "wq"), w.weight(p + "wk"), w.weight(p + "wv"),
                w.weight(p + "wo"), w.vec(p + "rms_att"))
            m = ffn_norm(x, w.vec(p + "rms_ffn"))
            for e in range(n_exp):
                q = f"{p}experts.{e}."
                x = expert(x, m, shares[..., e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
        if positions is not None:
            x = x[:, np.asarray(positions)]
        logits = head(x, w.vec("rms_final"), w.weight("wcls"))
        return np.asarray(logits, np.float32), np.stack(margins, -1)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: no kernels, no cache, no ring, weights read from the
    same ``.m`` file the server loads, one tensor at a time; every expert runs
    over every row and a row's unchosen experts get weight 0; the window is a
    mask over the whole sequence (query rows in blocks of ``QUERY_BLOCK``,
    which changes what is held at once and not what is computed).
    Departures from the published SmallThinker: the router is read from its
    Q40 bytes, as the file stores every matrix, where the published model
    keeps it unquantised; the four assumptions of the configuration file
    (``assumed``: the router's input, the window's convention, no biases,
    rotate-half lanes)."""
    return _forward(model_path, prompts, [len(prompts[0]) - 1])[0][:, 0]


def logits_at(model_path: str, prompts: list[list[int]], positions) -> np.ndarray:
    """``(n, len(positions), vocab)`` of the same reference in one pass: the
    logits after the tokens at ``positions`` (``tools/check_window.py``: the
    mask is causal, so position ``j``'s are ``last_logits`` of the prompt cut
    after token ``j``)."""
    return _forward(model_path, prompts, list(positions))[0]


def routing_margins(model_path: str, prompts: list[list[int]]):
    """``(logits (n, T, vocab), margins (n, T, layers))`` of the same reference
    in one pass over every position, for ``tools/check_routing.py`` and the
    CPU tests."""
    return _forward(model_path, prompts, None)


def _sizes(cfg: dict) -> tuple[int, int, int, int, int]:
    """Values of: a layer's attention matrices, a layer's router, one expert,
    the head; and the layers."""
    dim, hid = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return (2 * dim * qw + 2 * dim * kv, cfg["moe_num_primary_experts"] * dim,
            3 * dim * hid, cfg["vocab_size"] * dim, cfg["num_hidden_layers"])


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each taking k
    of E, under uniform, independent routing: ``E (1 - (1 - k/E)^rows)``."""
    e, k = cfg["moe_num_primary_experts"], cfg["moe_num_active_primary_experts"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the expert layers of a step of ``rows`` rows need, per
    chip: every layer's router and the experts its rows hit (six at one row:
    what ``moe_select_roof_pct`` divides by the time under scope ``moe``)."""
    _, router, one_expert, _, layers = _sizes(cfg)
    return layers * (router + experts_read(cfg, rows) * one_expert) * 18 / 32 / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) a step of ``rows`` rows streams, per
    chip: attention and head once, router and the experts its rows hit."""
    att, _, _, head, layers = _sizes(cfg)
    return (layers * att + head) * 18 / 32 / chips + moe_bytes(cfg, chips, rows)


def layer_kinds(cfg: dict) -> tuple[int, int]:
    """(full layers, window layers) of the published layout."""
    n_win = sum(cfg["sliding_window_layout"])
    return cfg["num_hidden_layers"] - n_win, n_win


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip
    (a position inside the window: every layer holds it)."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * elem_bytes / chips)


def kv_read_bytes(cfg: dict, context: float, chips: int = 1,
                  elem_bytes: int = 2) -> float:
    """Bytes of live keys and values one decoded token at ``context`` positions
    must read: all of them in a full layer, the last ``sliding_window_size``
    in a window layer.  What ``attn_kv_roof_pct`` divides by the time under
    scope ``attn``."""
    full, win = layer_kinds(cfg)
    positions = full * context + win * min(context, cfg["sliding_window_size"])
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * elem_bytes
            * positions / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights its rows hit once,
    plus the live context each row may see (``live_context_tokens`` summed
    over rows; a row's share of it is cut to the window in the window
    layers)."""
    ctx = live_context_tokens / max(rows, 1)
    return (weight_bytes(cfg, chips, rows)
            + kv_read_bytes(cfg, ctx, chips) * max(rows, 1))


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    attention, the router, its k experts and the head, and scores the keys it
    may see."""
    att, router, one_expert, head, layers = _sizes(cfg)
    mat = layers * (att + router
                    + cfg["moe_num_active_primary_experts"] * one_expert) + head
    full, win = layer_kinds(cfg)
    ctx = live_context_tokens / max(rows, 1)
    seen = (full * ctx + win * min(ctx, cfg["sliding_window_size"])) * max(rows, 1)
    scores = 2 * cfg["num_attention_heads"] * cfg["head_dim"]  # q.k and p.v
    return 2.0 * (mat * rows + scores * seen) / chips
