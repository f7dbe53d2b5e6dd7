"""The LFM2 mixture-of-experts decoder (``model_type`` ``lfm2_moe``) as the
yardstick knows it (``harness/models.py`` has the interface): the block the
program loads as ``ARCH_LFM2_MOE`` (0xABCD07).

Architecture (LiquidAI/LFM2-24B-A2B ``config.json``), the equations
``last_logits`` follows; ``x`` is the residual stream entering layer ``l``,
hidden ``D``, ``K = conv_L_cache`` taps::

    u     = RMSNorm_op,l(x)                      operator_norm
    layer_types[l] == "conv":
      [B, C, X] = W_in u                         D -> 3 D, split in that order
      z_t   = B_t * X_t
      y_t[c] = C_t[c] * sum_{j < K} w[c, j] * z_{t - (K - 1) + j}[c]
                                                 depthwise, causal; z before the
                                                 sequence's start is 0; no bias
      h     = x + W_out y
    layer_types[l] == "full_attention":
      q,k,v = W_q u, W_k u, W_v u                heads of hidden_size / num_attention_heads
      q, k  = RMSNorm_q,l(q), RMSNorm_k,l(k)     over each head's values, one weight
                                                 vector of a head's size each a layer
      q, k  = RoPE(q), RoPE(k)                   rotate-half lanes (j, j + head/2),
                                                 theta rope_theta, EVERY attention layer
      h     = x + W_o Attn(q, k, v)              causal softmax, scale 1/sqrt(head)
    n2    = RMSNorm_ffn,l(h)                     ffn_norm
    l < num_dense_layers:  x' = h + W_2(silu(W_1 n2) * W_3 n2)     width intermediate_size
    else: s   = sigmoid(W_router,l n2)           num_experts scores
          S   = the k largest of s + b_l         b: the expert bias, for the choice only
          w_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-6)
          x'  = h + sum_{e in S} w_e Exp_e(n2)   SwiGLU of moe_intermediate_size;
                                                 no shared expert
    logits = W_cls RMSNorm_final(x_L)            embedding_norm, then the head

The convolution is computed here as a sum of ``K`` shifted copies of ``z`` over
the whole sequence: no state, no ring, no cache.  RMSNorm eps ``norm_eps``.

Departures from the published description, all of them:

* the configuration's ``assumed`` conventions (the split order ``B, C, X`` and
  the gate placement; the per-head q/k norm before RoPE with rotate-half lanes;
  the ``+ 1e-6`` and a choice bias that is present, non-zero and used for the
  choice only; the head);
* the head: the published model ties it to the embedding; the seeded file
  draws ``wcls`` on its own, as every configuration's does (same shape, same
  bytes read a token); the converter writes ``wcls`` from the embedding's rows;
* the router is read from its Q40 bytes, as the file stores every matrix,
  where the published model keeps it unquantised;
* depth: the first ``num_hidden_layers`` layers of the published 40 (the
  configuration's ``reduced`` and ``deployment``).

File layout (``dllama_tpu/io/mfile.py tensor_plan`` for this arch id): in a
conv layer ``conv_in`` (3 dim, dim), ``conv_taps`` (dim x K values, f32,
channel by channel: value ``c K + j`` is ``w[c, j]``), ``conv_out`` (dim, dim);
in an attention layer ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``
(one head's size; f32); a dense layer's ``w1``, ``w2``, ``w3``; an expert
layer's ``moe_router`` (E, dim), ``moe_router_bias`` (E; f32) and the experts'
``up``, ``gate``, ``down``; then the two block norms.  The header has the
format's fourteen keys and eight more (``EXT_KEYS``).
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_LFM2_MOE = 0xABCD07
ACT_SILU = 1
# toy widths for --rehearse; the 64 experts, the 4 a token, the period of four
# with its attention layer third, the two dense layers and the 3 taps stay
REHEARSE = dict(dim=256, hidden_dim=512, moe_hidden_dim=64, n_layers=8,
                n_heads=8, n_kv_heads=2, head_dim=32, vocab_size=2048,
                seq_len=32768)
# A position is margin-steady where its routing margin (``routing_margins``)
# exceeds this at every expert layer.  Twice SmallThinker's figure: a conv
# layer multiplies three products of rounded activations, and at toy widths the
# packed path flips an expert at margins up to 0.008 (``tests/
# test_models_lfm2_moe.py``).  ``tools/check_routing.py``'s sweep on the chip at
# the published widths (PR 47): 94 of 392 positions steady here, 138 at 0.005,
# the worst of either set 0.051 max / 0.011 rms sigma
MARGIN_STEADY = 0.01
ROUTER_NORM_EPS = 1e-6
# (key, name, is_float) of the header's pairs past the format's fourteen
EXT_KEYS = ((19, "moe_hidden_dim", False), (23, "n_dense_layers", False),
            (24, "routed_scale", True), (31, "norm_eps", True),
            (32, "head_dim", False), (34, "window_period", False),
            (37, "window_full_at", False), (38, "conv_taps", False))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta") + tuple(name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time
QUERY_BLOCK = 1024
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD07 (its format
    module names it).  A text probe, not an import: the yardstick imports
    nothing of the program.  These files are also laid over checkouts older
    than the architecture (a new cell is tried on the parent commit first),
    which fail here, at once, before an 11 GB file is written for a loader
    that would refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd07" in f.read().lower()
    except OSError:
        return False


def _layout(config: dict) -> tuple[int, int]:
    """``(period, attention_at)`` of the served layers' ``layer_types``: whole
    periods of conv layers with one attention layer, at the same place in
    each."""
    layers = config["num_hidden_layers"]
    kinds = config["layer_types"][:layers]
    if len(kinds) != layers or set(kinds) - {"conv", "full_attention"} \
            or "full_attention" not in kinds:
        raise SystemExit("lfm2_moe: layer_types does not cover the layers with "
                         "conv and full_attention layers alone")
    att = [t == "full_attention" for t in kinds]
    at = att.index(True)
    period = att[at + 1:].index(True) + 1 if True in att[at + 1:] else 0
    if period < 2 or layers % period or att != [
            j == at for j in range(period)] * (layers // period):
        raise SystemExit("lfm2_moe: the layers are not whole periods of conv "
                         "layers with one attention layer")
    return period, at


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's keys, the published
    ``config.json``'s.  Refuses the settings the block above does not have
    (they would be computed silently wrong), and a checkout whose program
    lacks the arch id."""
    def no(why):
        raise SystemExit(f"lfm2_moe: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD07 (unknown "
           "architecture): it cannot load an LFM2 file")
    if config.get("conv_bias", False):
        no("conv_bias is true: this block's convolution and projections have none")
    if not config.get("norm_topk_prob", False):
        no("norm_topk_prob is false: this block's chosen weights are normalised")
    if not config.get("use_expert_bias", False):
        no("use_expert_bias is false: this block's router has a choice bias")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        no("rope_type is not default: this block's RoPE is unscaled")
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    period, at = _layout(config)
    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads:
        no("hidden_size is not a multiple of num_attention_heads")
    shp = dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
               n_layers=layers, n_heads=heads,
               n_kv_heads=config["num_key_value_heads"],
               n_experts=config["num_experts"],
               n_active_experts=config["num_experts_per_tok"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=rope["rope_theta"],
               moe_hidden_dim=config["moe_intermediate_size"],
               n_dense_layers=dense,
               routed_scale=float(config["routed_scaling_factor"]),
               norm_eps=float(config["norm_eps"]),
               head_dim=config["hidden_size"] // heads, window_period=period,
               window_full_at=at, conv_taps=config["conv_L_cache"])
    if not 0 < shp["n_active_experts"] <= shp["n_experts"]:
        no("num_experts_per_tok is not in 1..num_experts")
    if shp["n_heads"] % shp["n_kv_heads"]:
        no("num_attention_heads is not a multiple of num_key_value_heads")
    if not 0 <= dense < layers:
        no("num_dense_layers leaves no expert layer")
    if shp["conv_taps"] < 2:
        no("conv_L_cache is under 2: a convolution of one tap keeps no state")
    return shp


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_LFM2_MOE, hidden_act=ACT_SILU,
                weights_ftype=Q40, rope_theta=int(shape["rope_theta"]))
    pairs = [(k, int(vals[name])) for k, name in enumerate(mformat.HEADER_KEYS)]
    pairs += [(k, _f32_bits(shape[name]) if is_f else int(shape[name]))
              for k, name, is_f in EXT_KEYS]
    data = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    return struct.pack("<ii", mformat.MAGIC, 8 + len(data)) + data


def read_header(path: str) -> dict:
    """Every key of a file this module wrote, the floats decoded."""
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


def _is_attention(shape: dict, i: int) -> bool:
    return i % shape["window_period"] == shape["window_full_at"]


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, dh = shape["dim"], shape["vocab_size"], shape["head_dim"]
    qw, kv = shape["n_heads"] * dh, shape["n_kv_heads"] * dh
    hid, f = shape["hidden_dim"], shape["moe_hidden_dim"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        if _is_attention(shape, i):
            names += [(p + "wq", (qw, dim), Q40), (p + "wk", (kv, dim), Q40),
                      (p + "wv", (kv, dim), Q40), (p + "wo", (dim, qw), Q40),
                      (p + "q_norm", (dh,), F32), (p + "k_norm", (dh,), F32)]
        else:
            names += [(p + "conv_in", (3 * dim, dim), Q40),
                      (p + "conv_taps", (dim * shape["conv_taps"],), F32),
                      (p + "conv_out", (dim, dim), Q40)]
        if i < shape["n_dense_layers"]:
            names += [(p + "w1", (hid, dim), Q40), (p + "w2", (dim, hid), Q40),
                      (p + "w3", (hid, dim), Q40)]
        else:
            names += [(p + "moe_router", (shape["n_experts"], dim), Q40),
                      (p + "moe_router_bias", (shape["n_experts"],), F32)]
            for e in range(shape["n_experts"]):
                q = f"{p}experts.{e}."
                names += [(q + "up", (f, dim), Q40), (q + "gate", (f, dim), Q40),
                          (q + "down", (dim, f), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def _forward(model_path: str, prompts: list[list[int]], positions,
             act_dtype=None):
    """``(logits, margins)``: float32 logits ``(n, len(positions), vocab)`` at
    the token positions ``positions`` (``None``: every position) and the
    routing margin ``(n, T, expert layers)``: the gap between the last chosen
    expert's biased score and the first unchosen one's, over the standard
    deviation of the row's biased scores.  ``act_dtype``: round the residual
    stream and every sub-block's output to this type's mantissa (``tools/check_state.py``
    reads what the nearest precision below the configuration's gives)."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    shp = {k: hd[k] for k in SHAPE_KEYS}
    w = reference.Tensors(model_path, plan(shp))
    dim, hq, hkv, dh = hd["dim"], hd["n_heads"], hd["n_kv_heads"], hd["head_dim"]
    n_exp, k_act, eps = hd["n_experts"], hd["n_active_experts"], hd["norm_eps"]
    taps, scale = hd["conv_taps"], hd["routed_scale"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def cast(x):
        """``x`` rounded to ``act_dtype``'s mantissa (at float32's exponent
        range, so nothing overflows).  ``reduce_precision`` and not a pair of
        converts: the TPU's compiler drops such a pair as excess precision
        (read on the chip: a float8 "reading" equal to float32's, PR 47)."""
        if act_dtype is None:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=jnp.finfo(act_dtype).nmant)

    def rms(x, g):
        return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def attention(x, wq, wk, wv, wo, g, gq, gk):
        b, t, _ = x.shape
        u = rms(x, g)
        q = rms((u @ wq.T).reshape(b, t, hq, dh), gq)   # each head's own
        k = rms((u @ wk.T).reshape(b, t, hkv, dh), gk)
        v = (u @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        q, k = rope(q, jnp.cos(ang), jnp.sin(ang)), rope(k, jnp.cos(ang), jnp.sin(ang))
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        outs = []
        for lo in range(0, t, QUERY_BLOCK):  # query rows in blocks
            hi = min(lo + QUERY_BLOCK, t)
            s = jnp.einsum("bthd,bshd->bhts", q[:, lo:hi], k[:, :hi]) / np.sqrt(dh)
            mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(mask, s, -jnp.inf)
            outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                                   v[:, :hi]))
        att = jnp.concatenate(outs, 1)
        return x + cast(att.reshape(b, t, hq * dh) @ wo.T)

    @jax.jit
    def short_conv(x, w_in, w_taps, w_out, g):
        b, t, _ = x.shape
        u = rms(x, g)
        gb, gc, xs = jnp.split(cast(u @ w_in.T), 3, axis=-1)
        z = cast(gb * xs)
        wt = w_taps.reshape(dim, taps)
        y = jnp.zeros_like(z)
        for j in range(taps):  # z shifted by taps - 1 - j positions, zeros in front
            back = taps - 1 - j
            shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
            y = y + shifted * wt[:, j]
        return x + cast(cast(gc * y) @ w_out.T)

    @jax.jit
    def ffn_norm(x, g):
        return cast(rms(x, g))

    @jax.jit
    def route(m, router, bias):
        """Each row's weight for every expert (B, T, E): scale * s_e over the
        sum of the k chosen scores + 1e-6, 0 for the others; and its margin."""
        s = jax.nn.sigmoid(m @ router.T)
        biased = s + bias
        top, idx = jax.lax.top_k(biased, k_act + 1)
        margin = (top[..., k_act - 1] - top[..., k_act]) / jnp.std(biased, -1)
        chosen = jnp.sum(jax.nn.one_hot(idx[..., :k_act], n_exp), -2)
        picked = s * chosen
        return scale * picked / (jnp.sum(picked, -1, keepdims=True)
                                 + ROUTER_NORM_EPS), margin

    @jax.jit
    def swiglu(acc, m, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.silu(m @ gate.T) * (m @ up.T)) @ down.T)

    @jax.jit
    def head(x, g, wcls):
        return rms(x, g) @ wcls.T

    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w.rows("token_embedding", toks.reshape(-1)).reshape(
            toks.shape[0], t_len, dim))
        x = cast(x)
        ones = jnp.ones(toks.shape, jnp.float32)
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            if _is_attention(shp, i):
                x = attention(x, w.weight(p + "wq"), w.weight(p + "wk"),
                              w.weight(p + "wv"), w.weight(p + "wo"),
                              w.vec(p + "rms_att"), w.vec(p + "q_norm"),
                              w.vec(p + "k_norm"))
            else:
                x = short_conv(x, w.weight(p + "conv_in"), w.vec(p + "conv_taps"),
                               w.weight(p + "conv_out"), w.vec(p + "rms_att"))
            x = cast(x)
            m = ffn_norm(x, w.vec(p + "rms_ffn"))
            if i < hd["n_dense_layers"]:
                x = cast(swiglu(x, m, ones, w.weight(p + "w3"), w.weight(p + "w1"),
                                w.weight(p + "w2")))
                continue
            shares, margin = route(m, w.weight(p + "moe_router"),
                                   w.vec(p + "moe_router_bias"))
            margins.append(np.asarray(margin, np.float32))
            for e in range(n_exp):  # every expert over every row, weight 0 if unchosen
                q = f"{p}experts.{e}."
                x = swiglu(x, m, shares[..., e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
            x = cast(x)
        if positions is not None:
            x = x[:, np.asarray(positions)]
        logits = head(x, w.vec("rms_final"), w.weight("wcls"))
        return np.asarray(logits, np.float32), np.stack(margins, -1)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: float32 at matmul precision ``highest``, no kernels,
    no cache, no ring, no state, no pages, weights read from the same ``.m``
    file the server loads, one tensor at a time; every expert runs over every
    row and a row's unchosen experts get weight 0; the convolution is a sum of
    shifted copies of ``z`` over the whole sequence (attention's query rows in
    blocks of ``QUERY_BLOCK``, which changes what is held at once and not what
    is computed)."""
    return _forward(model_path, prompts, [len(prompts[0]) - 1])[0][:, 0]


def logits_at(model_path: str, prompts: list[list[int]], positions,
              act_dtype=None) -> np.ndarray:
    """``(n, len(positions), vocab)`` of the same reference in one pass: the
    logits after the tokens at ``positions`` (``tools/check_state.py``: the
    model is causal, so position ``j``'s are ``last_logits`` of the prompt cut
    after token ``j``)."""
    return _forward(model_path, prompts, list(positions), act_dtype)[0]


def routing_margins(model_path: str, prompts: list[list[int]]):
    """``(logits (n, T, vocab), margins (n, T, expert layers))`` of the same
    reference in one pass over every position, for ``tools/check_routing.py``
    and the CPU tests."""
    return _forward(model_path, prompts, None)


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def layer_kinds(cfg: dict) -> tuple[int, int]:
    """(attention layers, conv layers) among the served layers."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(t == "full_attention" for t in kinds)
    return full, len(kinds) - full


def _sizes(cfg: dict) -> dict:
    """Values of an attention operator's matrices, a conv operator's, a layer's
    router, one expert, a dense FFN, the head; and the counts."""
    dim, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dh = dim // cfg["num_attention_heads"]
    qw, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    dense = cfg["num_dense_layers"]
    full, conv = layer_kinds(cfg)
    return dict(att=2 * dim * qw + 2 * dim * kv, conv=4 * dim * dim,
                router=cfg["num_experts"] * dim, expert=3 * dim * f,
                dense=3 * dim * cfg["intermediate_size"],
                head=cfg["vocab_size"] * dim, n_att=full, n_conv=conv,
                n_dense=dense, n_moe=cfg["num_hidden_layers"] - dense,
                experts=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                dim=dim, head_dim=dh, taps=cfg["conv_L_cache"])


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each row
    taking k of E under uniform, independent routing: ``E (1 - (1 - k/E)^rows)``:
    41.2 of 64 at 16 rows."""
    z = _sizes(cfg)
    return z["experts"] * (1.0 - (1.0 - z["k"] / z["experts"]) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the expert layers of a step of ``rows`` rows need, per
    chip: every expert layer's router and the experts its rows hit (all under
    scope ``moe``; what ``serve_moe_roof_pct`` and ``moe_select_roof_pct``
    divide by that scope's time)."""
    z = _sizes(cfg)
    return z["n_moe"] * (z["router"] + experts_read(cfg, rows) * z["expert"]
                         ) * 18 / 32 / chips


def conv_bytes(cfg: dict, chips: int = 1, rows: float = 1,
               elem_bytes: int = 2) -> float:
    """Bytes the conv layers of a step of ``rows`` rows need, per chip: every
    conv layer's ``W_in`` and ``W_out`` as packed Q40 once, its taps (f32) once,
    and for each row the ``K - 1`` state rows read and the one written (what
    ``conv_roof_pct`` and ``serve_conv_roof_pct`` divide by the time under the
    part ``conv``)."""
    z = _sizes(cfg)
    state = z["taps"] * z["dim"] * elem_bytes * rows   # K - 1 read, 1 written
    return z["n_conv"] * (z["conv"] * 18 / 32 + 4 * z["dim"] * z["taps"]
                          + state) / chips


def conv_flops(cfg: dict, rows: float = 1, chips: int = 1) -> float:
    """Multiply-adds x 2 of the conv layers for ``rows`` rows: the two
    projections, both gates and the taps."""
    z = _sizes(cfg)
    return 2.0 * z["n_conv"] * rows * (z["conv"] + z["dim"] * (2 + z["taps"])) / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) a step of ``rows`` rows streams, per
    chip: the attention and conv operators, the dense layers and the head once,
    and what the expert layers need."""
    z = _sizes(cfg)
    return ((z["n_att"] * z["att"] + z["n_conv"] * z["conv"]
             + z["n_dense"] * z["dense"] + z["head"]) * 18 / 32 / chips
            + moe_bytes(cfg, chips, rows))


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one more cached position adds, per chip: the attention
    layers' (a conv layer's state is there whatever the context's depth)."""
    z = _sizes(cfg)
    return (2 * z["n_att"] * cfg["num_key_value_heads"] * z["head_dim"]
            * elem_bytes / chips)


def kv_read_bytes(cfg: dict, context: float, chips: int = 1,
                  elem_bytes: int = 2, rows: float = 1) -> float:
    """Bytes of live keys and values ``rows`` decoded tokens, each at
    ``context`` positions, must read: all of them in every attention layer.
    What ``serve_attn_kv_roof_pct`` divides by the time under scope ``attn``."""
    return kv_bytes_per_token(cfg, chips, elem_bytes) * context * rows


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights its rows hit once,
    the live context each row may see (``live_context_tokens`` summed over
    rows) and the conv layers' state rows."""
    z = _sizes(cfg)
    state = z["n_conv"] * z["taps"] * z["dim"] * 2 * max(rows, 1) / chips
    return (weight_bytes(cfg, chips, rows) + state
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    the operators, the dense layers, the router, its k experts and the head,
    and scores the keys it may see in the attention layers."""
    z = _sizes(cfg)
    mat = (z["n_att"] * z["att"] + z["n_conv"] * z["conv"]
           + z["n_dense"] * z["dense"] + z["head"]
           + z["n_moe"] * (z["router"] + z["k"] * z["expert"]))
    scores = 2 * cfg["num_attention_heads"] * z["head_dim"]  # q.k and p.v
    return 2.0 * (mat * rows + scores * z["n_att"] * live_context_tokens) / chips
