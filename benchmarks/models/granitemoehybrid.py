"""Granite-4.0-H (``"model": "granitemoehybrid"``): Mamba-2 mixer layers and
position-free grouped-query attention layers in periods (9 : 1 of 10 in
``ibm-granite/granite-4.0-h-small``), a mixture of experts with a softmax over
the CHOSEN logits beside a shared gated MLP behind every one of them, four
scalar multipliers and a tied head.  The ``.m`` layout, the plain reference and
the cost arithmetic.

The layer as the reference computes it (``N`` RMSNorm, eps ``rms_norm_eps``; no
bias but the convolution's; no rotation anywhere; ``r`` =
``residual_multiplier``; ``h`` a mixer head of ``P`` values, ONE group of ``n``
state rows)::

    x_0   = E[token] * embedding_multiplier
    u     = N(x; g_in)
    mixer layer (layer_types[l] == "mamba"):
      [z | xBC | dt] = W_in u                              # xBC = x | B | C
      xBC_t = silu(sum_{j<4} w[:, j] xBC_{t-3+j} + b)      # depthwise, causal
      dt = softplus(dt + dt_bias),  A = -exp(A_log)
      y_t^h = sum_{j<=t} exp(sum_{i=j+1..t} dt_i^h A^h) (C_t . B_j) dt_j^h x_j^h + D^h x_t^h
      a = W_out N(y * silu(z); g_ssm)                      # gate first, ONE norm over all of y
    attention layer (layer_types[l] == "attention"):
      a = W_o softmax(q k^T * attention_multiplier + causal) v     # q, k unrotated
    x     = x + r * a
    f     = N(x; g_ff)
    p     = W_r f;  T = top-k(p);  w = softmax(p[T])               # over the chosen alone
    x     = x + r * (sum_{e in T} w_e W2_e (silu(g_e) * h_e) + Ws2 (silu(gs) * hs))
    logits = W_head N(x_L; g_final) / logits_scaling

The ``.m`` file: header keys 0..13 and 19 (an expert's width), 20 (the shared
MLP's width in experts), 31, 32, 34, 37 (the period and the attention layer's
place in it), 41..45 (the mixer's sizes), 46 (``embedding_multiplier``), 47
(``1 / logits_scaling``), 52 (``attention_multiplier * sqrt(head)``: the key's
multiplier under the usual ``1 / sqrt(head)``) and 49, 51, 54
(``residual_multiplier``, under the key of each branch's output: attention's,
the mixer's, the experts' and the shared MLP's sum);
``token_embedding`` (f32); per layer the attention's ``wq wk wv wo`` OR the
mixer's ``ssm_in`` (``W_in``'s ``z | xBC`` rows, Q40), ``ssm_dt`` (its ``dt``
rows, f32), ``ssm_conv_w`` (channels x taps, flat), ``ssm_conv_b``,
``ssm_a_log``, ``ssm_dt_bias``, ``ssm_d``, ``ssm_norm`` (f32), ``ssm_out``
(Q40); then ``moe_router``, the experts' ``up gate down``, ``shared_w1 shared_w2
shared_w3`` (Q40), ``rms_att``, ``rms_ffn``; then ``rms_final`` and ``wcls``
(Q40: the published head is the embedding's rows, which the converter writes
there; the seeded file draws it on its own as every configuration's does).

Departures of the reference from the published model (``last_logits``): the
weights are the seeded Q40 file's, dequantized to float32; the mixer is
computed in its ATTENTION form, the double sum above, whole for every sequence
(the published kernel scans chunks of ``mamba_chunk_size`` 256 through a state:
the same function computed another way); no state, no ring, no convolution
cache, no pages, no batching; every expert runs over every row and a row's
unchosen experts get weight 0; query rows in blocks of ``QUERY_BLOCK`` and the
head in blocks of ``HEAD_ROWS``, which changes what is held at once and not
what is computed; ``dt`` is not clamped (``time_step_limit`` (0, inf)).

What the seeded file hides, as Falcon-H1's: ``harness/mformat.py`` draws every
f32 vector ``1 + N(0, 0.02)``, so ``A = -2.72`` and ``dt = 1.31`` in every head
(a position decays by ``e^-3.6``), and its Q40 nibbles 23 and 31 one-sided, so
the logits are close to one constant.  The state is held to the reference by
``tools/check_ssm_layers.py`` on a copy of the file with those drawn again.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_GRANITE_HYBRID = 0xABCD0B
ACT_SILU = 1
# toy widths for --rehearse; the period of ten with its attention layer at 5,
# the 72 experts, the 10 a token, ONE group, a mixer head of 64 (two to a row of
# the program's ring of x) and a state that is not the head size stay
REHEARSE = dict(dim=128, hidden_dim=64, moe_hidden_dim=32, n_layers=10,
                n_heads=8, n_kv_heads=2, head_dim=16, vocab_size=2048,
                ssm_heads=4, ssm_head_dim=64, ssm_state=24)
# (key, name, is_float) of the header's pairs past the format's fourteen
EXT_KEYS = ((19, "moe_hidden_dim", False), (20, "n_shared_experts", False),
            (31, "norm_eps", True), (32, "head_dim", False),
            (34, "window_period", False), (37, "window_full_at", False),
            (41, "ssm_heads", False), (42, "ssm_head_dim", False),
            (43, "ssm_state", False), (44, "ssm_groups", False),
            (45, "ssm_conv", False), (46, "mup_embedding", True),
            (47, "mup_head", True), (49, "mup_attn_out", True),
            (51, "mup_ssm_out", True), (52, "mup_key", True),
            (54, "mup_down", True))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta") + tuple(name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time, and multiplies by this
# many rows of the head at a time
QUERY_BLOCK = 512
HEAD_ROWS = 16384
# positions of recent B, x and dt a decoded row must read beside the state: the
# least a rewindable implementation keeps out of its state
RECENT = 32
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD0B (its format module
    names it).  A text probe, not an import: the yardstick imports nothing of
    the program.  These files are also laid over checkouts older than the
    architecture (a new cell is tried on the parent commit first), which fail
    here, at once, before an 11 GB file is written for a loader that would
    refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd0b" in f.read().lower()
    except OSError:
        return False


def _layout(config: dict) -> tuple[int, int]:
    """``(period, attention_at)`` of the served layers' ``layer_types``: whole
    periods of mixer layers with one attention layer, at the same place in
    each."""
    layers = config["num_hidden_layers"]
    kinds = config["layer_types"][:layers]
    if len(kinds) != layers or set(kinds) - {"mamba", "attention"} \
            or "attention" not in kinds:
        raise SystemExit("granitemoehybrid: layer_types does not cover the "
                         "layers with mamba and attention layers alone")
    att = [t == "attention" for t in kinds]
    at = att.index(True)
    rest = att[at + 1:]
    period = rest.index(True) + 1 if True in rest else layers
    if period < 2 or layers % period or att != [
            j == at for j in range(period)] * (layers // period):
        raise SystemExit("granitemoehybrid: the layers are not whole periods of "
                         "mamba layers with one attention layer")
    return period, at


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's published keys.
    Refuses the settings the layer above does not have (they would be computed
    silently wrong), and a checkout whose program lacks the arch id."""
    def no(why):
        raise SystemExit(f"granitemoehybrid: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD0B (unknown arch id): "
           "nothing to serve the configuration with")
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling"):
        if config.get(key):
            no(f"{key} is set; it is not part of this layer")
    if config["position_embedding_type"] != "nope":
        no("position_embedding_type is not nope: this layer rotates nothing")
    if not (config["mamba_conv_bias"] and config["hidden_act"] == "silu"
            and config["normalization_function"] == "rmsnorm"):
        no("the layer has a convolution bias, silu and RMSNorm")
    if config["mamba_n_groups"] != 1:
        no("mamba_n_groups is not 1: the gated norm here is ONE norm over all "
           "of the mixer's channels")
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * dh != config["mamba_expand"] * config["hidden_size"]:
        no("mamba_n_heads * mamba_d_head is not mamba_expand * hidden_size")
    f, fs = config["intermediate_size"], config["shared_intermediate_size"]
    if fs % f:
        no("shared_intermediate_size is not a whole number of experts' widths")
    qh = config["num_attention_heads"]
    if config["hidden_size"] % qh or qh % config["num_key_value_heads"]:
        no("the attention heads do not divide hidden_size, or the kv heads them")
    if not 0 < config["num_experts_per_tok"] <= config["num_local_experts"]:
        no("num_experts_per_tok is not in 1..num_local_experts")
    period, at = _layout(config)
    head = config["hidden_size"] // qh
    return dict(
        dim=config["hidden_size"], hidden_dim=fs,
        n_layers=config["num_hidden_layers"], n_heads=qh,
        n_kv_heads=config["num_key_value_heads"],
        n_experts=config["num_local_experts"],
        n_active_experts=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], moe_hidden_dim=f,
        n_shared_experts=fs // f, norm_eps=float(config["rms_norm_eps"]),
        head_dim=head, window_period=period, window_full_at=at,
        ssm_heads=heads, ssm_head_dim=dh, ssm_state=config["mamba_d_state"],
        ssm_groups=1, ssm_conv=config["mamba_d_conv"],
        mup_embedding=float(config["embedding_multiplier"]),
        mup_head=1.0 / float(config["logits_scaling"]),
        mup_key=float(config["attention_multiplier"]) * float(np.sqrt(head)),
        # residual_multiplier, under the key of each branch's output
        **dict.fromkeys(("mup_attn_out", "mup_ssm_out", "mup_down"),
                        float(config["residual_multiplier"])))


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_GRANITE_HYBRID, hidden_act=ACT_SILU,
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


def _widths(s: dict) -> dict:
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    bc = s["ssm_groups"] * s["ssm_state"]
    return dict(inner=inner, bc=bc, channels=inner + 2 * bc,
                q=s["n_heads"] * s["head_dim"], kv=s["n_kv_heads"] * s["head_dim"])


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, f = shape["dim"], shape["vocab_size"], shape["moe_hidden_dim"]
    fs = f * shape["n_shared_experts"]
    z, h = _widths(shape), shape["ssm_heads"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        if _is_attention(shape, i):
            names += [(p + "wq", (z["q"], dim), Q40), (p + "wk", (z["kv"], dim), Q40),
                      (p + "wv", (z["kv"], dim), Q40), (p + "wo", (dim, z["q"]), Q40)]
        else:
            names += [(p + "ssm_in", (z["inner"] + z["channels"], dim), Q40),
                      (p + "ssm_dt", (h, dim), F32),
                      (p + "ssm_conv_w", (z["channels"] * shape["ssm_conv"],), F32),
                      (p + "ssm_conv_b", (z["channels"],), F32),
                      (p + "ssm_a_log", (h,), F32), (p + "ssm_dt_bias", (h,), F32),
                      (p + "ssm_d", (h,), F32), (p + "ssm_norm", (z["inner"],), F32),
                      (p + "ssm_out", (dim, z["inner"]), Q40)]
        names += [(p + "moe_router", (shape["n_experts"], dim), Q40)]
        for e in range(shape["n_experts"]):
            q = f"{p}experts.{e}."
            names += [(q + "up", (f, dim), Q40), (q + "gate", (f, dim), Q40),
                      (q + "down", (dim, f), Q40)]
        names += [(p + "shared_w1", (fs, dim), Q40), (p + "shared_w2", (dim, fs), Q40),
                  (p + "shared_w3", (fs, dim), Q40),
                  (p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def logits_at(model_path: str, prompts: list[list[int]], positions,
              act_dtype=None, wrong: str = "") -> np.ndarray:
    """Float32 logits ``(n, len(positions), vocab)`` after the tokens at
    ``positions`` (the model is causal, so position ``j``'s are ``last_logits``
    of the prompt cut after token ``j``).  ``act_dtype``: round the residual
    stream and every sub-block's output to this type's mantissa (what the
    nearest precision below the configuration's reads).  ``wrong`` names one
    deliberate fault for the CPU tests: ``softmax_all`` (the chosen weights as
    the softmax over ALL experts gave them), ``rope`` (q and k rotated),
    ``no_residual`` / ``no_key`` (that multiplier set to 1)."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    shp = {k: hd[k] for k in SHAPE_KEYS}
    w = reference.Tensors(model_path, plan(shp))
    z = _widths(hd)
    dim, hq, hkv, dh, eps = (hd["dim"], hd["n_heads"], hd["n_kv_heads"],
                             hd["head_dim"], hd["norm_eps"])
    h, p, n, taps = (hd["ssm_heads"], hd["ssm_head_dim"], hd["ssm_state"],
                     hd["ssm_conv"])
    n_exp, k_act = hd["n_experts"], hd["n_active_experts"]
    inner, bc = z["inner"], z["bc"]
    r_att, r_ssm, r_ffn = (1.0,) * 3 if wrong == "no_residual" else (
        hd["mup_attn_out"], hd["mup_ssm_out"], hd["mup_down"])
    key_mult = 1.0 if wrong == "no_key" else hd["mup_key"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def cast(x):
        """``x`` rounded to ``act_dtype``'s mantissa (``reduce_precision`` and
        not a pair of converts: the TPU's compiler drops such a pair)."""
        if act_dtype is None:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=jnp.finfo(act_dtype).nmant)

    def rms(x, gw):
        return gw * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x):  # the fault ``rope``: halves rotated by the position
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def attention(u, wq, wk, wv, wo):
        b, t, _ = u.shape
        q = (u @ wq.T).reshape(b, t, hq, dh)
        k = (u @ wk.T * key_mult).reshape(b, t, hkv, dh)
        v = (u @ wv.T).reshape(b, t, hkv, dh)
        if wrong == "rope":
            q, k = rope(q), rope(k)
        q, k = cast(q), cast(k)
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(cast(v), hq // hkv, axis=2)
        outs = []
        for lo in range(0, t, QUERY_BLOCK):  # query rows in blocks
            hi = min(lo + QUERY_BLOCK, t)
            s = jnp.einsum("bthd,bshd->bhts", q[:, lo:hi], k[:, :hi]) / np.sqrt(dh)
            mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            s = jnp.where(mask, s, -jnp.inf)
            outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                                   v[:, :hi]))
        att = cast(jnp.concatenate(outs, 1))
        return att.reshape(b, t, hq * dh) @ wo.T

    @jax.jit
    def project(u, w_in, w_dt, conv_w, conv_b, a_log, dt_bias):
        """``z``, the convolved ``x``, ``B``, ``C``, ``dt`` and the decay's
        running sum ``G_t = sum_{i<=t} dt_i A`` of one layer."""
        b, t, _ = u.shape
        zx = u @ w_in.T
        zz, xbc = cast(zx[..., :inner]), cast(zx[..., inner:])
        dt = jax.nn.softplus(u @ w_dt.T + dt_bias)                     # (B, T, H)
        ext = jnp.concatenate([jnp.zeros((b, taps - 1, xbc.shape[-1])), xbc], 1)
        cw = conv_w.reshape(-1, taps)
        xbc = cast(jax.nn.silu(sum(ext[:, j:j + t] * cw[:, j] for j in range(taps))
                               + conv_b))
        xs, bm, cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
        cum = jnp.cumsum(dt * -jnp.exp(a_log), axis=1)
        return (zz, xs.reshape(b, t, h, p).transpose(0, 2, 1, 3), bm, cm,
                dt.transpose(0, 2, 1), cum.transpose(0, 2, 1))

    @jax.jit
    def scan_block(cb, xs, bm, dt, cum, first):
        """A block of query rows ``cb (B, Tq, n)`` from position ``first``
        against every earlier position: the double sum, no state."""
        tq = cb.shape[1]
        at = first + jnp.arange(tq)
        s = jnp.einsum("btn,bjn->btj", cb, bm)[:, None]                # one group
        gq = jax.lax.dynamic_slice_in_dim(cum, first, tq, axis=2)
        seen = jnp.arange(t_len)[None, :] <= at[:, None]
        decay = jnp.exp(jnp.where(seen, gq[..., :, None] - cum[..., None, :],
                                  -jnp.inf))
        return jnp.einsum("bhtj,bhjp->bhtp", s * decay * dt[:, :, None, :], xs)

    @jax.jit
    def mix_out(y, xs, zz, d, gn, w_out):
        b = y.shape[0]
        y = (y + d[None, :, None, None] * xs).transpose(0, 2, 1, 3).reshape(
            b, t_len, inner)
        y = cast(y) * jax.nn.silu(zz)                 # gate first, then ONE norm
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return cast(y * gn) @ w_out.T

    @jax.jit
    def route(f, router):
        """Each row's weight for every expert (B, T, E): the softmax over its
        ``k`` chosen logits, 0 for the others."""
        logits = f @ router.T
        top, idx = jax.lax.top_k(logits, k_act)
        share = jax.nn.softmax(logits, -1) if wrong == "softmax_all" \
            else jnp.exp(logits - top[..., :1]) / jnp.sum(
                jnp.exp(top - top[..., :1]), -1, keepdims=True)
        return share * jnp.sum(jax.nn.one_hot(idx, n_exp), -2)

    @jax.jit
    def swiglu(acc, f, share, up, gate, down):
        return acc + share[..., None] * ((jax.nn.silu(f @ gate.T) * (f @ up.T)) @ down.T)

    with jax.default_matmul_precision("highest"):
        x = cast(jnp.asarray(w.rows("token_embedding", toks)) * hd["mup_embedding"])
        ones = jnp.ones(toks.shape, jnp.float32)
        for i in range(hd["n_layers"]):
            q = f"layers.{i}."
            u = cast(rms(x, w.vec(q + "rms_att")))
            if _is_attention(shp, i):
                a = r_att * attention(u, w.weight(q + "wq"), w.weight(q + "wk"),
                                      w.weight(q + "wv"), w.weight(q + "wo"))
            else:
                w_dt = jnp.asarray(w.raw(q + "ssm_dt").view(np.float32).reshape(h, dim))
                zz, xs, bm, cm, dt, cum = project(
                    u, w.weight(q + "ssm_in"), w_dt, w.vec(q + "ssm_conv_w"),
                    w.vec(q + "ssm_conv_b"), w.vec(q + "ssm_a_log"),
                    w.vec(q + "ssm_dt_bias"))
                y = jnp.concatenate([
                    scan_block(cm[:, first:first + QUERY_BLOCK], xs, bm, dt, cum,
                               first) for first in range(0, t_len, QUERY_BLOCK)],
                    axis=2)
                a = r_ssm * mix_out(y, xs, zz, w.vec(q + "ssm_d"),
                                    w.vec(q + "ssm_norm"), w.weight(q + "ssm_out"))
            x = cast(x + cast(a))
            f = cast(rms(x, w.vec(q + "rms_ffn")))
            shares = route(f, w.weight(q + "moe_router"))
            ff = swiglu(jnp.zeros_like(x), f, ones, w.weight(q + "shared_w3"),
                        w.weight(q + "shared_w1"), w.weight(q + "shared_w2"))
            for e in range(n_exp):  # every expert over every row, weight 0 if unchosen
                qe = f"{q}experts.{e}."
                ff = swiglu(ff, f, shares[..., e], w.weight(qe + "up"),
                            w.weight(qe + "gate"), w.weight(qe + "down"))
            x = cast(x + cast(r_ffn * ff))
        pos = jnp.asarray(list(positions), jnp.int32)
        xl = rms(x[:, pos], w.vec("rms_final"))
        # the head in blocks of rows: 100352 x 4096 dequantized at once is
        # 1.6 GB, and four times that while its blocks of 32 lie a row each
        voc = hd["vocab_size"]
        raw = w.raw("wcls").reshape(voc, -1)
        head = jax.jit(lambda v, hw: v @ hw.T)
        logits = np.concatenate([np.asarray(head(xl, reference.deq(jnp.asarray(
            raw[lo:lo + HEAD_ROWS].reshape(-1, mformat.Q40_BLOCK))).reshape(
                -1, dim))) for lo in range(0, voc, HEAD_ROWS)], axis=-1)
    return (logits * hd["mup_head"]).astype(np.float32)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """The plain reference, departures in the module docstring: float32
    ``jax.numpy`` at ``default_matmul_precision("highest")``, the mixer's
    attention form and plain softmax attention over the whole sequence, no
    state, no ring, no convolution cache, no pages, no batching; weights read
    from the same ``.m`` file the server loads, one tensor at a time."""
    return logits_at(model_path, prompts, [len(prompts[0]) - 1])[:, 0]


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def layer_kinds(cfg: dict) -> tuple[int, int]:
    """(attention layers, mixer layers) among the served layers."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(t == "attention" for t in kinds)
    return full, len(kinds) - full


def _sizes(cfg: dict) -> dict:
    """Values of an attention layer's matrices, a mixer's, a layer's router, one
    expert, the shared MLP, the head; and the counts."""
    dim, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = dim // hq
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * p
    full, mix = layer_kinds(cfg)
    return dict(att=2 * dim * hq * dh + 2 * dim * hkv * dh,
                ssm=dim * (2 * inner + 2 * n) + inner * dim, dt=h * dim,
                router=cfg["num_local_experts"] * dim, expert=3 * dim * f,
                shared=3 * dim * cfg["shared_intermediate_size"],
                head=cfg["vocab_size"] * dim, n_att=full, n_ssm=mix,
                layers=full + mix, experts=cfg["num_local_experts"],
                k=cfg["num_experts_per_tok"], kv=hkv * dh, hq=hq, dh=dh,
                h=h, p=p, g=1, n=n, channels=inner + 2 * n,
                taps=cfg["mamba_d_conv"])


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer reads in a step of ``rows`` rows, each row
    taking k of E under uniform, independent routing: ``E (1 - (1 - k/E)^rows)``:
    65.4 of 72 at 16 rows."""
    z = _sizes(cfg)
    return z["experts"] * (1.0 - (1.0 - z["k"] / z["experts"]) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the layers' feed-forward halves of a step of ``rows``
    rows need, per chip: in EVERY layer the router, the experts its rows hit
    and the shared MLP (all under scope ``moe``; what ``serve_moe_roof_pct``
    divides by that scope's time)."""
    z = _sizes(cfg)
    return z["layers"] * (z["router"] + experts_read(cfg, rows) * z["expert"]
                          + z["shared"]) * 18 / 32 / chips


def ssm_bytes(cfg: dict, rows: float = 1, chips: int = 1,
              elem_bytes: int = 2) -> float:
    """Bytes the mixer layers of a step of ``rows`` decoded rows need, per
    chip, the least any exact implementation moves under the parts the time is
    read from: ``W_in`` and ``W_out`` once at 0.5625 B a parameter and the
    ``dt`` rows as float32; per busy row and mixer layer ONE read of the
    state, ``heads x state x head_dim`` float32, and no write of it (a fold is
    amortised over a block of tokens and is not counted); the convolution's
    ``taps - 1`` live rows; the ``RECENT`` rows of ``B``, ``x`` and ``dt`` a
    rewindable implementation keeps out of its state.  Over the MIXER layers
    alone (18 of the 20 served).  What ``serve_ssm_roof_pct`` divides by
    ``serve_ssm_ms_per_step``'s time."""
    z = _sizes(cfg)
    return z["n_ssm"] * (z["ssm"] * 18 / 32 + 4 * z["dt"]
                         + rows * _row_bytes(z, elem_bytes)) / chips


def _row_bytes(z: dict, elem_bytes: int = 2) -> float:
    """What one busy row reads of its own in one mixer layer: the state, the
    convolution's live rows, the recent rows."""
    state = z["h"] * z["n"] * z["p"] * 4
    conv = (z["taps"] - 1) * z["channels"] * elem_bytes
    recent = RECENT * ((z["h"] * z["p"] + z["g"] * z["n"]) * elem_bytes + 4 * z["h"])
    return state + conv + recent


def ssm_flops(cfg: dict, rows: float = 1, chips: int = 1) -> float:
    """Multiply-adds x 2 of the same: a row through ``W_in``, the ``dt`` rows and
    ``W_out``, each head's ``C`` against its state, and its scores and values
    over the ``RECENT`` rows, in every mixer layer."""
    z = _sizes(cfg)
    per_row = z["ssm"] + z["dt"] + z["h"] * (
        z["n"] * z["p"] + RECENT * (z["n"] + z["p"]))
    return 2.0 * z["n_ssm"] * rows * per_row / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Bytes of weights a step of ``rows`` rows streams, per chip: the
    attention layers' and the mixer layers' matrices and the head once as
    packed Q40 (18 B per 32 values), the ``dt`` rows as float32, and what the
    feed-forward halves need."""
    z = _sizes(cfg)
    return ((z["n_att"] * z["att"] + z["n_ssm"] * z["ssm"] + z["head"]) * 18 / 32
            + 4 * z["n_ssm"] * z["dt"]) / chips + moe_bytes(cfg, chips, rows)


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one more cached position adds, per chip: the attention
    layers' (8,192 B over the 2 served; a mixer layer's state is there whatever
    the context's depth)."""
    z = _sizes(cfg)
    return 2 * z["n_att"] * z["kv"] * elem_bytes / chips


def kv_read_bytes(cfg: dict, context: float, chips: int = 1, elem_bytes: int = 2,
                  rows: float = 1) -> float:
    """Bytes of live keys and values ``rows`` decoded tokens, each at
    ``context`` positions, must read: every live position in every attention
    layer.  What ``serve_attn_kv_roof_pct`` divides by the time under scope
    ``attn`` a step (here that scope also holds the mixer layers' reads, so the
    share reads low)."""
    return kv_bytes_per_token(cfg, chips, elem_bytes) * context * rows


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights its rows hit once,
    each row's state, convolution rows and recent rows in every mixer layer,
    and the live context of every row (``live_context_tokens`` summed over
    rows)."""
    z = _sizes(cfg)
    return (weight_bytes(cfg, chips, rows)
            + z["n_ssm"] * max(rows, 1) * _row_bytes(z) / chips
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    its layer's attention or mixer projections, the router, its k experts, the
    shared MLP and the head, reads its state in every mixer layer, and scores
    its live context in every attention layer."""
    z = _sizes(cfg)
    mat = (z["n_att"] * z["att"] + z["layers"] * (
        z["router"] + z["k"] * z["expert"] + z["shared"]) + z["head"])
    att = z["n_att"] * 2 * z["hq"] * z["dh"]
    return (2.0 * (mat * rows + att * live_context_tokens) / chips
            + ssm_flops(cfg, rows, chips))
