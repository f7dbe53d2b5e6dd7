"""Falcon-H1 (``"model": "falcon_h1"``): grouped-query attention and a Mamba-2
state-space mixer side by side in EVERY block (Zuo et al., "Falcon-H1: A Family
of Hybrid-Head Language Models", TII 2025).  The ``.m`` layout, the plain
reference and the cost arithmetic of ``tiiuae/Falcon-H1-34B-Instruct``.

The block as the reference computes it (``N`` RMSNorm, eps ``rms_norm_eps``;
every ``m_*`` a scalar of the config; ``h`` a mixer head of ``P`` values, ``g =
h // (heads / groups)`` its group of ``n`` state rows)::

    x = E[token] * embedding_multiplier
    u = N(x; g_in)                                       # ONE norm feeds both mixers
    a = W_o softmax-attention(RoPE(W_q ua), RoPE(W_k ua * key_multiplier), W_v ua)
            * attention_out_multiplier,   ua = u * attention_in_multiplier
    [z | xBC | dt] = (W_in us) * ssm_multipliers,        us = u * ssm_in_multiplier
    xBC_t = silu(sum_{j<4} w[:, j] xBC_{t-3+j} + b)      # depthwise, causal
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    y_t^h = sum_{j<=t} exp(sum_{i=j+1..t} dt_i^h A^h) (C_t^g . B_j^g) dt_j^h x_j^h + D^h x_t^h
    s = W_out N_grouped(y * silu(z); g_ssm) * ssm_out_multiplier
    x = x + a + s
    x = x + W_down(silu(W_gate f * m_gate) * W_up f) * m_down,   f = N(x; g_ff)
    logits = W_head N(x; g_final) * lm_head_multiplier

The ``.m`` file: header keys 0..13 (key 12, ``rope_theta``, clipped to an i32),
31 (``norm_eps``), 32 (``head_dim``) and 41..60 (the mixer's sizes, the fourteen
multipliers as f32 bits, ``rope_theta`` as a float); ``token_embedding`` (f32);
per layer ``wq wk wv wo`` (Q40), ``ssm_in`` (``W_in``'s ``z | xBC`` rows, Q40),
``ssm_dt`` (its ``dt`` rows, f32: 32 rows are no Q40 matrix), ``ssm_conv_w``
(channels x taps, flat), ``ssm_conv_b``, ``ssm_a_log``, ``ssm_dt_bias``,
``ssm_d``, ``ssm_norm`` (f32), ``ssm_out`` (Q40), ``w1 w2 w3`` (Q40),
``rms_att``, ``rms_ffn``; then ``rms_final`` and ``wcls`` (Q40, untied).

Departures of the reference from the published model (``last_logits``): the
weights are the seeded Q40 file's, dequantized to float32; the mixer is
computed in its ATTENTION form, the double sum above, whole for every sequence
(the published kernel scans chunks of ``mamba_chunk_size`` 128 through a state:
the same function computed another way); no state, no ring, no convolution
cache, no pages; query rows in blocks of ``QUERY_BLOCK`` and the head in blocks
of ``HEAD_ROWS``, which changes what is held at once and not what is computed;
``dt`` is not clamped (``time_step_limit`` (0, inf)); the multipliers are
applied where the equations above put them (``assumed`` in the configuration).

What the seeded file hides.  Its decay: ``harness/mformat.py`` draws every f32
vector ``1 + N(0, 0.02)``, so ``A = -e^1 = -2.72`` and ``dt = softplus(1 + .) =
1.31`` in every head: a position decays by ``e^-3.6`` = 0.03 and nothing older
than three positions moves a logit.  And its logits are close to one constant:
``mformat`` draws a Q40 block's packed bytes as two 63-bit integers, values 23
and 31 of every block of 32 have mean -3.5 steps, every row of a Q40 matrix sums
far off 0, and two blocks into the stack the residual stream is one direction
(every prompt serves one token; ``tools/check_ssm.py`` has the arithmetic).  The
program's work is the same whatever the weights are; its state is held to the
reference by ``tools/check_ssm.py`` on a copy of the file in which it draws
those nibbles, ``A_log`` and ``dt_bias`` again.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_FALCON_H1 = 0xABCD0A
ACT_SILU = 1
# toy widths for --rehearse; five query heads a kv head, two groups and a state
# that is not the head size stay
REHEARSE = dict(dim=128, hidden_dim=256, n_layers=2, n_heads=10, n_kv_heads=2,
                head_dim=32, vocab_size=2048, ssm_heads=4, ssm_head_dim=32,
                ssm_state=48, ssm_groups=2)
MUPS = ("mup_embedding", "mup_head", "mup_attn_in", "mup_attn_out", "mup_ssm_in",
        "mup_ssm_out", "mup_key", "mup_gate", "mup_down", "mup_z", "mup_x",
        "mup_b", "mup_c", "mup_dt")
EXT_KEYS = ((31, "norm_eps", True), (32, "head_dim", False),
            (41, "ssm_heads", False), (42, "ssm_head_dim", False),
            (43, "ssm_state", False), (44, "ssm_groups", False),
            (45, "ssm_conv", False)) + tuple(
                (46 + i, name, True) for i, name in enumerate(MUPS)) + (
                    (60, "rope_theta_f32", True),)
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "vocab_size", "seq_len") + tuple(name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time, and multiplies by this
# many rows of the head at a time
QUERY_BLOCK = 512
HEAD_ROWS = 16384
# positions of recent B, x and dt a decoded row must read beside the state: the
# least a rewindable implementation keeps out of its state
RECENT = 32
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD0A (its format module
    names it).  A text probe, not an import: the yardstick imports nothing of
    the program.  These files are also laid over checkouts older than the
    architecture (a new cell is tried on the parent commit first), which fail
    here, at once, before a 10 GB file is written for a loader that would
    refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd0a" in f.read().lower()
    except OSError:
        return False


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's published keys."""
    def no(why):
        raise SystemExit(f"falcon_h1: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD0A (falcon_h1): unknown "
           "arch id, nothing to serve the configuration with")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias",
                "rope_scaling", "tie_word_embeddings", "mamba_norm_before_gate",
                "attn_layer_indices"):
        if config.get(key):
            no(f"{key} is set; it is not part of this block")
    if not (config["mamba_conv_bias"] and config["mamba_rms_norm"]
            and config["mamba_use_mlp"] and config["hidden_act"] == "silu"):
        no("the block has a convolution bias, a gated grouped RMSNorm, a "
           "feed-forward and silu")
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * dh != config["mamba_d_ssm"] or heads % config["mamba_n_groups"]:
        no("mamba_n_heads * mamba_d_head is not mamba_d_ssm, or the heads are "
           "not whole groups")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        no("num_attention_heads is not a multiple of num_key_value_heads")
    gate, down = config["mlp_multipliers"]
    mup = dict(zip(MUPS[9:], config["ssm_multipliers"]))
    return dict(
        dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]), head_dim=config["head_dim"],
        ssm_heads=heads, ssm_head_dim=dh, ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"], ssm_conv=config["mamba_d_conv"],
        mup_embedding=config["embedding_multiplier"],
        mup_head=config["lm_head_multiplier"],
        mup_attn_in=config["attention_in_multiplier"],
        mup_attn_out=config["attention_out_multiplier"],
        mup_ssm_in=config["ssm_in_multiplier"],
        mup_ssm_out=config["ssm_out_multiplier"],
        mup_key=config["key_multiplier"], mup_gate=gate, mup_down=down, **mup,
        rope_theta_f32=float(config["rope_theta"]))


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_FALCON_H1, hidden_act=ACT_SILU,
                n_experts=0, n_active_experts=0, weights_ftype=Q40,
                rope_theta=min(int(shape["rope_theta_f32"]), 2 ** 31 - 1))
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


def _widths(s: dict) -> dict:
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    bc = s["ssm_groups"] * s["ssm_state"]
    return dict(inner=inner, bc=bc, channels=inner + 2 * bc,
                q=s["n_heads"] * s["head_dim"], kv=s["n_kv_heads"] * s["head_dim"])


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, hid = shape["dim"], shape["vocab_size"], shape["hidden_dim"]
    z, h = _widths(shape), shape["ssm_heads"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (z["q"], dim), Q40), (p + "wk", (z["kv"], dim), Q40),
                  (p + "wv", (z["kv"], dim), Q40), (p + "wo", (dim, z["q"]), Q40),
                  (p + "ssm_in", (z["inner"] + z["channels"], dim), Q40),
                  (p + "ssm_dt", (h, dim), F32),
                  (p + "ssm_conv_w", (z["channels"] * shape["ssm_conv"],), F32),
                  (p + "ssm_conv_b", (z["channels"],), F32),
                  (p + "ssm_a_log", (h,), F32), (p + "ssm_dt_bias", (h,), F32),
                  (p + "ssm_d", (h,), F32), (p + "ssm_norm", (z["inner"],), F32),
                  (p + "ssm_out", (dim, z["inner"]), Q40),
                  (p + "w1", (hid, dim), Q40), (p + "w2", (dim, hid), Q40),
                  (p + "w3", (hid, dim), Q40),
                  (p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def logits_at(model_path: str, prompts: list[list[int]], positions,
              act_dtype=None) -> np.ndarray:
    """Float32 logits ``(n, len(positions), vocab)`` after the tokens at
    ``positions`` (the model is causal, so position ``j``'s are ``last_logits``
    of the prompt cut after token ``j``).  ``act_dtype``: round the residual
    stream and every sub-block's output to this type's mantissa (what the
    nearest precision below the configuration's reads)."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    shp = {k: hd[k] for k in SHAPE_KEYS}
    w = reference.Tensors(model_path, plan(shp))
    z = _widths(hd)
    dim, hq, hkv, dh, eps = (hd["dim"], hd["n_heads"], hd["n_kv_heads"],
                             hd["head_dim"], hd["norm_eps"])
    h, p, g, n, taps = (hd["ssm_heads"], hd["ssm_head_dim"], hd["ssm_groups"],
                        hd["ssm_state"], hd["ssm_conv"])
    inner, bc = z["inner"], z["bc"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]
    mup_xbc = jnp.asarray(np.repeat([hd["mup_x"], hd["mup_b"], hd["mup_c"]],
                                    [inner, bc, bc]).astype(np.float32))

    def cast(x):
        """``x`` rounded to ``act_dtype``'s mantissa (``reduce_precision`` and
        not a pair of converts: the TPU's compiler drops such a pair)."""
        if act_dtype is None:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=jnp.finfo(act_dtype).nmant)

    def rms(x, gw):
        return gw * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    causal = jnp.tril(jnp.ones((t_len, t_len), bool))

    @jax.jit
    def attention(u, wq, wk, wv, wo):
        b, t, _ = u.shape
        ua = u * hd["mup_attn_in"]
        q = (ua @ wq.T).reshape(b, t, hq, dh)
        k = (ua @ wk.T * hd["mup_key"]).reshape(b, t, hkv, dh)
        v = (ua @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta_f32"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        q = cast(rope(q, jnp.cos(ang), jnp.sin(ang)))
        k = cast(rope(k, jnp.cos(ang), jnp.sin(ang)))
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(cast(v), hq // hkv, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dh)
        s = jnp.where(causal, s, -jnp.inf)
        att = cast(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v))
        return att.reshape(b, t, hq * dh) @ wo.T * hd["mup_attn_out"]

    @jax.jit
    def project(u, w_in, w_dt, conv_w, conv_b, a_log, dt_bias):
        """``z``, the convolved ``x``, ``B``, ``C``, ``dt`` and the decay's
        running sum ``G_t = sum_{i<=t} dt_i A`` of one layer."""
        b, t, _ = u.shape
        us = u * hd["mup_ssm_in"]
        zx = us @ w_in.T
        zz = cast(zx[..., :inner] * hd["mup_z"])
        xbc = cast(zx[..., inner:] * mup_xbc)
        dt = jax.nn.softplus(us @ w_dt.T * hd["mup_dt"] + dt_bias)   # (B, T, H)
        ext = jnp.concatenate([jnp.zeros((b, taps - 1, xbc.shape[-1])), xbc], 1)
        cw = conv_w.reshape(-1, taps)
        xbc = cast(jax.nn.silu(sum(ext[:, j:j + t] * cw[:, j] for j in range(taps))
                               + conv_b))
        xs, bm, cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
        cum = jnp.cumsum(dt * -jnp.exp(a_log), axis=1)
        return (zz, xs.reshape(b, t, h, p).transpose(0, 2, 1, 3),
                bm.reshape(b, t, g, n).transpose(0, 2, 1, 3),
                cm.reshape(b, t, g, n).transpose(0, 2, 1, 3),
                dt.transpose(0, 2, 1), cum.transpose(0, 2, 1))

    @jax.jit
    def scan_block(cb, xs, bm, dt, cum, first):
        """A block of query rows ``cb (B, G, Tq, n)`` from position ``first``
        against every earlier position: the double sum, no state."""
        tq = cb.shape[2]
        at = first + jnp.arange(tq)
        s = jnp.einsum("bgtn,bgjn->bgtj", cb, bm)                      # (B, G, Tq, T)
        s = jnp.repeat(s, h // g, axis=1)                              # (B, H, Tq, T)
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
        y = cast(y) * jax.nn.silu(zz)                 # gate first, then the norm
        y = y.reshape(b, t_len, g, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return cast(y.reshape(b, t_len, inner) * gn) @ w_out.T * hd["mup_ssm_out"]

    @jax.jit
    def ffn(x, w1, w2, w3, gw):
        f = rms(x, gw)
        return cast(jax.nn.silu(f @ w1.T * hd["mup_gate"]) * (f @ w3.T)) @ w2.T \
            * hd["mup_down"]

    with jax.default_matmul_precision("highest"):
        x = cast(jnp.asarray(w.rows("token_embedding", toks)) * hd["mup_embedding"])
        for i in range(hd["n_layers"]):
            q = f"layers.{i}."
            u = cast(rms(x, w.vec(q + "rms_att")))
            a = cast(attention(u, w.weight(q + "wq"), w.weight(q + "wk"),
                               w.weight(q + "wv"), w.weight(q + "wo")))
            w_dt = jnp.asarray(w.raw(q + "ssm_dt").view(np.float32).reshape(h, dim))
            zz, xs, bm, cm, dt, cum = project(
                u, w.weight(q + "ssm_in"), w_dt, w.vec(q + "ssm_conv_w"),
                w.vec(q + "ssm_conv_b"), w.vec(q + "ssm_a_log"),
                w.vec(q + "ssm_dt_bias"))
            y = jnp.concatenate([
                scan_block(cm[:, :, first:first + QUERY_BLOCK], xs, bm, dt, cum,
                           first) for first in range(0, t_len, QUERY_BLOCK)], axis=2)
            s = cast(mix_out(y, xs, zz, w.vec(q + "ssm_d"), w.vec(q + "ssm_norm"),
                             w.weight(q + "ssm_out")))
            x = cast(x + a + s)
            x = cast(x + cast(ffn(x, w.weight(q + "w1"), w.weight(q + "w2"),
                                  w.weight(q + "w3"), w.vec(q + "rms_ffn"))))
        pos = jnp.asarray(list(positions), jnp.int32)
        xl = rms(x[:, pos], w.vec("rms_final"))
        # the head in blocks of rows: 261120 x 5120 dequantized at once is
        # 5.3 GB, and four times that while its blocks of 32 lie a row each
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
    state, no ring, no convolution cache, no pages; weights read from the same
    ``.m`` file the server loads, one tensor at a time."""
    return logits_at(model_path, prompts, [len(prompts[0]) - 1])[:, 0]


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def _sizes(cfg: dict) -> dict:
    dim, hid = cfg["hidden_size"], cfg["intermediate_size"]
    dh, hkv, hq = cfg["head_dim"], cfg["num_key_value_heads"], cfg["num_attention_heads"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = h * p
    return dict(att=2 * dim * hq * dh + 2 * dim * hkv * dh,
                ssm=dim * (2 * inner + 2 * g * n) + inner * dim, dt=h * dim,
                ffn=3 * dim * hid, head=cfg["vocab_size"] * dim,
                layers=cfg["num_hidden_layers"], kv=hkv * dh, hq=hq, dh=dh,
                h=h, p=p, g=g, n=n, channels=inner + 2 * g * n,
                taps=cfg["mamba_d_conv"])


def ssm_bytes(cfg: dict, rows: float = 1, chips: int = 1,
              elem_bytes: int = 2) -> float:
    """Bytes the state-space mixers of a step of ``rows`` decoded rows need,
    per chip, the least any exact implementation moves under the parts the time
    is read from: ``W_in`` and ``W_out`` once at 0.5625 B a parameter and the
    ``dt`` rows as float32; per busy row and layer ONE read of the state,
    ``heads x state x head_dim`` float32, and no write of it (a fold is
    amortised over a block of tokens and is not counted); the convolution's
    ``taps - 1`` live rows; the ``RECENT`` rows of ``B``, ``x`` and ``dt`` a
    rewindable implementation keeps out of its state.  What
    ``serve_ssm_roof_pct`` divides by ``serve_ssm_ms_per_step``'s time."""
    z = _sizes(cfg)
    return z["layers"] * (z["ssm"] * 18 / 32 + 4 * z["dt"]
                          + rows * _row_bytes(z, elem_bytes)) / chips


def _row_bytes(z: dict, elem_bytes: int = 2) -> float:
    """What one busy row reads of its own in one layer: the state, the
    convolution's live rows, the recent rows."""
    state = z["h"] * z["n"] * z["p"] * 4
    conv = (z["taps"] - 1) * z["channels"] * elem_bytes
    recent = RECENT * ((z["h"] * z["p"] + z["g"] * z["n"]) * elem_bytes + 4 * z["h"])
    return state + conv + recent


def ssm_flops(cfg: dict, rows: float = 1, chips: int = 1) -> float:
    """Multiply-adds x 2 of the same: a row through ``W_in``, the ``dt`` rows and
    ``W_out``, each head's ``C`` against its state, and its scores and values
    over the ``RECENT`` rows."""
    z = _sizes(cfg)
    per_row = z["ssm"] + z["dt"] + z["h"] * (
        z["n"] * z["p"] + RECENT * (z["n"] + z["p"]))
    return 2.0 * z["layers"] * rows * per_row / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Bytes of weights a step streams, per chip: every layer's attention,
    mixer and SwiGLU matrices and the head as packed Q40 (18 B per 32 values),
    the ``dt`` rows as float32."""
    z = _sizes(cfg)
    return (z["layers"] * ((z["att"] + z["ssm"] + z["ffn"]) * 18 / 32 + 4 * z["dt"])
            + z["head"] * 18 / 32) / chips


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one cached position holds over all layers, per chip
    (36,864 B at 18 layers in bfloat16): the attention's; the mixer's state is
    there whatever the context's depth."""
    z = _sizes(cfg)
    return 2 * z["layers"] * z["kv"] * elem_bytes / chips


def kv_read_bytes(cfg: dict, context: float, chips: int = 1, elem_bytes: int = 2,
                  rows: float = 1) -> float:
    """Bytes of live keys and values ``rows`` decoded tokens, each at
    ``context`` positions, must read: every live position in every layer.  What
    ``serve_attn_kv_roof_pct`` divides by the time under scope ``attn`` a step
    (here that scope also holds the mixer's reads, so the share reads low)."""
    return kv_bytes_per_token(cfg, chips, elem_bytes) * context * rows


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights once, each row's
    state, convolution rows and recent rows, and the live context of every row
    (``live_context_tokens`` summed over rows)."""
    z = _sizes(cfg)
    return (weight_bytes(cfg, chips, rows)
            + z["layers"] * max(rows, 1) * _row_bytes(z) / chips
            + kv_bytes_per_token(cfg, chips) * live_context_tokens)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    the projections, the SwiGLU and the head, reads its state, and scores its
    live context in every layer."""
    z = _sizes(cfg)
    mat = z["layers"] * (z["att"] + z["ffn"]) + z["head"]
    att = z["layers"] * 2 * z["hq"] * z["dh"]
    return (2.0 * (mat * rows + att * live_context_tokens) / chips
            + ssm_flops(cfg, rows, chips))
