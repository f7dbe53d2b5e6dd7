"""Brumby (``"model": "brumby"``): Qwen3's dense block with every attention
layer replaced by power retention (Buckman, Gelada, Zhang, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239).  The ``.m`` layout, the
plain reference and the cost arithmetic of ``manifestai/Brumby-14B-Base``.

The layer as the reference computes it, in the ATTENTION form (``u`` the
input-normed residual, ``h`` a query head, ``g = h // (heads / kv heads)`` its
kv head, ``dh`` the head size, degree ``p`` = 2)::

    q_t^h = RoPE(RMSNorm_q(W_q u_t)^h, t)    k_t^g = RoPE(RMSNorm_k(W_k u_t)^g, t)
    v_t^g = (W_v u_t)^g                      log gamma_t^g = logsigmoid((W_g u_t)^g)
    a_{t,j}^h = exp(sum_{i=j+1..t} log gamma_i^g) * (q_t^h . k_j^g / sqrt(dh))^p    j <= t
    y_t^h = sum_j a_{t,j}^h v_j^g / (sum_j a_{t,j}^h + eps)
    x'    = x + W_o concat_h y_t^h ;  then x' + W_2(silu(W_1 n) * W_3 n), n = RMSNorm(x')

The ``.m`` file: header keys 0..13, 31 (``norm_eps``) and 39
(``retention_degree``); ``token_embedding`` (f32); per layer ``wq``, ``wk``,
``wv``, ``wo`` (Q40), ``wg`` (n_kv_heads, dim; f32), ``q_norm`` / ``k_norm``
(one head's size, f32), ``w1``, ``w2``, ``w3`` (Q40), ``rms_att``, ``rms_ffn``;
then ``rms_final`` and ``wcls`` (Q40, untied).

Departures of the reference from the published model (``last_logits``): the
weights are the seeded Q40 file's, dequantized to float32; the attention form
is computed whole for every sequence (the published implementation answers
short sequences from keys and values and long ones from a chunked state,
``switch_over_seq_len``: the same function computed another way); no state, no
ring, no ``phi``, no cache; query rows in blocks of ``QUERY_BLOCK``, which
changes what is held at once and not what is computed; ``eps`` = 1e-6 and the
``1 / sqrt(dh)`` scale are assumed (the scale cancels in the quotient but for
``eps``).

What the seeded file's gates are: ``wg`` is an f32 matrix like any other, drawn
N(0, 0.02) by ``harness/mformat.py``, so ``W_g u`` is about N(0, 1.4) and
``log gamma = logsigmoid`` of it has median -0.69: a context older than a few
tens of positions is below float32's resolution in these logits.  The program's
work is the same whatever the gates are; its state is held to the reference by
``tools/check_retention.py`` on a file whose gates it redraws (there: how).
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_BRUMBY = 0xABCD08
ACT_SILU = 1
EPS = 1e-6
# toy widths for --rehearse; five query heads a kv head and the degree stay
REHEARSE = dict(dim=160, hidden_dim=256, n_layers=4, n_heads=10, n_kv_heads=2,
                vocab_size=2048, seq_len=32768)
EXT_KEYS = ((31, "norm_eps", True), (39, "retention_degree", False))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "vocab_size", "seq_len", "rope_theta") + tuple(
                  name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time, and multiplies by this
# many rows of the head at a time
QUERY_BLOCK = 512
HEAD_ROWS = 16384
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD08 (its format
    module names it).  A text probe, not an import: the yardstick imports
    nothing of the program.  These files are also laid over checkouts older
    than the architecture (a new cell is tried on the parent commit first),
    which fail here, at once, before a 7 GB file is written for a loader that
    would refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd08" in f.read().lower()
    except OSError:
        return False


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's published keys."""
    def no(why):
        raise SystemExit(f"brumby: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD08 (brumby): unknown "
           "arch id, nothing to serve the configuration with")
    if config.get("attention_bias") or config.get("use_sliding_window") \
            or config.get("rope_scaling") or config.get("tie_word_embeddings"):
        no("attention_bias, use_sliding_window, rope_scaling and tied "
           "embeddings are not part of this block")
    heads, dh = config["num_attention_heads"], config["head_dim"]
    if heads * dh != config["hidden_size"]:
        no("num_attention_heads * head_dim is not hidden_size (the .m file "
           "of this arch states no head size)")
    if heads % config["num_key_value_heads"]:
        no("num_attention_heads is not a multiple of num_key_value_heads")
    return dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
                n_layers=config["num_hidden_layers"], n_heads=heads,
                n_kv_heads=config["num_key_value_heads"],
                vocab_size=config["vocab_size"],
                seq_len=config["max_position_embeddings"],
                rope_theta=config["rope_theta"],
                norm_eps=float(config["rms_norm_eps"]),
                retention_degree=2)  # the release's; config.json has no key for it


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_BRUMBY, hidden_act=ACT_SILU,
                n_experts=0, n_active_experts=0, weights_ftype=Q40,
                rope_theta=int(shape["rope_theta"]))
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


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, hid = shape["dim"], shape["vocab_size"], shape["hidden_dim"]
    dh = dim // shape["n_heads"]
    kv = shape["n_kv_heads"] * dh
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (dim, dim), Q40), (p + "wk", (kv, dim), Q40),
                  (p + "wv", (kv, dim), Q40), (p + "wo", (dim, dim), Q40),
                  (p + "wg", (shape["n_kv_heads"], dim), F32),
                  (p + "q_norm", (dh,), F32), (p + "k_norm", (dh,), F32),
                  (p + "w1", (hid, dim), Q40), (p + "w2", (dim, hid), Q40),
                  (p + "w3", (hid, dim), Q40),
                  (p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def _forward(model_path: str, prompts: list[list[int]], positions,
             act_dtype=None, state: str = "", regate=None) -> np.ndarray:
    """Float32 logits ``(n, len(positions), vocab)`` after the tokens at
    ``positions``.  ``act_dtype``: round the residual stream and every
    sub-block's output to this type's mantissa (what the nearest precision below
    the configuration's reads).  ``state``, for ``tools/check_retention.py``'s
    two counter-readings: what a program that kept the tokens before each
    query's last ``RECENT`` positions in a *wrong* state would answer,
    ``"zero"`` (those tokens dropped) or ``"bfloat16"`` (their part of both
    sums rounded to bfloat16's mantissa as it is carried from block to
    block).  ``regate``: :func:`regate`'s callback, which replaces a layer's
    gate matrix as the pass reaches it."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    shp = {k: hd[k] for k in SHAPE_KEYS}
    w = reference.Tensors(model_path, plan(shp))
    hq, hkv, eps = hd["n_heads"], hd["n_kv_heads"], hd["norm_eps"]
    dh = hd["dim"] // hq
    if hd["retention_degree"] != 2:
        raise SystemExit("brumby: the reference squares its scores (degree 2)")
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def cast(x, dt=act_dtype):
        """``x`` rounded to ``dt``'s mantissa (``reduce_precision`` and not a
        pair of converts: the TPU's compiler drops such a pair)."""
        if dt is None:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=jnp.finfo(dt).nmant)

    def rms(x, g):
        return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    @jax.jit
    def project(x, wq, wk, wv, wg, g, gq, gk):
        b, t, _ = x.shape
        u = rms(x, g)
        q = rms((u @ wq.T).reshape(b, t, hq, dh), gq)   # each head's own
        k = rms((u @ wk.T).reshape(b, t, hkv, dh), gk)
        v = (u @ wv.T).reshape(b, t, hkv, dh)
        freqs = 1.0 / (float(hd["rope_theta"]) ** (
            jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
        q, k = rope(q, jnp.cos(ang), jnp.sin(ang)), rope(k, jnp.cos(ang), jnp.sin(ang))
        # the gate's running sum: G_t = sum_{i <= t} log gamma_i, (B, Hkv, T)
        cum = jnp.cumsum(jax.nn.log_sigmoid(u @ wg.T), axis=1).transpose(0, 2, 1)
        return (q.transpose(0, 2, 1, 3).reshape(b, hkv, hq // hkv, t, dh),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), cum)

    @jax.jit
    def scores(qb, k, cum, first):
        """A block of query rows ``qb (B, Hkv, m, Tq, dh)`` from position
        ``first`` against every key: the masked, decayed, squared scores."""
        tq = qb.shape[3]
        at = first + jnp.arange(tq)
        s = jnp.einsum("bgmtd,bgjd->bgmtj", qb, k) * dh ** -0.5
        gq = jax.lax.dynamic_slice_in_dim(cum, first, tq, axis=2)
        seen = jnp.arange(t_len)[None, :] <= at[:, None]               # (Tq, T)
        decay = jnp.exp(jnp.where(seen, gq[..., :, None] - cum[..., None, :],
                                  -jnp.inf))                           # (B, Hkv, Tq, T)
        return s * s * decay[:, :, None]

    def retain(q, k, v, cum):
        outs = []
        for first in range(0, t_len, QUERY_BLOCK):
            qb = q[:, :, :, first:first + QUERY_BLOCK]
            a = scores(qb, k, cum, first)
            if state:  # a counter-reading: the old tokens through a wrong state
                at = first + jnp.arange(qb.shape[3])
                old = jnp.arange(t_len)[None, :] <= (at[:, None] - RECENT)
                a_old = jnp.where(old, a, 0.0)
                a = a - a_old
                num_old = den_old = 0.0
                if state != "zero":  # carried block to block in bfloat16
                    for j0 in range(0, max(first + qb.shape[3] - RECENT, 0), RECENT):
                        blk = slice(j0, j0 + RECENT)
                        num_old = cast(num_old + jnp.einsum(
                            "bgmtj,bgjd->bgmtd", a_old[..., blk], v[:, :, blk]),
                            jnp.bfloat16)
                        den_old = cast(den_old + a_old[..., blk].sum(-1),
                                       jnp.bfloat16)
                num = jnp.einsum("bgmtj,bgjd->bgmtd", a, v) + num_old
                den = a.sum(-1) + den_old
            else:
                num = jnp.einsum("bgmtj,bgjd->bgmtd", a, v)
                den = a.sum(-1)
            outs.append(num / (den[..., None] + EPS))
        y = jnp.concatenate(outs, axis=3)                 # (B, Hkv, m, T, dh)
        b = y.shape[0]
        return y.reshape(b, hq, t_len, dh).transpose(0, 2, 1, 3).reshape(
            b, t_len, hq * dh)

    @jax.jit
    def ffn(x, w1, w2, w3, g):
        n = rms(x, g)
        return (jax.nn.silu(n @ w1.T) * (n @ w3.T)) @ w2.T

    with jax.default_matmul_precision("highest"):
        x = cast(jnp.asarray(w.rows("token_embedding", toks)))
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            wg = w.raw(p + "wg").view(np.float32).reshape(hkv, -1)
            if regate is not None:  # from the mean of this layer's normed input
                wg = regate(i, np.asarray(jnp.mean(
                    rms(x, w.vec(p + "rms_att")), axis=(0, 1))))
            q, k, v, cum = project(
                x, w.weight(p + "wq"), w.weight(p + "wk"), w.weight(p + "wv"),
                jnp.asarray(wg, jnp.float32),
                w.vec(p + "rms_att"), w.vec(p + "q_norm"), w.vec(p + "k_norm"))
            y = cast(retain(cast(q), cast(k), cast(v), cum))
            x = cast(x + cast(y @ w.weight(p + "wo").T))
            x = cast(x + cast(ffn(x, w.weight(p + "w1"), w.weight(p + "w2"),
                                  w.weight(p + "w3"), w.vec(p + "rms_ffn"))))
        pos = jnp.asarray(list(positions), jnp.int32)
        xl = rms(x[:, pos], w.vec("rms_final"))
        # the head in blocks of rows: 151936 x 5120 dequantized at once is
        # 3.1 GB, and four times that while its blocks of 32 lie a row each
        voc = hd["vocab_size"]
        raw = w.raw("wcls").reshape(voc, -1)
        head = jax.jit(lambda a, h: a @ h.T)
        logits = np.concatenate([np.asarray(head(xl, reference.deq(jnp.asarray(
            raw[lo:lo + HEAD_ROWS].reshape(-1, mformat.Q40_BLOCK))).reshape(
                -1, hd["dim"]))) for lo in range(0, voc, HEAD_ROWS)], axis=-1)
    return logits.astype(np.float32)


# positions a ``state=`` counter-reading keeps exact behind each query: the
# least the program's lagged state ever keeps out of its state matrix
# (``dllama_tpu/ops/retention.py REWIND``), so everything such a reading gets
# wrong is something the program reads from its state
RECENT = 32


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """The plain reference, departures in the module docstring: float32
    ``jax.numpy`` at ``default_matmul_precision("highest")``; the attention form
    of power retention over the whole sequence (a cumulative sum of ``log
    gamma``, the masked, decayed, squared scores, the quotient by their sum);
    no state, no ring, no ``phi``, no cache; weights read from the same ``.m``
    file the server loads, one tensor at a time."""
    return _forward(model_path, prompts, [len(prompts[0]) - 1])[:, 0]


def logits_at(model_path: str, prompts: list[list[int]], positions,
              act_dtype=None, state: str = "") -> np.ndarray:
    """``(n, len(positions), vocab)`` of the same reference in one pass: the
    logits after the tokens at ``positions`` (the model is causal, so position
    ``j``'s are ``last_logits`` of the prompt cut after token ``j``)."""
    return _forward(model_path, prompts, list(positions), act_dtype, state)


def regate(model_path: str, prompts: list[list[int]], draw) -> None:
    """One pass of the same reference over ``prompts`` in which each layer's
    gate matrix is ``draw(layer, mean)``, ``mean (dim,)`` the mean over the
    prompts' positions of that layer's normed input as the layers before it
    (their new gates included) leave it.  For ``tools/check_retention.py``,
    which draws gates that are not saturated: a seeded model's residual stream
    grows with depth and turns away from any direction fixed beforehand, so a
    gate drawn along the embedding's own direction reads ``W_g u`` near 0 (``log
    gamma`` -0.69) from the third layer on; along each layer's own mean input
    it reads what was asked for at every depth.  ``draw`` also writes what it
    returns to the file."""
    _forward(model_path, prompts, [0], regate=draw)


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def _sizes(cfg: dict) -> dict:
    dim, hid = cfg["hidden_size"], cfg["intermediate_size"]
    dh, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    kv = hkv * dh
    return dict(att=2 * dim * dim + 2 * dim * kv, gate=hkv * dim,
                ffn=3 * dim * hid, head=cfg["vocab_size"] * dim,
                layers=cfg["num_hidden_layers"], dh=dh, hkv=hkv,
                hq=cfg["num_attention_heads"],
                # the symmetric square of a head: the least any exact state holds
                d=dh * (dh + 1) // 2)


# positions of recent keys, values and gates a decoded row must read beside the
# state: the least a rewindable implementation keeps out of its state
# (``RECENT``), each a key and a value of ``dh`` and a float32 gate a kv head
def retention_bytes(cfg: dict, rows: float = 1, chips: int = 1,
                    elem_bytes: int = 2) -> float:
    """Bytes the retention operators of a step of ``rows`` decoded rows need,
    per chip, the least any exact implementation reads: per row and layer ONE
    read of the state, ``Hkv x dh (dh + 1) / 2 x (dh + 1)`` float32 values (the
    symmetric ``D``, whatever the program stores), plus the ring's ``RECENT``
    rows; no write of the state (a fold is amortised over a block of tokens and
    is not counted).  What ``serve_retention_roof_pct`` divides by the time
    under the parts ``state`` and ``recent``."""
    z = _sizes(cfg)
    state = z["hkv"] * z["d"] * (z["dh"] + 1) * 4
    recent = RECENT * z["hkv"] * (2 * z["dh"] * elem_bytes + 4)
    return z["layers"] * rows * (state + recent) / chips


def retention_flops(cfg: dict, rows: float = 1, chips: int = 1) -> float:
    """Multiply-adds x 2 of the same: each query head's ``phi(q)`` against its
    kv head's state and sum, and its scores and values over the ring's rows."""
    z = _sizes(cfg)
    state = z["hq"] * z["d"] * (z["dh"] + 1)
    recent = z["hq"] * RECENT * 2 * z["dh"]
    return 2.0 * z["layers"] * rows * (state + recent) / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Bytes of weights a step streams, per chip: every layer's four
    projections and its SwiGLU and the head as packed Q40 (18 B per 32
    values), the gate as float32."""
    z = _sizes(cfg)
    return (z["layers"] * ((z["att"] + z["ffn"]) * 18 / 32 + 4 * z["gate"])
            + z["head"] * 18 / 32) / chips


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """0: no layer keeps keys and values; a sequence's state is there whatever
    the context's depth."""
    return 0.0


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights once and each
    row's state and recent rows; nothing that grows with the context."""
    return weight_bytes(cfg, chips, rows) + retention_bytes(cfg, max(rows, 1), chips)


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    the projections, the gate, the SwiGLU and the head, and reads its state."""
    z = _sizes(cfg)
    mat = z["layers"] * (z["att"] + z["gate"] + z["ffn"]) + z["head"]
    return 2.0 * mat * rows / chips + retention_flops(cfg, rows, chips)
