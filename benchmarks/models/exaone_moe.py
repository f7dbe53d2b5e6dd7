"""The K-EXAONE decoder (``model_type`` ``exaone_moe``) as the yardstick knows
it (``harness/models.py`` has the interface): the block the program loads as
``ARCH_EXAONE_MOE`` (0xABCD06), GIVEN THE SAME SHARE of an expert-parallel
deployment as the program: a router over all ``E`` experts, the ``held``
experts' part of the routed sum, the shared expert, the stated rows of the
vocabulary.

Architecture (LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``), the equations
``last_logits`` follows; ``x`` is the residual stream entering layer ``l``::

    n1    = RMSNorm_att,l(x)
    q,k,v = W_q n1, W_k n1, W_v n1   n_heads x head_dim query columns (64 x 128 =
                                      8192, not hidden_size), no bias
    q, k  = RMSNorm_q,l(q), RMSNorm_k,l(k)   over each head's head_dim values, one
                                      weight vector of head_dim each a layer
    layer_types[l] == "sliding_attention" (l % 4 != 3):
                                      q, k = RoPE(q), RoPE(k), rotate-half lanes
                                      (j, j + head_dim/2), theta 1e6;
                                      key j visible to query p iff p - W < j <= p
    layer_types[l] == "full_attention" (l % 4 == 3):
                                      no rotation at all (NoPE);
                                      key j visible to query p iff j <= p
    h     = x + W_o Attn(q, k, v)    softmax, scale 1/sqrt(head_dim)
    n2    = RMSNorm_ffn,l(h)
    l < first_k_dense_replace:       x' = h + W_2(silu(W_1 n2) * W_3 n2)
    else: s   = sigmoid(W_router,l n2)        E scores (n_group 1: no grouping)
          S   = the k largest of s + b_l       b: e_score_correction_bias, for the
                                               choice only
          w_e = routed_scaling_factor * s_e / sum_{e' in S} s_e'   (norm_topk_prob)
          x'  = h + sum_{e in S, e held here} w_e Exp_e(n2) + Shared(n2)
    logits = W_cls RMSNorm_final(x_L)          the held rows of the vocabulary

``Exp_e`` and the one shared expert are SwiGLU of ``moe_intermediate_size``.
The normalisation runs over ALL k chosen experts, held here or not: the eight
shares' routed parts add up to the uncut layer.  RMSNorm eps ``rms_norm_eps``
(1e-5), untied head.

Departures from the published description, all of them:

* the configuration's four ``assumed`` conventions (pre-norm residuals, the
  per-head q/k norm before RoPE, unrotated full layers, a choice bias that is
  present and used for the choice only);
* the multi-token-prediction block (``num_nextn_predict_layers``) is left out:
  next-token logits do not depend on it;
* the router is read from its Q40 bytes, as the file stores every matrix,
  where the published model keeps it unquantised;
* the share: ``experts_held`` of the ``num_experts`` routed experts from
  ``first_expert``, the first ``num_hidden_layers`` layers, ``vocab_size`` rows
  of embedding and head (the configuration's ``deployment``).

File layout (``dllama_tpu/io/mfile.py tensor_plan`` for this arch id): in a
layer ``wq`` (heads x head_dim, dim), ``wk``, ``wv``, ``wo`` (dim, heads x
head_dim), ``q_norm`` and ``k_norm`` (head_dim; f32); a dense layer's ``w1``,
``w2``, ``w3``; an expert layer's ``moe_router`` (E, dim), ``moe_router_bias``
(E; f32), the held experts' ``up``, ``gate``, ``down`` (file index ``e`` is the
router's ``first_expert + e``), ``shared_w1``, ``shared_w2``, ``shared_w3``;
then the two block norms.  The header has the format's fourteen keys and
thirteen more (``EXT_KEYS``); ``mformat.pack_header`` / ``read_header`` stop
at key 13, so this module packs and reads its own.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from harness import mformat
from harness.mformat import F32, Q40

ARCH_EXAONE_MOE = 0xABCD06
ACT_SILU = 1
# toy widths for --rehearse; the 128 router outputs, the 16 held, the 8 a token,
# the period of four with its full layer last, the dense first layer and a head
# size that is not dim / n_heads stay; the window is shorter than the
# rehearsal's prompts
REHEARSE = dict(dim=256, hidden_dim=512, moe_hidden_dim=64, n_layers=8,
                n_heads=16, n_kv_heads=2, head_dim=32, vocab_size=2048,
                window=128, seq_len=6144)
# A position is margin-steady where its routing margin (``routing_margins``)
# exceeds this at every expert layer: SmallThinker's figure, whose reasoning
# holds here (23 expert layers at which to fall under the threshold; read
# against ``tools/check_routing.py``'s sweep on the chip, PERF.md section 6,
# PR 40).  A flip between two experts held elsewhere moves this share's logits
# only through the normalising sum.
MARGIN_STEADY = 0.005
# (key, name, is_float) of the header's pairs past the format's fourteen
EXT_KEYS = ((19, "moe_hidden_dim", False), (20, "n_shared_experts", False),
            (21, "n_groups", False), (22, "topk_groups", False),
            (23, "n_dense_layers", False), (24, "routed_scale", True),
            (31, "norm_eps", True), (32, "head_dim", False),
            (33, "window", False), (34, "window_period", False),
            (35, "experts_held", False), (36, "first_expert", False),
            (37, "window_full_at", False))
SHAPE_KEYS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
              "n_experts", "n_active_experts", "vocab_size", "seq_len",
              "rope_theta") + tuple(name for _, name, _ in EXT_KEYS)
# the reference scores this many query rows at a time
QUERY_BLOCK = 1024
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _program_has_the_arch() -> bool:
    """Whether this checkout's program knows arch id 0xABCD06 (its format
    module names it).  A text probe, not an import: the yardstick imports
    nothing of the program.  These files are also laid over checkouts older
    than the architecture (a new cell is tried on the parent commit first),
    which fail here, at once, before a 10.6 GB file is written for a loader
    that would refuse it."""
    try:
        with open(os.path.join(_ROOT, "dllama_tpu", "io", "mfile.py")) as f:
            return "0xabcd06" in f.read().lower()
    except OSError:
        return False


def _layout(config: dict) -> tuple[int, int]:
    """``(period, full_at)`` of the served layers' ``layer_types``: whole
    periods with one full layer, at the same place in each."""
    layers = config["num_hidden_layers"]
    kinds = [t == "full_attention" for t in config["layer_types"][:layers]]
    if len(kinds) != layers or True not in kinds:
        raise SystemExit("exaone_moe: layer_types does not cover the layers "
                         "with a full_attention layer among them")
    at = kinds.index(True)
    period = kinds[at + 1:].index(True) + 1 if True in kinds[at + 1:] else 0
    if period < 2 or layers % period or kinds != [
            j == at for j in range(period)] * (layers // period):
        raise SystemExit("exaone_moe: the layers are not whole periods of "
                         "window layers with one full layer")
    return period, at


def shape(config: dict) -> dict:
    """The ``.m`` header's sizes from the configuration's keys: the published
    ``config.json``'s, with ``num_experts`` the experts HELD (the router's width
    is ``published.num_experts``) and ``deployment.first_expert`` the first of
    them.  Refuses the settings the block above does not have (they would be
    computed silently wrong), and a checkout whose program lacks the arch id."""
    def no(why):
        raise SystemExit(f"exaone_moe: {why}")

    if not _program_has_the_arch():
        no("this checkout's program has no arch id 0xABCD06 (unknown "
           "architecture): it cannot load a K-EXAONE file")
    if config.get("scoring_func") != "sigmoid":
        no("scoring_func is not sigmoid: this block's router is")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        no("n_group / topk_group are not 1: this block chooses over all "
           "experts at once")
    if not config.get("norm_topk_prob", False):
        no("norm_topk_prob is false: this block's chosen weights are normalised")
    if config.get("num_nextn_predict_layers", 0):
        no("num_nextn_predict_layers is not 0: the multi-token-prediction "
           "block is not computed (the configuration leaves it out by name)")
    if config.get("tie_word_embeddings", False):
        no("the head is tied")
    if config.get("hidden_act") != "silu":
        no("hidden_act is not silu")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        no("rope_type is not default: this block's RoPE is unscaled")
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    if [m == "dense" for m in config["mlp_layer_types"][:layers]] != [
            i < dense for i in range(layers)]:
        no("mlp_layer_types is not first_k_dense_replace dense layers and "
           "then sparse ones")
    period, at = _layout(config)
    windows = config["sliding_windows"][:layers]
    window = config["sliding_window"]
    if windows != [0 if l % period == at else window for l in range(layers)]:
        no("sliding_windows is not sliding_window in the window layers and 0 "
           "in the full ones")
    published = config.get("published", {})
    deploy = config.get("deployment_share", {})
    shp = dict(dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
               n_layers=layers, n_heads=config["num_attention_heads"],
               n_kv_heads=config["num_key_value_heads"],
               n_experts=published.get("num_experts", config["num_experts"]),
               n_active_experts=config["num_experts_per_tok"],
               vocab_size=config["vocab_size"],
               seq_len=config["max_position_embeddings"],
               rope_theta=rope["rope_theta"],
               moe_hidden_dim=config["moe_intermediate_size"],
               n_shared_experts=config["num_shared_experts"], n_groups=1,
               topk_groups=1, n_dense_layers=dense,
               routed_scale=float(config["routed_scaling_factor"]),
               norm_eps=float(config["rms_norm_eps"]),
               head_dim=config["head_dim"], window=window,
               window_period=period, experts_held=config["num_experts"],
               first_expert=int(deploy.get("first_expert", 0)),
               window_full_at=at)
    if not 0 < shp["n_active_experts"] <= shp["n_experts"]:
        no("num_experts_per_tok is not in 1..experts")
    if shp["first_expert"] + shp["experts_held"] > shp["n_experts"]:
        no("the held experts are not a run of the router's")
    if shp["n_heads"] % shp["n_kv_heads"]:
        no("num_attention_heads is not a multiple of num_key_value_heads")
    if not 0 <= dense < layers:
        no("first_k_dense_replace leaves no expert layer")
    return shp


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header(shape: dict) -> bytes:
    vals = dict(shape, version=1, arch=ARCH_EXAONE_MOE, hidden_act=ACT_SILU,
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


def plan(shape: dict) -> list[tuple[str, tuple, int, int, int]]:
    """(name, shape, ftype, offset, nbytes) of every tensor, in file order."""
    dim, voc, dh = shape["dim"], shape["vocab_size"], shape["head_dim"]
    qw, kv = shape["n_heads"] * dh, shape["n_kv_heads"] * dh
    hid, f = shape["hidden_dim"], shape["moe_hidden_dim"]
    fs = f * shape["n_shared_experts"]
    names = [("token_embedding", (voc, dim), F32)]
    for i in range(shape["n_layers"]):
        p = f"layers.{i}."
        names += [(p + "wq", (qw, dim), Q40), (p + "wk", (kv, dim), Q40),
                  (p + "wv", (kv, dim), Q40), (p + "wo", (dim, qw), Q40),
                  (p + "q_norm", (dh,), F32), (p + "k_norm", (dh,), F32)]
        if i < shape["n_dense_layers"]:
            names += [(p + "w1", (hid, dim), Q40), (p + "w2", (dim, hid), Q40),
                      (p + "w3", (hid, dim), Q40)]
        else:
            names += [(p + "moe_router", (shape["n_experts"], dim), Q40),
                      (p + "moe_router_bias", (shape["n_experts"],), F32)]
            for e in range(shape["experts_held"]):
                q = f"{p}experts.{e}."
                names += [(q + "up", (f, dim), Q40), (q + "gate", (f, dim), Q40),
                          (q + "down", (dim, f), Q40)]
            names += [(p + "shared_w1", (fs, dim), Q40),
                      (p + "shared_w2", (dim, fs), Q40),
                      (p + "shared_w3", (fs, dim), Q40)]
        names += [(p + "rms_att", (dim,), F32), (p + "rms_ffn", (dim,), F32)]
    names += [("rms_final", (dim,), F32), ("wcls", (voc, dim), Q40)]
    return mformat.lay_out(names, len(header(shape)))


def _forward(model_path: str, prompts: list[list[int]], positions):
    """``(logits, margins)``: float32 logits ``(n, len(positions), vocab)`` at
    the token positions ``positions`` (``None``: every position) and the
    routing margin ``(n, T, expert layers)``: the gap between the last chosen
    expert's biased score and the first unchosen one's, over the standard
    deviation of the row's biased scores."""
    import jax
    import jax.numpy as jnp

    from harness import reference

    hd = read_header(model_path)
    w = reference.Tensors(model_path, plan({k: hd[k] for k in SHAPE_KEYS}))
    dim, hq, hkv, dh = hd["dim"], hd["n_heads"], hd["n_kv_heads"], hd["head_dim"]
    n_exp, k_act, eps = hd["n_experts"], hd["n_active_experts"], hd["norm_eps"]
    held, first, scale = hd["experts_held"], hd["first_expert"], hd["routed_scale"]
    window, period, at = hd["window"], hd["window_period"], hd["window_full_at"]
    toks = np.asarray(prompts, np.int32)
    t_len = toks.shape[1]

    def rms(x, g):
        return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rope(x, cos, sin):  # x (B, T, H, dh); halves
        x0, x1 = x[..., :dh // 2], x[..., dh // 2:]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)

    def make_attention(windowed: bool):
        @jax.jit
        def attention(x, wq, wk, wv, wo, g, gq, gk):
            b, t, _ = x.shape
            xb = rms(x, g)
            q = rms((xb @ wq.T).reshape(b, t, hq, dh), gq)   # each head's own
            k = rms((xb @ wk.T).reshape(b, t, hkv, dh), gk)
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
                lo_k = max(lo - window + 1, 0) if windowed else 0
                s = jnp.einsum("bthd,bshd->bhts", q[:, lo:hi], k[:, lo_k:hi]) / np.sqrt(dh)
                qi = jnp.arange(lo, hi)[:, None]
                kj = jnp.arange(lo_k, hi)[None, :]
                mask = kj <= qi
                if windowed:
                    mask = mask & (kj > qi - window)
                s = jnp.where(mask, s, -jnp.inf)
                outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                                       v[:, lo_k:hi]))
            att = jnp.concatenate(outs, 1)
            return x + att.reshape(b, t, hq * dh) @ wo.T
        return attention

    attend = {False: make_attention(False), True: make_attention(True)}

    @jax.jit
    def ffn_norm(x, g):
        return rms(x, g)

    @jax.jit
    def route(m, router, bias):
        """Each row's weight for every expert (B, T, E): scale * s_e over the
        sum of the k chosen scores, 0 for the others; and its margin."""
        s = jax.nn.sigmoid(m @ router.T)
        biased = s + bias
        top, idx = jax.lax.top_k(biased, k_act + 1)
        margin = (top[..., k_act - 1] - top[..., k_act]) / jnp.std(biased, -1)
        chosen = jnp.sum(jax.nn.one_hot(idx[..., :k_act], n_exp), -2)
        picked = s * chosen
        return scale * picked / jnp.sum(picked, -1, keepdims=True), margin

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
        ones = jnp.ones(toks.shape, jnp.float32)
        for i in range(hd["n_layers"]):
            p = f"layers.{i}."
            x = attend[i % period != at](
                x, w.weight(p + "wq"), w.weight(p + "wk"), w.weight(p + "wv"),
                w.weight(p + "wo"), w.vec(p + "rms_att"), w.vec(p + "q_norm"),
                w.vec(p + "k_norm"))
            m = ffn_norm(x, w.vec(p + "rms_ffn"))
            if i < hd["n_dense_layers"]:
                x = swiglu(x, m, ones, w.weight(p + "w3"), w.weight(p + "w1"),
                           w.weight(p + "w2"))
                continue
            shares, margin = route(m, w.weight(p + "moe_router"),
                                   w.vec(p + "moe_router_bias"))
            margins.append(np.asarray(margin, np.float32))
            for e in range(held):  # the held experts' part of the routed sum
                q = f"{p}experts.{e}."
                x = swiglu(x, m, shares[..., first + e], w.weight(q + "up"),
                           w.weight(q + "gate"), w.weight(q + "down"))
            if hd["n_shared_experts"]:
                x = swiglu(x, m, ones, w.weight(p + "shared_w3"),
                           w.weight(p + "shared_w1"), w.weight(p + "shared_w2"))
        if positions is not None:
            x = x[:, np.asarray(positions)]
        logits = head(x, w.vec("rms_final"), w.weight("wcls"))
        return np.asarray(logits, np.float32), np.stack(margins, -1)


def last_logits(model_path: str, prompts: list[list[int]]) -> np.ndarray:
    """Float32 logits ``(len(prompts), vocab)`` after each prompt's last
    token.  All prompts have one length.

    The plain reference: float32 at matmul precision ``highest``, no kernels,
    no cache, no ring, no pages, weights read from the same ``.m`` file the
    server loads, one tensor at a time; every held expert runs over every row
    and a row's unchosen experts get weight 0; the window is a mask over the
    whole sequence (query rows in blocks of ``QUERY_BLOCK``, which changes what
    is held at once and not what is computed).  It is given the share the file
    states (the module's docstring lists it with the other departures)."""
    return _forward(model_path, prompts, [len(prompts[0]) - 1])[0][:, 0]


def logits_at(model_path: str, prompts: list[list[int]], positions) -> np.ndarray:
    """``(n, len(positions), vocab)`` of the same reference in one pass: the
    logits after the tokens at ``positions`` (``tools/check_window.py``,
    ``tools/check_paged_window.py``: the mask is causal, so position ``j``'s
    are ``last_logits`` of the prompt cut after token ``j``)."""
    return _forward(model_path, prompts, list(positions))[0]


def routing_margins(model_path: str, prompts: list[list[int]]):
    """``(logits (n, T, vocab), margins (n, T, expert layers))`` of the same
    reference in one pass over every position, for ``tools/check_routing.py``
    and the CPU tests."""
    return _forward(model_path, prompts, None)


# ---- what a decode step needs (``harness/cost.py`` and the readers) -----------

def _sizes(cfg: dict) -> dict:
    """Values of a layer's attention matrices, its router, one expert, the
    shared expert, the dense FFN, the head; and the counts."""
    dim, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    router = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    dense = cfg["first_k_dense_replace"]
    return dict(att=2 * dim * qw + 2 * dim * kv, router=router * dim,
                expert=3 * dim * f, shared=3 * dim * f * cfg["num_shared_experts"],
                dense=3 * dim * cfg["intermediate_size"],
                head=cfg["vocab_size"] * dim, layers=cfg["num_hidden_layers"],
                n_dense=dense, n_moe=cfg["num_hidden_layers"] - dense,
                n_router=router, held=cfg["num_experts"],
                k=cfg["num_experts_per_tok"])


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct HELD experts a layer reads in a step of ``rows`` rows, each row
    taking k of the router's E under uniform, independent routing: ``held (1 -
    (1 - k/E)^rows)``: 10.3 of 16 at 16 rows."""
    z = _sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["n_router"]) ** rows)


def moe_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes the expert layers of a step of ``rows`` rows need, per
    chip: every expert layer's router, the held experts its rows hit and the
    shared expert (all under scope ``moe``; what ``serve_moe_roof_pct`` divides
    by that scope's time)."""
    z = _sizes(cfg)
    return z["n_moe"] * (z["router"] + experts_read(cfg, rows) * z["expert"]
                         + z["shared"]) * 18 / 32 / chips


def weight_bytes(cfg: dict, chips: int = 1, rows: float = 1) -> float:
    """Packed Q40 bytes (18 per 32 values) a step of ``rows`` rows streams, per
    chip: attention, the dense layer and the head once, and what the expert
    layers need."""
    z = _sizes(cfg)
    return ((z["layers"] * z["att"] + z["n_dense"] * z["dense"] + z["head"])
            * 18 / 32 / chips + moe_bytes(cfg, chips, rows))


def layer_kinds(cfg: dict) -> tuple[int, int]:
    """(full layers, window layers) among the served layers."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(t == "full_attention" for t in kinds)
    return full, len(kinds) - full


def kv_bytes_per_token(cfg: dict, chips: int = 1, elem_bytes: int = 2) -> float:
    """Bytes of K and V one more cached position adds, per chip: the full
    layers' (a window layer's ring is there whatever the context's depth)."""
    return (2 * layer_kinds(cfg)[0] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * elem_bytes / chips)


def kv_read_bytes(cfg: dict, context: float, chips: int = 1,
                  elem_bytes: int = 2, rows: float = 1) -> float:
    """Bytes of live keys and values ``rows`` decoded tokens, each at
    ``context`` positions, must read: all of them in a full layer, the last
    ``sliding_window`` in a window layer.  What ``serve_attn_kv_roof_pct``
    divides by the time under scope ``attn`` a step."""
    full, win = layer_kinds(cfg)
    positions = full * context + win * min(context, cfg["sliding_window"])
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * elem_bytes
            * positions * rows / chips)


def step_bytes(cfg: dict, live_context_tokens: float, chips: int = 1,
               rows: float = 1) -> float:
    """HBM bytes one decode step needs per chip: the weights its rows hit once,
    plus the live context each row may see (``live_context_tokens`` summed
    over rows; a row's share of it is cut to the window in the window
    layers)."""
    ctx = live_context_tokens / max(rows, 1)
    return (weight_bytes(cfg, chips, rows)
            + kv_read_bytes(cfg, ctx, chips, rows=max(rows, 1)))


def step_flops(cfg: dict, rows: float, live_context_tokens: float,
               chips: int = 1) -> float:
    """Multiply-adds x 2 of one decode step per chip: a row multiplies through
    attention, the dense layer, the router, its share of its k experts (k x
    held / E of them live here), the shared expert and the head, and scores the
    keys it may see."""
    z = _sizes(cfg)
    here = z["k"] * z["held"] / z["n_router"]
    mat = (z["layers"] * z["att"] + z["n_dense"] * z["dense"] + z["head"]
           + z["n_moe"] * (z["router"] + here * z["expert"] + z["shared"]))
    full, win = layer_kinds(cfg)
    ctx = live_context_tokens / max(rows, 1)
    seen = (full * ctx + win * min(ctx, cfg["sliding_window"])) * max(rows, 1)
    scores = 2 * cfg["num_attention_heads"] * cfg["head_dim"]  # q.k and p.v
    return 2.0 * (mat * rows + scores * seen) / chips
