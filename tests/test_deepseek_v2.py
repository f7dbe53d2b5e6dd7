"""``ARCH_DEEPSEEK2`` (0xABCD04), DeepSeek-V2 at a toy size that keeps every
ratio (a dense prefix layer and two expert layers, 32 experts in 8 groups of
which 3 are kept, top-6, two shared experts, a rotated part of 8 of a head's
24, YaRN past its original 16 positions): the format, the program against the
plain reference of ``tests/reference_impl.py`` (logits, not tokens) in both
cache forms, the two forms of the attention against each other, the choice of
experts, every strategy of ``moe_ffn``, the YaRN frequencies, the page's bytes,
the refusals, the tracing names, and the proof that the older architectures'
programs did not move.  The converter's cases are in ``tests/test_converter.py``.
"""

import hashlib
import io
import math
import re
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_impl as ref
from dllama_tpu import quants
from dllama_tpu.io import mfile
from dllama_tpu.io.integrity import ArtifactError
from dllama_tpu.models.config import ModelConfig, tiny_config, tiny_deepseek2
from dllama_tpu.models.params import (init_params, load_params, param_shapes,
                                      quantize_matmuls)
from dllama_tpu.models.transformer import (forward, forward_slots,
                                           forward_slots_all, init_kv_cache,
                                           init_kv_pool, moe_ffn)
from dllama_tpu.obs import cost as obs_cost, dispatch as obs_dispatch
from dllama_tpu.ops import mla, q40
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.runtime.engine import Engine
from dllama_tpu.runtime.scheduler import SlotScheduler

CFG = tiny_deepseek2()
TOKS = np.array([3, 17, 42, 99, 7, 64, 5, 23, 81, 11, 90, 2, 55, 31, 77, 8, 19,
                 100, 43, 12], np.int32)
TOKS2 = TOKS[::-1].copy()
# float32 on both sides at matmul precision "highest": what is left is the
# order of float32 sums (the program's online softmax and einsums against
# numpy's loops), a few 1e-6 of logits whose spread is 0.6
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=5, scale=0.08)


@pytest.fixture(scope="module")
def want(params):
    p = {k: np.asarray(v) for k, v in params.items()}
    return {"a": ref.np_forward_deepseek2(p, CFG, TOKS),
            "b": ref.np_forward_deepseek2(p, CFG, TOKS2), "np": p}


@pytest.fixture(autouse=True)
def _highest(request):
    """float32 products on both sides; the jaxpr hashes are of the programs as
    they are traced with no precision set."""
    if "programs_are_the_parents" in request.node.name:
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _spec(cfg=CFG, ftype=quants.F32):
    return mfile.ModelSpec(
        arch=cfg.arch, dim=cfg.dim, hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        n_experts=cfg.n_experts, n_active_experts=cfg.n_active_experts,
        vocab_size=cfg.vocab_size, seq_len=cfg.seq_len, weights_ftype=ftype,
        **{name: getattr(cfg, name) for _, name, _ in mfile.EXT_KEYS})


def _write_model(path, params_np, cfg=CFG, ftype=quants.F32):
    """The runtime-layout ``params_np`` as a ``.m`` file: each plan tensor is
    the stack's slice, transposed back to the file's (d_out, n_in)."""
    ld = cfg.n_dense_layers
    with mfile.MFileWriter(path, _spec(cfg, ftype)) as w:
        for t in w.plan:
            parts = t.name.split(".")
            if parts[0] != "layers":
                key = {"token_embedding": "embedding"}.get(t.name, t.name)
                x = params_np[key]
                x = x.T if t.name == "wcls" else x
            else:
                i, leaf = int(parts[1]), parts[-1]
                key = "router" if leaf == "moe_router" else leaf
                seg = i if key in ("w1", "w2", "w3") or key not in (
                    "router", "up", "gate", "down", "shared_w1", "shared_w2",
                    "shared_w3") else i - ld
                x = params_np[key][seg]
                if parts[2] == "experts":
                    x = x[int(parts[3])]
                x = x.T if x.ndim == 2 else x
            w.write_tensor(t.name, np.ascontiguousarray(x, np.float32))


# ---- the format ----------------------------------------------------------

def test_arch_id_header_keys_and_round_trip(tmp_path):
    assert mfile.ARCH_DEEPSEEK2 == 0xABCD04
    assert mfile.ARCH_NAMES[mfile.ARCH_DEEPSEEK2] == "deepseek2"
    assert [k for k, _, _ in mfile.EXT_KEYS] == list(range(14, 32))
    buf = io.BytesIO()
    n = mfile.write_header(buf, _spec())
    assert n == 8 + 8 * 32  # the reference's fourteen pairs and eighteen more
    path = tmp_path / "m.m"
    _write_model(path, {k: np.asarray(v) for k, v in init_params(CFG, 1).items()})
    spec = mfile.read_spec(path)
    for _, name, is_float in mfile.EXT_KEYS:
        got, put = getattr(spec, name), getattr(CFG, name)
        assert got == (np.float32(put) if is_float else put), name
    cfg = ModelConfig.from_spec(spec)
    assert (cfg.is_mla, cfg.latent_dim, cfg.n_moe_layers, cfg.expert_dim) == \
        (True, 40, 2, 32)
    assert cfg.norm_eps == np.float32(1e-6) and cfg.routed_scale == 16.0


def test_older_archs_keep_the_fourteen_keys():
    buf = io.BytesIO()
    spec = mfile.ModelSpec(arch=mfile.ARCH_OLMOE, dim=64, hidden_dim=32,
                           n_layers=2, n_heads=4, n_kv_heads=2, n_experts=4,
                           n_active_experts=2, vocab_size=96, seq_len=32)
    assert mfile.write_header(buf, spec) == 120


@pytest.mark.parametrize("patch,says", [
    ({mfile.KEY_MAX + 1: 1}, "unsupported .m header key"),
    ({35: 1}, "keys 35..37 describe an exaone_moe file"),
    ({15: 0}, "a deepseek2 file states this size"),
    ({17: 7}, "RoPE rotates pairs"),
    ({21: 5}, "experts not divisible into groups"),
    ({22: 9}, "more groups kept than groups"),
    ({8: 13}, "more experts a token than the kept groups hold"),
    ({23: 4}, "more dense layers than layers"),
    ({6: 2}, "one latent for all heads"),
    ({1: mfile.ARCH_OLMOE}, "describe a deepseek2 file"),
])
def test_header_refusals(tmp_path, patch, says):
    buf = io.BytesIO()
    mfile.write_header(buf, _spec())
    raw = bytearray(buf.getvalue())
    pairs = dict(zip(*[iter(struct.unpack(f"<{(len(raw) - 8) // 4}i", raw[8:]))] * 2))
    pairs.update(patch)
    body = b"".join(struct.pack("<ii", k, v) for k, v in pairs.items())
    path = tmp_path / "bad.m"
    path.write_bytes(struct.pack("<ii", mfile.MAGIC_V2, 8 + len(body)) + body)
    with pytest.raises(ArtifactError, match=says):
        mfile.read_spec(path)


def test_plan_has_a_dense_layer_then_expert_layers_and_kv_b_whole():
    spec = _spec()
    spec.header_size = 264
    plan = mfile.tensor_plan(spec)
    names = [t.name for t in plan]
    by = {t.name: t for t in plan}
    l0 = [n.split(".", 2)[2] for n in names if n.startswith("layers.0.")]
    assert l0 == ["wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
                  "wo", "w1", "w2", "w3", "rms_att", "rms_ffn"]
    l1 = [n.split(".", 2)[2] for n in names if n.startswith("layers.1.")]
    assert l1[:8] == ["wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
                      "wo", "moe_router"]
    assert l1[8:11] == ["experts.0.up", "experts.0.gate", "experts.0.down"]
    assert l1[-5:] == ["shared_w1", "shared_w2", "shared_w3", "rms_att", "rms_ffn"]
    assert by["layers.1.wkv_b"].shape == (4 * (16 + 16), 32)
    assert by["layers.1.wkv_a"].shape == (32 + 8, 64)
    assert by["layers.1.experts.3.up"].shape == (32, 64)
    assert by["layers.1.shared_w1"].shape == (2 * 32, 64)
    assert by["layers.0.w1"].shape == (96, 64)
    shapes = param_shapes(CFG)
    assert shapes["w1"][0] == 1 and shapes["up"][:2] == (2, 32)
    assert shapes["wq_a"][0] == shapes["wkv_b"][0] == 3


# ---- the program against the plain reference ----------------------------------

def test_contiguous_prefill_then_decode_through_the_cache(params, want):
    cache = init_kv_cache(CFG, 1)
    assert cache.k.shape == (3, 1, 64, 32) and cache.v.shape == (3, 1, 64, 8)
    assert cache.latent and not init_kv_cache(tiny_config(), 1).latent
    lg, cache = forward(params, CFG, jnp.asarray(TOKS[:12])[None], cache,
                        jnp.int32(0))
    errs = [np.abs(np.asarray(lg)[0] - want["a"][:12]).max()]
    for i in range(12, 20):
        lg, cache = forward(params, CFG, jnp.asarray(TOKS[i:i + 1])[None], cache,
                            jnp.int32(i))
        errs.append(np.abs(np.asarray(lg)[0, 0] - want["a"][i]).max())
    assert max(errs) < TOL, errs


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_slot_path_chunked_prefill_mixed_step_pure_decode(params, want, paged):
    """Two rows with clocks of their own: chunks of 4 (row b's first chunk has
    2 real tokens), then a mixed step (row a decodes one token while row b
    prefills a chunk), then pure-decode steps.  Pages out of order."""
    if paged:
        cache = init_kv_pool(CFG, 20, 4)
        assert cache.k.shape == (3, 20, 4, 32) and cache.v.shape == (3, 20, 4, 8)
        table = jnp.asarray(np.array([[3, 7, 1, 9, 12, 2, 0, 0],
                                      [5, 4, 8, 6, 10, 11, 0, 0]], np.int32))
    else:
        cache, table = init_kv_cache(CFG, 2, 32), None
    srcs, wants = (TOKS, TOKS2), (want["a"], want["b"])
    done, errs = [0, 0], []

    def step(n_valid, t):
        nonlocal cache
        tk = np.zeros((2, t), np.int32)
        for r in range(2):
            tk[r, :n_valid[r]] = srcs[r][done[r]:done[r] + n_valid[r]]
        lg, cache = forward_slots(
            params, CFG, jnp.asarray(tk), cache, jnp.asarray(done, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), table)
        for r in range(2):
            done[r] += n_valid[r]
            errs.append(np.abs(np.asarray(lg)[r] - wants[r][done[r] - 1]).max())

    for nv in ([4, 2], [4, 4], [4, 4]):
        step(nv, 4)
    step([1, 4], 4)            # mixed: a decodes, b prefills
    for _ in range(6):
        step([1, 1], 1)        # pure decode
    assert done == [19, 20]
    assert max(errs) < TOL, errs


def test_verify_window_keeps_every_position(params, want):
    """``forward_slots_all`` (the speculative verify step) over a window of 5
    on a pool: every position's logits are the reference's, so a verify step
    needs nothing this architecture lacks."""
    pool = init_kv_pool(CFG, 12, 4)
    table = jnp.asarray(np.array([[3, 7, 1, 9, 0, 0]], np.int32))
    _, pool = forward_slots(params, CFG, jnp.asarray(TOKS[None, :8]), pool,
                            jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
                            table)
    lg, _ = forward_slots_all(params, CFG, jnp.asarray(TOKS[None, 8:13]), pool,
                              jnp.full((1,), 8, jnp.int32),
                              jnp.full((1,), 5, jnp.int32), table)
    assert np.abs(np.asarray(lg)[0] - want["a"][8:13]).max() < TOL


def test_absorbed_and_expanded_forms_agree(params, want, monkeypatch):
    """The same prompt through ``mla-expanded`` (T at or over EXPAND_MIN_T,
    lowered here) and ``mla-absorbed``: each against the reference, a
    continuation chunk over a cache the other form wrote, and the ledger
    names the form of each compiled call site."""
    def run(min_t):
        monkeypatch.setattr(mla, "EXPAND_MIN_T", min_t)
        before = obs_dispatch.dispatches()
        cache = init_kv_cache(CFG, 1)
        a, cache = forward(params, CFG, jnp.asarray(TOKS[None, :12]), cache,
                           jnp.int32(0))
        monkeypatch.setattr(mla, "EXPAND_MIN_T", 128 if min_t == 4 else 4)
        b, _ = forward(params, CFG, jnp.asarray(TOKS[None, 12:]), cache,
                       jnp.int32(12))
        after = obs_dispatch.dispatches()
        new = {k for k in after if k.startswith("attn/mla")
               and after[k] > before.get(k, 0)}
        return np.concatenate([np.asarray(a)[0], np.asarray(b)[0]]), new

    expanded_first, seen1 = run(4)
    absorbed_first, seen2 = run(128)
    assert seen1 == seen2 == {"attn/mla-absorbed", "attn/mla-expanded"}
    assert np.abs(expanded_first - want["a"]).max() < TOL
    assert np.abs(absorbed_first - want["a"]).max() < TOL
    assert np.abs(expanded_first - absorbed_first).max() < TOL


@pytest.mark.parametrize("left_out", ["mscale", "yarn", "groups", "scale", "shared"])
def test_leaving_a_piece_of_the_mathematics_out_is_seen(want, left_out):
    """The reference with one piece removed differs from the whole by far more
    than ``TOL``: a program that dropped the group stage, the x16, the shared
    expert, ``mscale^2`` or the YaRN blend would fail the tests above."""
    got = ref.np_forward_deepseek2(want["np"], CFG, TOKS, **{left_out: False})
    assert np.abs(got - want["a"]).max() > 1000 * TOL


# ---- the choice of experts -----------------------------------------------------

def test_grouped_choice_where_the_flat_top6_would_choose_otherwise():
    """Hand-worked: 32 experts, 8 groups of 4.  Group 0 holds the four largest
    probabilities' runners-up but its best is below three other groups' bests,
    so the flat top-6 takes experts of group 0 and the grouped choice none."""
    p = np.full(32, 0.001)
    p[[4, 8, 12]] = [0.20, 0.19, 0.18]          # the bests of groups 1, 2, 3
    p[[0, 1, 2, 3]] = [0.10, 0.09, 0.08, 0.07]  # group 0: best 0.10
    p[[5, 9, 13]] = [0.02, 0.015, 0.012]
    p = p / p.sum()
    flat = set(np.argsort(-p)[:6])
    idx, w = ref.grouped_choice(p, 8, 3, 6)
    assert flat == {4, 8, 12, 0, 1, 2}
    assert set(idx) == {4, 8, 12, 5, 9, 13} and set(idx).isdisjoint({0, 1, 2, 3})
    np.testing.assert_allclose(w, p[idx])
    # the program, on logits that softmax to exactly this p, with experts
    # whose output names them: expert e returns the unit vector e
    cfg = CFG.with_(n_layers=1, n_dense_layers=0)
    x = np.zeros((1, 64), np.float32)
    x[0, :32], x[0, 63] = np.log(p), 1.0
    gate = np.zeros((32, 64, 32), np.float32)
    gate[:, 63, 0] = 1.0                     # h = silu(1) * 1 in column 0
    down = np.zeros((32, 32, 64), np.float32)
    down[np.arange(32), 0, np.arange(32)] = 1.0 / float(ref.silu(np.float32(1.0)))
    lp = {"router": jnp.asarray(np.eye(64, 32), jnp.float32),
          "up": jnp.asarray(gate), "gate": jnp.asarray(gate), "down": jnp.asarray(down)}
    got = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg))[0]
    wanted = np.zeros(64, np.float32)
    wanted[idx] = 16.0 * p[idx]              # the chosen p, scaled, not renormalised
    np.testing.assert_allclose(got, wanted, rtol=1e-5, atol=1e-7)
    assert not got[[0, 1, 2, 3]].any()


def _moe_case(experts, groups, kept, k, packed, seed=7):
    cfg = tiny_deepseek2(n_layers=1, n_dense_layers=0, n_experts=experts,
                         n_groups=groups, topk_groups=kept, n_active_experts=k)
    p = init_params(cfg, seed=seed, scale=0.2)
    lp_np = {key: np.asarray(p[key][0], np.float32) for key in
             ("router", "up", "gate", "down", "shared_w1", "shared_w2", "shared_w3")}
    if packed:
        # the reference sees the weights the packed tensors hold
        for key in lp_np:
            if key != "router":
                lp_np[key] = np.asarray(q40.dequantize(q40.quantize(lp_np[key])))
        qp = quantize_matmuls(p, cfg)
        lp = {key: (q40.QLayerView(qp[key], jnp.int32(0))
                    if isinstance(qp[key], q40.QTensor) else qp[key][0])
              for key in qp if key in ("router", "up", "gate", "down",
                                       "shared_w13", "shared_w2")}
    else:
        lp = {key: jnp.asarray(v) for key, v in lp_np.items()}
    return cfg, lp, lp_np


@pytest.mark.parametrize("rows,experts,groups,kept,k,packed,impl,path", [
    (2, 32, 8, 3, 6, True, "xla", "select"),
    (1, 32, 8, 3, 6, True, "pallas_interpret", "select-chosen"),
    (4, 32, 8, 3, 6, True, "pallas_interpret", "select-chosen"),
    (2, 32, 8, 3, 6, False, "xla", "select"),
    (6, 32, 8, 3, 6, True, "pallas_interpret", "all-experts"),
    (6, 32, 8, 3, 6, True, "xla", "scan"),
    (6, 8, 4, 2, 3, True, "xla", "unrolled"),
    (6, 32, 8, 3, 6, False, "xla", "dense"),
], ids=lambda v: str(v))
def test_every_moe_strategy_with_groups_scale_and_shared_expert(
        rows, experts, groups, kept, k, packed, impl, path):
    """Each strategy of ``moe_ffn`` against the reference's loop, with the
    grouped choice, the x16 and the shared expert.  Packed cases compare at
    the weights the Q40 tensors hold, so what is left is bf16 rounding of
    activations inside the Q40 matmul (2^-8 relative, summed over 6 experts
    scaled by 16): held to 3% of the output's spread; dense cases to 1e-5."""
    cfg, lp, lp_np = _moe_case(experts, groups, kept, k, packed)
    cfg = cfg.with_(quant_impl=impl)
    x = np.random.RandomState(3).randn(rows, 64).astype(np.float32)
    wanted = ref.deepseek2_moe(x, lp_np, cfg, ref.silu)
    before = obs_dispatch.dispatches()
    got = np.asarray(moe_ffn(jnp.asarray(x), lp, cfg))
    after = obs_dispatch.dispatches()
    assert {key for key in after if key.startswith("moe/")
            and after[key] > before.get(key, 0)} == {"moe/" + path}
    tol = 0.03 * wanted.std() if packed else 1e-5 * max(1.0, np.abs(wanted).max())
    assert np.abs(got - wanted).max() < tol
    for gone in ("groups", "scale", "shared"):
        less = ref.deepseek2_moe(x, lp_np, cfg, ref.silu, **{gone: False})
        assert np.abs(less - wanted).max() > 10 * tol, gone


# ---- YaRN ----------------------------------------------------------------------

def test_yarn_angles_against_the_published_formula_past_4096():
    """DeepSeek-V2's own numbers (rope 64, theta 1e4, factor 40, original 4096,
    beta 32 / 1, mscale = mscale_all_dim = 0.707) at positions past 4096,
    against the formula written out here."""
    cfg = CFG.with_(qk_rope_head_dim=64, rope_orig_seq_len=4096)
    i = np.arange(32, dtype=np.float64)
    extra = 1e4 ** (-2 * i / 64)
    inter = extra / 40

    def c(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))

    low, high = math.floor(c(32)), math.ceil(c(1))
    assert (low, high) == (10, 23)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = inter * (1 - m) + extra * m
    assert inv[0] == extra[0] and inv[31] == inter[31] and inter[15] < inv[15] < extra[15]
    np.testing.assert_allclose(
        mla.yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0), inv, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 1e4, 40.0, 4096, 32, 1), inv,
                               rtol=1e-12)
    pos = jnp.asarray([0, 4095, 4096, 5000, 100000, 163839])
    cos, sin = mla.rope_angles(pos, cfg)
    ang = np.asarray(pos, np.float64)[:, None] * inv.astype(np.float32)
    # float32 angles up to 1.6e5 radians: 2^-24 * 1.6e5 = 0.01 of a radian
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=0.02)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=0.02)
    # cos and sin times mscale(40, .707) / mscale(40, .707) = 1, and the
    # softmax scale carries mscale^2
    assert np.abs(np.asarray(cos)[0]).max() == 1.0
    ms = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.with_(qk_nope_head_dim=128).attn_scale == pytest.approx(
        192 ** -0.5 * ms * ms, rel=1e-12)
    assert tiny_deepseek2(rope_factor=1.0).attn_scale == 24 ** -0.5


# ---- the loader, the engine, the page -------------------------------------------

@pytest.fixture(scope="module")
def q40_file(tmp_path_factory, want):
    path = tmp_path_factory.mktemp("ds2") / "toy_q40.m"
    _write_model(path, want["np"], ftype=quants.Q40)
    return str(path)


def test_loader_fused_unfused_and_dense_agree_with_the_reference(q40_file):
    """One Q40 file three ways: packed and fused (``wqkv_a``, ``w13``,
    ``shared_w13``), packed and unfused, dequantized.  The reference runs on
    the dequantized stacks: the dense load is float32 against float32; the
    packed loads add bf16 rounding inside the Q40 matmuls (held to 5% of the
    logits' spread at this toy width, where one rounding is a large share)
    and are equal to each other: fusing changes launches, not sums."""
    mf = mfile.MFile(q40_file)
    cfg, dense = load_params(mf, dtype=jnp.float32)
    assert cfg.is_mla and cfg.n_dense_layers == 1
    wanted = ref.np_forward_deepseek2({k: np.asarray(v) for k, v in dense.items()},
                                      cfg, TOKS)
    out = {}
    for name, kw in (("dense", {}), ("fused", dict(keep_quantized=True)),
                     ("unfused", dict(keep_quantized=True, fuse=False))):
        _, p = load_params(mf, dtype=jnp.float32, **kw)
        lg, _ = forward(p, cfg.with_(quant_impl="xla"), jnp.asarray(TOKS)[None],
                        init_kv_cache(cfg, 1), jnp.int32(0))
        out[name] = (np.asarray(lg)[0], p)
    assert np.abs(out["dense"][0] - wanted).max() < TOL
    fused, unfused = out["fused"][1], out["unfused"][1]
    assert {"wqkv_a", "w13", "shared_w13"} <= set(fused) and "wq_a" not in fused
    assert {"wq_a", "wkv_a", "w1", "shared_w1"} <= set(unfused)
    assert isinstance(fused["wqkv_a"], q40.QTensor)
    assert fused["wqkv_a"].logical_nd == (64, 64 + 40)
    assert not isinstance(fused["wkv_b"], q40.QTensor)  # dequantized once
    assert fused["up"].qpacked.shape[:2] == (2, 32) and fused["w13"].qpacked.shape[0] == 1
    for name in ("fused", "unfused"):
        # a position's largest error over the logits' spread: 1-4% from bf16
        # activations in the Q40 matmuls; a position where that rounding
        # flips a near-tied expert reads 30% (benchmarks/models/deepseek_v2.py
        # routing_margins tells those apart on the chip), so: the median, and
        # at most two such positions of twenty
        worst = np.abs(out[name][0] - wanted).max(1) / wanted.std()
        assert np.median(worst) < 0.05 and (worst > 0.05).sum() <= 2, (name, worst)
    np.testing.assert_allclose(out["fused"][0], out["unfused"][0], atol=1e-5)


def test_engine_and_paged_scheduler_serve_the_file(q40_file):
    """The same ``Engine``, ``SlotScheduler`` and ``PagePool`` as the other
    arch ids: greedy tokens through the paged pool are the contiguous
    engine's, a cached token is layers x (32 + 8) x element size in both forms, in two
    planes with no head axis."""
    mf = mfile.MFile(q40_file)
    cfg, params = load_params(mf, dtype=jnp.float32, keep_quantized=True)
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    solo = Engine(cfg, params, mesh=mesh, batch=1)
    eng = Engine(cfg, params, mesh=mesh, batch=2,
                 kv_pages=2 * (cfg.seq_len // 4) + 1, kv_page_size=4)
    per_token = cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4
    assert solo.kv_bytes_per_token == eng.kv_bytes_per_token == per_token
    assert eng.cache.k.shape == (3, 33, 4, 32) and eng.cache.v.shape == (3, 33, 4, 8)
    bf16 = Engine(cfg, params, mesh=mesh, batch=1, kv_dtype=jnp.bfloat16)
    assert bf16.kv_bytes_per_token == per_token // 2
    p1, p2 = [5, 9, 2], [7, 3, 11, 4, 6, 1, 8]
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0, decode_burst=4)
    try:
        tickets = [sched.submit(p, 16, temperature=0.0) for p in (p1, p2)]
        outs = [list(t.tokens()) for t in tickets]
    finally:
        sched.close()
    for p, out in zip((p1, p2), outs):
        solo.reset()
        wanted = [t for t, _ in solo.generate_stream(
            p, len(p) + 16, temperature=0.0, chunk=5)][len(p):]
        assert out == wanted and len(out) == 16
    pages = eng.read_pool_pages([1, 2])
    assert {k: v.shape for k, v in pages.items()} == {
        "pages.k": (3, 2, 4, 32), "pages.v": (3, 2, 4, 8)}
    eng.write_pool_pages([5, 6], pages)
    back = eng.read_pool_pages([5, 6])
    for name in pages:
        np.testing.assert_array_equal(back[name], pages[name])
    model = obs_cost.model_from_engine(eng)
    assert model.kv_pos_bytes() == 40 * 4 and model.attn_path("decode") == "mla-absorbed"


def test_fingerprints_learn_the_page_from_the_cache(params):
    """A latent pool and a GQA pool of the same arch-independent sizes differ
    in both fingerprints by the page's named axes and shape, and a snapshot
    restores a latent cache."""
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(CFG, params, mesh=mesh, batch=2, kv_pages=9, kv_page_size=4)
    from dllama_tpu.runtime.engine import page_axes
    assert page_axes(eng.cache) == "ps,r|ps,rope"
    assert page_axes(init_kv_pool(tiny_config(), 4, 4)) == "ps,Hkv,Dh"
    assert set(eng._cache_arrays()) == {"cache.k", "cache.v"}
    assert [a.shape[-1] for a in eng._cache_arrays().values()] == [32, 8]
    assert len(eng.handoff_fingerprint()) == len(eng.config_fingerprint())
    other = Engine(CFG.with_(kv_lora_rank=32, qk_rope_head_dim=8), params,
                   mesh=mesh, batch=2, kv_pages=9, kv_page_size=8)
    assert other.handoff_fingerprint() != eng.handoff_fingerprint()


def test_snapshot_round_trip_of_a_latent_cache(params, tmp_path):
    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    eng = Engine(CFG, params, mesh=mesh, batch=1)
    first = [t for t, _ in eng.generate_stream([5, 9, 2], 9, temperature=0.0, chunk=3)]
    path = str(tmp_path / "e.snap")
    eng.snapshot(path)
    rest = [t for t, _ in eng.generate_stream([first[-1]], 6, temperature=0.0, chunk=3)]
    eng2 = Engine(CFG, params, mesh=mesh, batch=1)
    eng2.restore(path)
    for plane in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(eng2.cache, plane))[:, :, :8],
            np.asarray(getattr(eng.cache, plane))[:, :, :8])
    assert eng2.pos == 8
    again = [t for t, _ in eng2.generate_stream([first[-1]], 6, temperature=0.0, chunk=3)]
    assert again == rest  # the restored latent cache continues the stream


# ---- tracing -------------------------------------------------------------------

_OP_NAME = re.compile(r"op_name=\"([^\"]+)\"")


@pytest.mark.parametrize("t,min_t,form", [(1, 128, "absorbed"), (6, 4, "expanded")])
def test_mla_parts_are_named_under_the_scopes_the_yardstick_knows(
        params, monkeypatch, t, min_t, form):
    from dllama_tpu.ops.scopes import PARTS, SCOPES
    monkeypatch.setattr(mla, "EXPAND_MIN_T", min_t)
    # the last two: K-EXAONE's and a short-convolution layer's (LFM2)
    assert PARTS["qkv"][:4] == ("q_lora", "kv_lora", "qk_norm", "conv")
    assert PARTS["attn"][:3] == ("absorb", "latent", "expand")
    assert PARTS["moe"] == ("router", "experts", "combine", "shared")
    text = jax.jit(lambda p, tk, c: forward(p, CFG, tk, c, jnp.int32(0))).lower(
        params, jnp.zeros((1, t), jnp.int32), init_kv_cache(CFG, 1)
    ).compile().as_text()
    names = _OP_NAME.findall(text)

    def parts_under(scope):
        return {c for n in names if f"/{scope}/" in n
                for c in n.split(f"/{scope}/", 1)[1].split("/") if c in PARTS[scope]}

    assert parts_under("qkv") == {"q_lora", "kv_lora"}
    assert parts_under("attn") == ({"absorb", "latent"} if form == "absorbed"
                                   else {"expand"})
    assert "shared" in parts_under("moe")
    for n in names:  # a part never hides the scope it splits
        comps = n.split("/")
        for scope, parts in PARTS.items():
            for part_name in parts:
                if part_name in comps:
                    assert [c for c in comps if c in SCOPES][-1] in (
                        scope, "rope", "kv_write"), n


# ---- the older architectures did not move -------------------------------------

# sha256 of str(jax.make_jaxpr(...)) on the parent of the PR that added
# ARCH_DEEPSEEK2, for the Llama block (Mistral's and Yi's) and OLMoE's, dense and
# packed, through ``forward`` (t rows) and the paged ``forward_slots`` (2 x t)
PARENT_JAXPRS = {
    ('llama', False, 1, 'fwd'): '244ffea6f71b404f',
    ('llama', False, 1, 'slots'): '04999115c2827089',
    ('llama', False, 4, 'slots'): '20ca748bb4954515',
    ('llama', False, 7, 'fwd'): 'f81759e5c1ed7901',
    ('llama', True, 1, 'fwd'): '04a5e1308434b8a4',
    ('llama', True, 1, 'slots'): '919ef2bec29fefdd',
    ('llama', True, 4, 'slots'): '1c29a3bd0444df5f',
    ('llama', True, 7, 'fwd'): 'e934aef4ae92799a',
    ('olmoe', False, 1, 'fwd'): '0ee8e7147d39593d',
    ('olmoe', False, 1, 'slots'): '1c75f275ce0be4d8',
    ('olmoe', False, 4, 'slots'): '16196b6f4a08443f',
    ('olmoe', False, 7, 'fwd'): 'e0f27fc262df9da4',
    ('olmoe', True, 1, 'fwd'): '6fc8963ba1ec6953',
    ('olmoe', True, 1, 'slots'): '7e996c9a1246b7e8',
    ('olmoe', True, 4, 'slots'): '589870c5e4e32bbb',
    ('olmoe', True, 7, 'fwd'): 'c623c4e799750b2e',
}
OLDER = {"llama": dict(arch=mfile.ARCH_LLAMA),
         "olmoe": dict(arch=mfile.ARCH_OLMOE, n_experts=16, n_active_experts=4)}


@pytest.mark.parametrize("name,packed,t,entry", sorted(PARENT_JAXPRS),
                         ids=lambda v: str(v))
def test_llama_and_olmoe_programs_are_the_parents(name, packed, t, entry):
    cfg = tiny_config(**OLDER[name]).with_(quant_impl="xla")
    p = init_params(cfg, seed=3)
    if packed:
        p = quantize_matmuls(p, cfg)
    if entry == "fwd":
        jaxpr = jax.make_jaxpr(
            lambda p, tk, c: forward(p, cfg, tk, c, jnp.int32(0)))(
                p, jnp.zeros((1, t), jnp.int32), init_kv_cache(cfg, 1))
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, tk, c, pr, nv, pt: forward_slots(p, cfg, tk, c, pr, nv, pt))(
                p, jnp.zeros((2, t), jnp.int32), init_kv_pool(cfg, 9, 4),
                jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
                jnp.zeros((2, 4), jnp.int32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        PARENT_JAXPRS[(name, packed, t, entry)]


def test_cost_model_charges_two_layer_kinds_and_a_latent_cache():
    """DeepSeek-V2's published sizes at the benchmark's 5 layers: a token's
    matmul weights are the MLA projections of every layer, the dense FFN of
    the first and six routed plus two shared experts of the other four; a
    cached position is 576 values a layer, not 2 x 128 heads x 40."""
    m = obs_cost.CostModel(
        dim=5120, hidden_dim=12288, n_layers=5, n_heads=128, n_kv_heads=128,
        vocab_size=102400, weight_codec="q40", kv_codec="kv_bfloat16",
        kv_el_bytes=2, n_experts=160, n_active_experts=6, n_dense_layers=1,
        moe_hidden_dim=1536, n_shared_experts=2,
        mla=dict(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128))
    attn = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 128 * 128 * 5120)
    assert m.params_per_token == (5 * attn + 3 * 5120 * 12288
                                  + 4 * 8 * 3 * 5120 * 1536)
    assert m.kv_pos_bytes() == 576 * 2
    assert m.kv_write_bytes(1) == 5760
    assert m.attn_flops(99, 1) == 5 * 100 * 2 * 128 * (512 + 576)
    gqa = obs_cost.CostModel(dim=5120, hidden_dim=12288, n_layers=5, n_heads=128,
                             n_kv_heads=128, vocab_size=102400, kv_el_bytes=2)
    assert gqa.kv_pos_bytes() == 2 * 5120 * 2 and gqa.pair_flops == 4 * 5120
