"""Packed-Q40 on-device path: format, matmul impls, model + TP equivalence.

Mirrors the reference's kernel test strategy (funcs-test.cpp:18-60:
quantized matmul vs F32 matmul within tolerance on random data) plus the
N-shard ≡ 1-shard invariance pattern (commands-test.cpp:30-69)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu import quants
from dllama_tpu.ops import q40


def _rand(shape, seed=0, scale=0.1):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(np.float32)


# How close the kernel comes to its reference, as a share of max |ref|.  Above
# SLICED_MAX_ROWS rows a block (the dot body) a weight is rounded to bf16 as in
# the XLA path, which it equals up to summation order.  At one row (the grouped
# body, PR 50) and at 2 to SLICED_MAX_ROWS rows of a tile of whole vregs (the
# sliced body, PR 62) no weight is rounded: the reference is the float32
# dequantization, and the bound is the f32 sums', 20 times tighter.
DOT_TOL, GROUPED_TOL = 1e-4, 5e-6
R = q40.SLICED_MAX_ROWS


def _f32_reference(x, w) -> np.ndarray:
    """``x @ dequantize(w, float32)``, summed in float64."""
    dense = q40.dequantize(w.sliced() if isinstance(w, q40.QLayerView) else w)
    return np.asarray(x, np.float64) @ np.asarray(dense, np.float64)


def _reference(x, w, tiles=None):
    """The reference of the body that ``x``'s rows take (at the rule's tiles,
    or at ``tiles``) and its bound: ``(ref, tol)``."""
    qt = w.qt if isinstance(w, q40.QLayerView) else w
    tile_n = (tiles or q40._tiles(qt.qpacked.shape[-2] * 2, qt.logical_nd[1]))[0]
    if q40._body(x.shape[-2], tile_n) != "dot":
        return _f32_reference(x, w), GROUPED_TOL
    return np.asarray(q40.matmul(x, w, impl="xla", out_dtype=jnp.float32)), DOT_TOL


class TestFormat:
    def test_quantize_matches_reference_codec(self):
        """q40.quantize must produce the exact same values as the byte
        codec (quants.quantize_q40) — same clamp/floor/offset semantics."""
        w = _rand((64, 48))
        qt = q40.quantize(w)
        via_qt = np.asarray(q40.dequantize(qt))
        # reference codec path: quantize each *input-dim column* — blocks run
        # along axis 0 (input) in the runtime layout, so quantize the
        # transposed row-major view as the converter does per weight row
        via_codec = np.stack([
            quants.dequantize_q40(quants.quantize_q40(w[:, j]), 64)
            for j in range(48)], axis=1)
        np.testing.assert_allclose(via_qt, via_codec, rtol=0, atol=0)

    def test_from_q40_bytes_roundtrip(self):
        """File bytes for a (d_out, n_in) weight → QTensor ≡ dequantized."""
        d_out, n_in = 24, 96
        w = _rand((d_out, n_in), seed=3)
        raw = np.frombuffer(quants.quantize_q40(w), np.uint8)
        qt = q40.from_q40_bytes(raw, d_out, n_in)
        assert qt.shape == (n_in, d_out)
        expect = quants.dequantize_q40(raw, d_out * n_in).reshape(d_out, n_in).T
        np.testing.assert_allclose(np.asarray(q40.dequantize(qt)), expect,
                                   rtol=0, atol=0)

    def test_stacked_leading_dims(self):
        w = _rand((3, 64, 32), seed=1)
        qt = q40.quantize(w)
        assert qt.shape == (3, 64, 32)
        assert qt.qpacked.shape == (3, 32, 32)
        assert qt.scales.shape == (3, 2, 32)
        # per-layer slice == slice-then-quantize
        one = q40.quantize(w[1])
        np.testing.assert_array_equal(np.asarray(qt.qpacked[1]), np.asarray(one.qpacked))


class TestMatmul:
    def _setup(self, t=2, n=128, d=192, seed=0):
        w = _rand((n, d), seed)
        x = _rand((t, n), seed + 1, scale=1.0)
        qt = q40.quantize(w)
        ref = x @ np.asarray(q40.dequantize(qt))
        return x, qt, ref

    def test_xla_impl(self):
        x, qt, ref = self._setup()
        out = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="xla"))
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_pallas_interpret_matches_xla(self):
        """The fused kernel (interpret mode on CPU) ≡ the XLA emulation."""
        x, qt, ref = self._setup(t=1, n=2048, d=256)
        out_p = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="pallas_interpret"))
        np.testing.assert_allclose(out_p, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_kernel_matches_xla_flat_and_stacked(self):
        """The kernel computes the reference matmul within its rounding
        bound, flat and layer-indexed."""
        x, qt, ref = self._setup(t=1, n=1024, d=256)
        tol = 2e-2 * np.abs(ref).max()
        out = np.asarray(q40._pallas_matmul(
            jnp.asarray(x), qt.qpacked, qt.scales, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=0, atol=tol)
        w3 = _rand((2, 1024, 256), seed=6)
        qt3 = q40.quantize(w3)
        x3 = _rand((1, 1024), seed=7, scale=1.0)
        for l in range(2):
            out = np.asarray(q40._pallas_matmul_stacked(
                jnp.asarray(x3), qt3.qpacked, qt3.scales, jnp.int32(l),
                interpret=True))
            ref3 = x3 @ np.asarray(q40.dequantize(qt3))[l]
            np.testing.assert_allclose(out, ref3, rtol=0,
                                       atol=2e-2 * np.abs(ref3).max())

    def test_kernel_multirow_prefill_chunk(self):
        """Prefill-sized inputs (t=8 rows, under PALLAS_MAX_ROWS) — the
        multi-row path the auto dispatch uses for short prefills."""
        x, qt, ref = self._setup(t=8, n=2048, d=256)
        out = np.asarray(q40._pallas_matmul(
            jnp.asarray(x), qt.qpacked, qt.scales, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_pallas_interpret_ragged_d(self):
        """Output dim not divisible by the tile: ragged last tile masked."""
        x, qt, ref = self._setup(t=1, n=1024, d=1024 + 384)
        out_p = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="pallas_interpret"))
        assert np.all(np.isfinite(out_p))
        np.testing.assert_allclose(out_p, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_batched_x(self):
        x, qt, ref = self._setup(t=1)
        x3 = np.broadcast_to(x, (2, 1, 128)).copy()
        out = np.asarray(q40.matmul(jnp.asarray(x3), qt, impl="xla"))
        assert out.shape == (2, 1, 192)
        np.testing.assert_allclose(out[0], ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_mm_dense_passthrough(self):
        x = jnp.asarray(_rand((2, 8)))
        w = jnp.asarray(_rand((8, 4), seed=2))
        np.testing.assert_allclose(np.asarray(q40.mm(x, w)), np.asarray(x @ w),
                                   rtol=1e-6, atol=1e-6)

    def test_split_d_unfuse(self):
        """split_d (the tp>1 unfuse of wqkv/w13) ≡ quantizing the pieces."""
        w = _rand((2, 64, 96), seed=5)
        qt = q40.quantize(w)
        a, b = q40.split_d(qt, [64, 32])
        np.testing.assert_array_equal(
            np.asarray(q40.dequantize(a)), np.asarray(q40.dequantize(qt))[..., :64])
        np.testing.assert_array_equal(
            np.asarray(q40.dequantize(b)), np.asarray(q40.dequantize(qt))[..., 64:])
        assert a.logical_nd == (64, 64) and b.logical_nd == (64, 32)


class TestShardMap:
    """The fused kernel per-shard under shard_map (VERDICT r01 #2): the
    tp>1 production path must be the pallas kernel, not the XLA emulation.
    Interpret mode stands in for Mosaic on the CPU test mesh."""

    def _mesh(self, tp):
        from dllama_tpu.parallel.mesh import make_mesh
        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} devices")
        return make_mesh(tp=tp, devices=jax.devices()[:tp])

    def test_row_sharded_matmul(self):
        from dllama_tpu.parallel.mesh import active_mesh
        w = _rand((512, 256), seed=7)
        x = _rand((2, 512), seed=8, scale=1.0)
        qt = q40.quantize(w)
        ref = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="xla"))
        mesh = self._mesh(8)
        with active_mesh(mesh):
            out = np.asarray(q40.matmul(jnp.asarray(x), qt,
                                        impl="pallas_interpret", kind="row"))
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_col_sharded_matmul_psums_partials(self):
        from dllama_tpu.parallel.mesh import active_mesh
        w = _rand((512, 192), seed=9)
        x = _rand((2, 512), seed=10, scale=1.0)
        qt = q40.quantize(w)
        ref = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="xla"))
        mesh = self._mesh(8)
        with active_mesh(mesh):
            out = np.asarray(q40.matmul(jnp.asarray(x), qt,
                                        impl="pallas_interpret", kind="col"))
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_unshardable_falls_back_to_xla(self):
        """A weight whose blocks don't divide the mesh must still compute
        correctly (per-tensor XLA fallback, not an error)."""
        from dllama_tpu.parallel.mesh import active_mesh
        w = _rand((64, 48), seed=11)          # 2 blocks: not col-shardable over 8
        x = _rand((1, 64), seed=12, scale=1.0)
        qt = q40.quantize(w)
        ref = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="xla"))
        with active_mesh(self._mesh(8)):
            out = np.asarray(q40.matmul(jnp.asarray(x), qt,
                                        impl="pallas_interpret", kind="col"))
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-2 * np.abs(ref).max())

    def test_sp_mesh_keeps_fused_pallas_path(self):
        """On an sp>1, tp=1 mesh the fused wqkv/w13 stay fused and run the
        pallas kernel replicated under shard_map (no XLA downgrade)."""
        from dllama_tpu.models.config import tiny_config
        from dllama_tpu.models.params import init_params, quantize_matmuls
        from dllama_tpu.parallel.mesh import make_mesh
        from dllama_tpu.runtime.engine import Engine

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        cfg = tiny_config(dim=64, hidden_dim=96, n_layers=2, n_heads=4,
                          n_kv_heads=2, vocab_size=128, seq_len=64,
                          ).with_(quant_impl="pallas_interpret")
        params = quantize_matmuls(init_params(cfg, seed=3), cfg)
        e1 = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
        esp = Engine(cfg, params, mesh=make_mesh(tp=1, sp=2, devices=jax.devices()[:2]))
        assert "wqkv" in esp.params  # fused layout kept on a tp=1 mesh
        l1, _ = e1.prefill([5, 9, 2])
        lsp, _ = esp.prefill([5, 9, 2])
        np.testing.assert_allclose(l1, lsp, atol=1e-3 + 1e-3 * np.abs(l1).max(), rtol=0)

    def test_tp8_engine_pallas_matches_tp1(self):
        """End-to-end: a tp=8 engine on the pallas(-interpret) path produces
        the same logits and greedy tokens as tp=1 — the VERDICT r01 done-
        criterion for the fused kernel under tensor parallelism."""
        from dllama_tpu.models.config import tiny_config
        from dllama_tpu.models.params import init_params, quantize_matmuls
        from dllama_tpu.parallel.mesh import make_mesh
        from dllama_tpu.runtime.engine import Engine
        from dllama_tpu.sampling import Sampler

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        # shapes chosen to divide an 8-way mesh at Q40 block granularity
        cfg = tiny_config(dim=256, hidden_dim=256, n_layers=2, n_heads=8,
                          n_kv_heads=8, vocab_size=128, seq_len=64,
                          ).with_(quant_impl="pallas_interpret")
        # a toy shard's reduction tile (256 / 8 = 32 rows) keeps the dot body,
        # which rounds a weight to bf16, where the whole matrix's tile takes the
        # sliced body at the prompt's four rows, which rounds none (PR 62):
        # weights whose rounding is exact compute the same function on both
        from fixtures import bf16_exact_scales
        params = bf16_exact_scales(quantize_matmuls(init_params(cfg, seed=4), cfg))
        prompt = [3, 17, 29, 5]

        e1 = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
        e8 = Engine(cfg, params, mesh=make_mesh(tp=8))
        assert "wq" in e8.params and "wqkv" not in e8.params  # unfused for tp
        l1, _ = e1.prefill(prompt)
        l8, _ = e8.prefill(prompt)
        # no weight's rounding differs across tp configs, so the bound stays
        # tight
        np.testing.assert_allclose(l1, l8, atol=1e-3 + 1e-3 * np.abs(l1).max(), rtol=0)

        def greedy(engine):
            s = Sampler(cfg.vocab_size, 0.0, 0.9, 1)
            return [t for t, _ in engine.generate(prompt, 16, s)]

        t1 = greedy(Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1])))
        t8 = greedy(Engine(cfg, params, mesh=make_mesh(tp=8)))
        assert t1 == t8


def _parent_tiles(n, d):
    """The tile rule of PRs 28-33, kept to show which shapes it still
    decides: 1024 x 1024, the reduction tile shrunk down a power-of-two ladder
    to the first that divides, the output tile one of 1024 whatever d."""
    tile_n = n
    for tn in (1024, 512, 256, 128, 64, 32):
        if n % tn == 0:
            tile_n = tn
            break
    return tile_n, (min(1024, d) if d % 128 == 0 else 1024)


def _parent_padded_n(n):
    return n if n <= 1024 else -(-n // 1024) * 1024


# Every Q40 matmul of the benchmark's five cells: (configuration, matrix,
# input dim n, output dim d, tp slicing, the tile pair, the stored n).  n and d
# are the whole tensor's; Yi-34B runs at tp=4, where the kernel cuts one
# shard (q40._shard_nd).  Mistral's two cells share a configuration.
CELL_SHAPES = [
    ("mistral-7b", "wqkv", 4096, 6144, None, (1024, 1024), 4096),
    ("mistral-7b", "wo", 4096, 4096, None, (1024, 1024), 4096),
    ("mistral-7b", "w13", 4096, 28672, None, (1024, 1024), 4096),
    ("mistral-7b", "w2", 14336, 4096, None, (1024, 1024), 14336),
    ("mistral-7b", "wcls", 4096, 32768, None, (1024, 1024), 4096),
    ("olmoe-1b-7b", "wqkv", 2048, 6144, None, (1024, 1024), 2048),
    ("olmoe-1b-7b", "wo", 2048, 2048, None, (1024, 1024), 2048),
    ("olmoe-1b-7b", "gate/up", 2048, 1024, None, (1024, 1024), 2048),
    ("olmoe-1b-7b", "down", 1024, 2048, None, (1024, 1024), 1024),
    ("olmoe-1b-7b", "wcls", 2048, 50304, None, (1024, 1024), 2048),
    ("deepseek-v2", "wqkv_a", 5120, 2112, None, (1280, 768), 5120),
    ("deepseek-v2", "wq_b", 1536, 24576, None, (1536, 640), 1536),
    ("deepseek-v2", "wo", 16384, 5120, None, (1024, 1024), 16384),
    ("deepseek-v2", "w13", 5120, 24576, None, (1024, 1024), 5120),
    ("deepseek-v2", "w2", 12288, 5120, None, (1024, 1024), 12288),
    ("deepseek-v2", "gate/up", 5120, 1536, None, (1280, 768), 5120),
    ("deepseek-v2", "down", 1536, 5120, None, (1536, 640), 1536),
    ("deepseek-v2", "shared_w13", 5120, 6144, None, (1024, 1024), 5120),
    ("deepseek-v2", "shared_w2", 3072, 5120, None, (1024, 1024), 3072),
    ("deepseek-v2", "wcls", 5120, 102400, None, (1024, 1024), 5120),
    ("yi-34b", "wq", 7168, 7168, "row", (1024, 896), 7168),
    ("yi-34b", "wk/wv", 7168, 1024, "row", (1792, 256), 7168),
    ("yi-34b", "wo", 7168, 7168, "col", (1792, 512), 7168),
    ("yi-34b", "w1/w3", 7168, 20480, "row", (1024, 1024), 7168),
    ("yi-34b", "w2", 20480, 7168, "col", (1024, 1024), 20480),
    ("yi-34b", "wcls", 7168, 64000, "row", (1024, 1024), 7168),
    # K-EXAONE's share (PR 40): every side divides by 1024 but the vocabulary's
    # 19200 rows, whose last d tile is ragged by 768
    ("k-exaone-236b-a23b", "wqkv", 6144, 10240, None, (1024, 1024), 6144),
    ("k-exaone-236b-a23b", "wo", 8192, 6144, None, (1024, 1024), 8192),
    ("k-exaone-236b-a23b", "gate/up", 6144, 2048, None, (1024, 1024), 6144),
    ("k-exaone-236b-a23b", "down", 2048, 6144, None, (1024, 1024), 2048),
    ("k-exaone-236b-a23b", "shared_w13", 6144, 4096, None, (1024, 1024), 6144),
    ("k-exaone-236b-a23b", "w13", 6144, 36864, None, (1024, 1024), 6144),
    ("k-exaone-236b-a23b", "w2", 18432, 6144, None, (1024, 1024), 18432),
    ("k-exaone-236b-a23b", "wcls", 6144, 19200, None, (1024, 1024), 6144),
]
# the cells that bypass the rule's new part: every shape divides by 1024
PARENTS_OWN = ("mistral-7b", "olmoe-1b-7b", "k-exaone-236b-a23b")


class TestTiles:
    def test_ladder(self):
        """One rule: the pair that divides the matrix with the largest tiles,
        1024 x 1024 where the sides divide by 1024."""
        assert q40._tiles(4096, 28672) == (1024, 1024)   # Mistral w13
        assert q40._tiles(14336, 4096) == (1024, 1024)   # w2
        assert q40._tiles(3584, 4096) == (1792, 512)     # w2 per tp=4 shard
        assert q40._tiles(1792, 20480) == (1792, 512)    # Yi hidden / 4
        assert q40._tiles(64, 192) == (64, 1024)         # toy: the whole axis
        assert q40._tiles(11264 // 4, 4096) == (256, 1024)  # Llama-2 w2 / 4
        assert q40._tiles(7168, 256) == (1792, 256)      # not over MAX_TILE_N
        assert q40._tiles(8224, 4096)[0] == 32           # no legal tile: XLA

    @pytest.mark.parametrize("n,stored", [
        (1536, 1536), (1792, 1792), (3584, 3584), (1056, 1056),  # as they are
        (11008, 11264), (5632, 6144), (2752, 3072),   # no healthy tile: padded
        (96, 96), (1024, 1024), (4096, 4096), (14336, 14336)])
    def test_padded_n(self, n, stored):
        assert q40.padded_n(n) == stored
        # what is stored can be cut into legal tiles
        assert q40._tile_n_legal(stored, q40._tiles(stored, 4096)[0])

    @pytest.mark.parametrize("config,name,n,d,kind,tiles,stored", CELL_SHAPES,
                             ids=[f"{c[0]}-{c[1]}" for c in CELL_SHAPES])
    def test_cells_tile_table(self, config, name, n, d, kind, tiles, stored):
        """The tile pair and the stored input dim of every matmul the five
        cells run.  Mistral's and OLMoE's are what the parent's rule gave
        them: nothing of this rule reaches the cells it was not written for."""
        tp = 4 if kind else 1
        assert q40.padded_n(n) == stored
        local = q40._shard_nd(stored, d, kind, tp)
        got = q40._tiles(*local)
        assert got == tiles
        tile_n, tile_d = got
        assert local[0] % tile_n == 0 and q40._tile_n_legal(local[0], tile_n)
        assert tile_d % 128 == 0 and tile_n * tile_d <= q40.TILE_ELEMS
        # the last output tile is ragged by less than a tile
        assert -(-local[1] // tile_d) * tile_d - local[1] < tile_d
        if config in PARENTS_OWN:
            assert got == _parent_tiles(*local)
            assert stored == _parent_padded_n(n)
        else:
            # what the rule changed is never more tile work than the parent's
            work = lambda t, nd: -(-nd[0] // t[0]) * t[0] * -(-nd[1] // t[1]) * t[1]  # noqa: E731
            plocal = q40._shard_nd(_parent_padded_n(n), d, kind, tp)
            assert work(got, local) <= work(_parent_tiles(*plocal), plocal)

    @pytest.mark.parametrize("form", ["flat", "stacked"])
    def test_kernel_correct_at_rule_tiles(self, form):
        """``tiles=`` (the sweep's handle, and what the ladder yields for
        tp shards): numerics hold at a tile pair other than the default."""
        rng = np.random.RandomState(0)
        w = (rng.randn(2, 1024, 2048) * 0.1).astype(np.float32)
        qt = q40.quantize(w)
        x = jnp.asarray(rng.randn(1, 1024).astype(np.float32), jnp.bfloat16)
        if form == "flat":
            out = q40._pallas_matmul(x, qt.qpacked[1], qt.scales[1],
                                     interpret=True, tiles=(512, 2048))
        else:
            out = q40._pallas_matmul_stacked(x, qt.qpacked, qt.scales,
                                             jnp.int32(1), interpret=True,
                                             tiles=(512, 2048))
        ref = np.asarray(x @ q40.dequantize(qt, jnp.bfloat16)[1])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=0,
                                   atol=1e-2 * np.abs(ref).max())


# (n, d) whose tile pair the parent's rule did not give: DeepSeek-V2's gate
# (1280 x 768), down at an unpadded 1536 (1536 x 640), wqkv_a (a ragged third
# tile of 768), Yi-34B's shards of wo (the whole 1792 against 512), q
# (1024 x 896) and k / v (1792 x 256)
NEW_TILE_SHAPES = [(5120, 1536), (1536, 5120), (5120, 2112), (1792, 7168),
                   (7168, 1792), (7168, 256)]


@pytest.mark.parametrize("form", ["flat", "stacked"])
@pytest.mark.parametrize("n,d", NEW_TILE_SHAPES, ids=lambda v: str(v))
def test_kernel_matches_xla_at_the_rules_new_tiles(form, n, d):
    """Interpret-mode numerics at the tile pairs this rule brought, chosen by
    the rule itself, against the reference of the body five rows take (the
    sliced body's float32 dequantization): another tile_n only moves where
    the f32 accumulator's partial sums are cut."""
    assert q40._tiles(n, d) != _parent_tiles(_parent_padded_n(n), d)
    lead = (2,) if form == "stacked" else ()
    qt = q40.quantize(_rand((*lead, n, d), seed=n % 97))
    assert qt.qpacked.shape[-2] * 2 == n  # stored as it is
    x = jnp.asarray(_rand((5, n), seed=d % 89, scale=1.0), jnp.bfloat16)
    w = q40.QLayerView(qt, jnp.int32(1)) if form == "stacked" else qt
    got = np.asarray(q40.matmul(x, w, impl="pallas_interpret", out_dtype=jnp.float32))
    ref, tol = _reference(x, w)
    np.testing.assert_allclose(got, ref, rtol=0, atol=tol * np.abs(ref).max())


def test_dispatch_record_carries_the_tile_pair_and_the_stored_n(caplog, monkeypatch):
    import logging
    # an earlier test of this worker may have configured the program's logger
    # (obs.log.configure stops its propagation, which caplog listens through)
    monkeypatch.setattr(logging.getLogger("dllama"), "propagate", True)
    qt = q40.quantize(_rand((1536, 256), seed=5))
    x = jnp.asarray(_rand((2, 1536), seed=6, scale=1.0), jnp.bfloat16)
    with caplog.at_level(logging.DEBUG, logger="dllama"):
        q40.matmul(x, qt, impl="pallas_interpret")
    recs = [r for r in caplog.records if getattr(r, "path", "") == "pallas-fused"]
    assert recs and recs[-1].tiles == (1536, 256) and recs[-1].stored_n == 1536


class TestScaleValidation:
    def test_inf_scale_in_file_bytes_rejected(self):
        """A converter-overflowed or corrupt scale (f16 inf/NaN) must fail
        at pack time: the in-kernel f16-bit decode has no exp==0x1F branch
        and would map it to a large finite weight silently (ADVICE r03)."""
        d, n = 2, 64
        nb = n // 32
        raw = np.zeros((d, nb, quants.Q40_BLOCK_BYTES), np.uint8)
        raw[..., :2] = np.frombuffer(np.float16(0.01).tobytes(), np.uint8)
        ok = q40.pack_file_groups([[(raw.reshape(d, -1), d, n)]], stacked=False)
        assert ok.logical_nd == (n, d)
        bad = raw.copy()
        bad[0, 0, :2] = np.frombuffer(np.float16(np.inf).tobytes(), np.uint8)
        with pytest.raises(ValueError, match="inf/NaN"):
            q40.pack_file_groups([[(bad.reshape(d, -1), d, n)]], stacked=False)


class TestRowBlocks:
    """Over PALLAS_MAX_ROWS rows the fused kernel runs over row blocks:
    same rounding as the XLA path (bf16 dequant, f32 accumulation), another
    summation order.  At every row count the activation goes in whole, in
    the model's column order (1 and 16 rows: the one-block programs)."""

    @staticmethod
    def _case(form, n, d, rows):
        lead = {"plain": (), "stacked": (3,), "experts": (2, 3)}[form]
        qt = q40.quantize(_rand((*lead, n, d), seed=11))
        x = jnp.asarray(_rand((rows, n), seed=rows, scale=1.0), jnp.bfloat16)
        if form == "plain":
            return x, qt, qt
        view = q40.QLayerView(qt, jnp.int32(1))
        if form == "experts":  # (L, E, n/2, d): layer 1, expert 2
            view = view.select(jnp.int32(2), 3)
        return x, qt, view

    @pytest.mark.parametrize("rows", [1, 16, 129, 256, 272, 300, 1024])
    @pytest.mark.parametrize("form,n", [("plain", 1024), ("plain", 1056),
                                        ("plain", 2752), ("stacked", 2752),
                                        ("experts", 1024)])
    def test_matches_xla(self, form, n, rows):
        """n=1056 is stored as it is (one reduction step of 1056); n=2752 is
        stored padded to 3072 rows of zero scales."""
        x, _, w = self._case(form, n, 384, rows)
        got = np.asarray(q40.matmul(x, w, impl="pallas_interpret",
                                    out_dtype=jnp.float32))
        ref, tol = _reference(x, w)
        np.testing.assert_allclose(got, ref, rtol=0, atol=tol * np.abs(ref).max())

    @pytest.mark.parametrize("form", ["plain", "stacked"])
    def test_ragged_last_block_is_masked(self, form):
        """272 rows in blocks of 256: the last block holds 16 rows and 240
        of padding that must not reach the output."""
        x, qt, w = self._case(form, 2752, 384, 272)
        xp = q40._pad_x(x, 2752, 3072)
        if form == "plain":
            got = q40._pallas_matmul(xp, qt.qpacked, qt.scales,
                                     interpret=True, row_block=256)
        else:
            got = q40._pallas_matmul_stacked(xp, qt.qpacked, qt.scales,
                                             jnp.int32(1), interpret=True,
                                             row_block=256)
        ref = np.asarray(q40.matmul(x, w, impl="xla", out_dtype=jnp.float32))
        assert got.shape == ref.shape and np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), ref, rtol=0,
                                   atol=1e-4 * np.abs(ref).max())

    @pytest.mark.parametrize("rows,want", [
        (1, None), (128, None),                         # today's programs
        (129, 144), (256, 256), (272, 272), (600, 608), (1024, 1024),  # one block
        (2048, 1024), (2500, 848),                      # equal blocks
    ])
    def test_row_block_rule(self, rows, want):
        assert q40._row_block(rows, 1024, 1024) == want

    def test_row_block_shrinks_to_the_vmem_budget(self):
        """A 4096-wide output tile leaves room for fewer rows than
        ROW_BLOCK_MAX, never fewer than 256."""
        tr = q40._row_block(2048, 256, 4096)
        assert 256 <= tr < q40.ROW_BLOCK_MAX and tr % 16 == 0


class TestAutoChoice:
    """``impl="auto"`` is a static choice (platform, rows, mesh, tile
    legality): the same inside and outside a jit trace, and a kernel that
    cannot lower raises instead of degrading to the XLA path."""

    @pytest.mark.parametrize("n,tile_n,legal", [
        (4096, 1024, True),    # 7B wqkv/wo/w13/wcls
        (2816, 256, True),     # 7B w2 per tp=4 shard
        (96, 96, True),        # whole-axis tile: always legal
        (1408, 128, False),    # partial tile below 256: scales sublanes < 8
        (96, 32, False),
    ])
    def test_tile_rule(self, n, tile_n, legal):
        assert q40._tile_n_legal(n, tile_n) is legal

    @pytest.mark.parametrize("np_,d,rows,kind,want", [
        (4096, 4096, 1, None, True),
        (4096, 4096, 128, None, True),
        (4096, 4096, 129, None, True),     # over 128 rows: row blocks
        (1408, 4096, 1, None, True),       # the whole axis against 640
        (8224, 4096, 1, None, False),      # no legal tile: 257 blocks of 32
    ])
    def test_auto_rule_single_device(self, np_, d, rows, kind, want):
        assert q40._auto_pallas(np_, d, rows, kind) is want

    def test_auto_rule_on_tp_mesh(self):
        from dllama_tpu.parallel.mesh import active_mesh, make_mesh
        with active_mesh(make_mesh(tp=4, devices=jax.devices()[:4])):
            assert q40._auto_pallas(4096, 4096, 1, "col") is True
            assert q40._auto_pallas(11264, 4096, 1, "col") is True
            assert q40._auto_pallas(4096, 32000, 1, "row") is True
            assert q40._auto_pallas(4096, 4096, 1, None) is False  # no kind
            assert q40._auto_pallas(96, 4096, 1, "col") is False   # splits a block

    @pytest.mark.parametrize("rows,want", [(128, True), (256, False)])
    def test_auto_rule_keeps_the_row_cap_on_a_mesh(self, rows, want):
        """The row-blocked form is single-device: on a tp mesh more than
        PALLAS_MAX_ROWS rows still take the GSPMD XLA path."""
        from dllama_tpu.parallel.mesh import active_mesh, make_mesh
        with active_mesh(make_mesh(tp=4, devices=jax.devices()[:4])):
            assert q40._auto_pallas(4096, 4096, rows, "col") is want
            assert q40._auto_pallas(4096, 32000, rows, "row") is want

    @pytest.mark.parametrize("codec", ["q40", "q8"])
    def test_auto_on_tpu_is_pallas_in_and_out_of_jit_and_raises(
            self, codec, monkeypatch):
        """Platform patched to read ``tpu``: auto picks the Pallas kernel
        outside and inside ``jax.jit`` alike (the ledger says so), and the
        lowering failure — this backend is really the CPU — propagates:
        no degrade, no silent xla-dequant."""
        from dllama_tpu.obs import dispatch as obs_dispatch
        from dllama_tpu.ops import q8
        mod = q40 if codec == "q40" else q8
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        rng = np.random.RandomState(0)
        qt = mod.quantize((rng.randn(256, 128) * 0.1).astype(np.float32))
        x = jnp.asarray(rng.randn(1, 256), jnp.bfloat16)
        for call in (lambda: mod.matmul(x, qt, impl="auto"),
                     lambda: jax.jit(
                         lambda v: mod.matmul(v, qt, impl="auto"))(x)):
            obs_dispatch.reset()
            try:
                with pytest.raises(Exception):  # noqa: B017 — any lowering error
                    jax.block_until_ready(call())
                # a Q40 site also says which body its block's rows took
                body = {"q40_body/grouped-words": 1} if codec == "q40" else {}
                assert obs_dispatch.dispatches() == {f"{codec}/pallas-fused": 1,
                                                     **body}
                assert obs_dispatch.degraded() is False
            finally:
                obs_dispatch.reset()

    def test_auto_off_tpu_is_xla(self):
        from dllama_tpu.obs import dispatch as obs_dispatch
        rng = np.random.RandomState(0)
        qt = q40.quantize((rng.randn(256, 128) * 0.1).astype(np.float32))
        x = jnp.asarray(rng.randn(1, 256), jnp.bfloat16)
        obs_dispatch.reset()
        try:
            jax.jit(lambda v: q40.matmul(v, qt, impl="auto"))(x)
            assert obs_dispatch.dispatches() == {"q40/xla-dequant": 1}
            assert obs_dispatch.degraded() is False
        finally:
            obs_dispatch.reset()


class TestModel:
    def test_quantized_forward_close_to_dense(self):
        """Tiny llama with quantized matmuls ≡ same model with the
        dequantized weights (not the f32 originals — quantization error is
        the codec's, the matmul must add only matmul-precision error)."""
        from dllama_tpu.models.config import tiny_config
        from dllama_tpu.models.params import init_params, quantize_matmuls
        from dllama_tpu.models.transformer import forward, init_kv_cache

        cfg = tiny_config(dim=64, hidden_dim=96, n_layers=2, n_heads=4,
                          n_kv_heads=2, vocab_size=128, seq_len=32)
        params = init_params(cfg, seed=0)
        qparams = quantize_matmuls(params, cfg)
        dparams = {k: (q40.dequantize(v, jnp.float32) if isinstance(v, q40.QTensor) else v)
                   for k, v in qparams.items()}

        tokens = jnp.asarray([[1, 5, 9, 2]], jnp.int32)
        cfg_q = cfg.with_(quant_impl="xla")
        lq, _ = forward(qparams, cfg_q, tokens, init_kv_cache(cfg, 1), jnp.int32(0))
        ld, _ = forward(dparams, cfg, tokens, init_kv_cache(cfg, 1), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                   rtol=0, atol=5e-2 + 2e-2 * np.abs(np.asarray(ld)).max())

    @pytest.mark.parametrize("hidden,padded", [(2752, True), (1408, False)])
    def test_quantized_forward_padded_hidden(self, hidden, padded):
        """Hidden dims over TILE_N that are no multiple of it.  2752 has no
        healthy tile: the w2 input axis gets pack-time padding rows whose zero
        scales must contribute nothing.  1408 (TinyLlama's 5632 shape class at
        a quarter) is stored as it is and reduced in one step.  Checked
        through a full forward, both matmul implementations."""
        from dllama_tpu.models.config import tiny_config
        from dllama_tpu.models.params import init_params, quantize_matmuls
        from dllama_tpu.models.transformer import forward, init_kv_cache

        cfg = tiny_config(dim=64, hidden_dim=hidden, n_layers=2,
                          n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=32)
        assert (q40.padded_n(hidden) != hidden) is padded
        params = init_params(cfg, seed=2)
        qparams = quantize_matmuls(params, cfg)
        dparams = {k: (q40.dequantize(v, jnp.float32) if isinstance(v, q40.QTensor) else v)
                   for k, v in qparams.items()}
        tokens = jnp.asarray([[1, 5, 9, 2]], jnp.int32)
        ld, _ = forward(dparams, cfg, tokens, init_kv_cache(cfg, 1), jnp.int32(0))
        tol = 5e-2 + 2e-2 * np.abs(np.asarray(ld)).max()
        for impl in ("xla", "pallas_interpret"):
            lq, _ = forward(qparams, cfg.with_(quant_impl=impl), tokens,
                            init_kv_cache(cfg, 1), jnp.int32(0))
            np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                       rtol=0, atol=tol)

    def test_tp_sharded_quantized_equivalence(self):
        """N-shard ≡ 1-shard (commands-test.cpp pattern) with packed Q40
        weights: the sharded run uses the partitionable XLA impl."""
        from dllama_tpu.models.config import tiny_config
        from dllama_tpu.models.params import init_params, quantize_matmuls
        from dllama_tpu.models.transformer import forward, init_kv_cache
        from dllama_tpu.parallel import sharding as sh
        from dllama_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device CPU mesh")
        cfg = tiny_config(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                          n_kv_heads=2, vocab_size=128, seq_len=32).with_(quant_impl="xla")
        params = quantize_matmuls(init_params(cfg, seed=0), cfg)
        tokens = jnp.asarray([[3, 7, 11]], jnp.int32)

        ref, _ = forward(params, cfg, tokens, init_kv_cache(cfg, 1), jnp.int32(0))

        mesh = make_mesh(tp=2, devices=jax.devices()[:2])
        placed = sh.place_params(params, cfg, mesh)
        cache = jax.device_put(init_kv_cache(cfg, 1), sh.kv_cache_sharding(mesh))
        out, _ = jax.jit(lambda p, c, t: forward(p, cfg, t, c, jnp.int32(0)))(
            placed, cache, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-3 + 1e-3 * np.abs(np.asarray(ref)).max())


class TestEngineIntegration:
    def test_mfile_quantized_load_and_generate(self, tmp_path):
        """End-to-end: Q40 .m file loaded packed, engine generates the same
        tokens as the dequantized load at temperature 0."""
        from tests.fixtures import write_tiny_model
        from dllama_tpu.io import mfile
        from dllama_tpu.models.config import ModelConfig
        from dllama_tpu.models.params import load_params
        from dllama_tpu.runtime.engine import Engine
        from dllama_tpu.sampling import Sampler

        path = tmp_path / "tiny-q40.m"
        write_tiny_model(str(path), ftype=quants.Q40, vocab_size=64, seq_len=64)
        mf = mfile.MFile(str(path))
        cfg = ModelConfig.from_spec(mf.spec, dtype=jnp.float32)

        outs = []
        for keep in (True, False):
            cfg_l, params = load_params(mf, cfg, keep_quantized=keep)
            if keep:
                # a Q40 load keeps packed fused projections, no dense f32
                assert isinstance(params["wqkv"], q40.QTensor)
                assert isinstance(params["w13"], q40.QTensor)
                assert params["wqkv"].logical_nd == (64, 64 + 2 * 32)
            eng = Engine(cfg_l, params)
            toks = [t for t, _ in eng.generate(
                [1, 5, 9], steps=10, sampler=Sampler(cfg.vocab_size, 0.0, 0.9, 0))]
            outs.append(toks)
        # keep=False dequantizes the same Q40 bytes → same values → greedy
        # decode must match exactly
        assert outs[0] == outs[1]


def test_f16_bits_to_f32_exhaustive():
    """The in-kernel integer widening must agree with IEEE f16→f32 for
    every finite bit pattern (the codec never stores inf/nan scales) —
    this is what keeps dequantization bit-identical to the file format
    with uint16-stored scales."""
    bits = np.arange(1 << 16, dtype=np.uint16)
    finite = np.isfinite(bits.view(np.float16))
    got = np.asarray(q40._f16_bits_to_f32(jnp.asarray(bits[finite])))
    exp = bits[finite].view(np.float16).astype(np.float32)
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n,rows", [(64, 1), (256, 1), (256, 2), (256, 16), (256, R)])
def test_extreme_scales_roundtrip_through_kernel(n, rows):
    """Scales at the f16 extremes — subnormal deltas (tiny weights) and
    near-max deltas (|w| up to ~524k pre-clamp) — must dequantize exactly
    through the uint16 bit path in both the XLA and interpret-kernel
    implementations.  One row takes the grouped body (a tile of two
    quantization blocks or of eight) and 2 to SLICED_MAX_ROWS rows the sliced
    body (two slices of four), whose bias term ``24 * sum(x)`` cancels in f32:
    the error against the float32 reference is under GROUPED_TOL over the tiny
    blocks alone, over the huge ones alone and over both, and no larger than
    the dot body's on the same input."""
    rng = np.random.RandomState(0)
    w = rng.randn(n, 128).astype(np.float32)
    w[:n // 2] *= 1e-7      # subnormal f16 deltas (amax/8 < 6.1e-5)
    w[n // 2:] *= 5e4       # deltas near the f16 normal range top
    qt = q40.quantize(w)
    assert qt.scales.dtype == jnp.uint16
    dq = np.asarray(q40.dequantize(qt))
    # independent reconstruction from the stored f16 bits
    sc = np.asarray(qt.scales).view(np.float16).astype(np.float32)
    v = np.asarray(qt.qpacked).astype(np.int32)
    lo = (v & 0xF) - 8
    hi = (v >> 4) - 8
    dense = np.concatenate(
        [lo.reshape(n // 32, 16, 128), hi.reshape(n // 32, 16, 128)], axis=1
    ).reshape(n, 128) * np.repeat(sc, 32, axis=0)
    np.testing.assert_array_equal(dq, dense.astype(np.float32))

    x = _rand((rows, n), seed=1, scale=1.0)
    ref = x @ dq
    out = np.asarray(q40.matmul(jnp.asarray(x), qt, impl="pallas_interpret"))
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=2e-2 * np.abs(ref).max() + 1e-12)
    assert q40._body(rows, n) == ("grouped" if rows == 1 else "sliced")
    for keep in (slice(None), slice(0, n // 2), slice(n // 2, n)):
        xk = np.zeros_like(x)
        xk[:, keep] = x[:, keep]
        xk = jnp.asarray(xk, jnp.bfloat16)
        ref = _f32_reference(xk, qt)
        few = np.asarray(q40._pallas_matmul(xk, qt.qpacked, qt.scales, interpret=True))
        # the same rows in a block of more than SLICED_MAX_ROWS take the dot
        # (bf16 weights)
        many = np.asarray(q40._pallas_matmul(
            jnp.concatenate([xk] * (R // rows + 1)), qt.qpacked, qt.scales,
            interpret=True))[:rows]
        err = np.abs(few - ref).max()
        assert err <= GROUPED_TOL * np.abs(ref).max(), (keep, err)
        # (the tiny blocks alone are exact on the dot body too: a subnormal
        # scale times a nibble is a bf16)
        if rows == 1 or keep == slice(None):
            assert err <= np.abs(many - ref).max(), keep


# ---- few rows are contracted a quantization block at a time (PRs 50, 62) ---

# (n, d, tiles): an input dim stored padded (2752 -> 3072, three steps), a
# ragged last d tile (320 in tiles of 256), several n steps at a forced tile,
# and whole-axis tiles of 44 quantization blocks (DeepSeek-V2's expert width)
# and of 3: no multiple of the eight sublanes the block partials lie on
ONE_ROW_SHAPES = [(2752, 384, None), (512, 320, (256, 256)), (1024, 256, (256, 256)),
                  (1408, 256, None), (96, 128, None)]
# (rows, n, d, tiles) of the sliced body (PR 62): rows short of a sublane
# group, a padded n, a ragged d, whole-axis tiles of 1024, 1536 and 2048 rows
# (8, 12 and 16 slices) and of 1408 (11); then what keeps the dot: one row
# more than SLICED_MAX_ROWS, and a toy's tile of 96 rows (no whole vreg)
FEW_ROW_SHAPES = [(2, 2752, 384, None), (3, 512, 320, (256, 256)), (8, 1024, 256, None),
                  (16, 1536, 128, None), (R, 2048, 128, None), (16, 1408, 256, None),
                  (R + 1, 1024, 256, (256, 256)), (16, 96, 128, None)]


@pytest.mark.parametrize("rows,n,d,tiles",
                         [(1, *s) for s in ONE_ROW_SHAPES] + FEW_ROW_SHAPES,
                         ids=lambda v: str(v))
@pytest.mark.parametrize("form", ["flat", "stacked", "chosen", "chosen-x-an-expert"])
def test_few_rows_are_contracted_by_blocks_and_equal_the_f32_reference(form, rows, n,
                                                                       d, tiles):
    """A block of 1 to SLICED_MAX_ROWS rows through each launch: the result is
    ``x @ dequantize(qt, float32)`` within GROUPED_TOL, a bound the same rows
    cannot meet on the dot body (which rounds each weight to bf16: the rows in
    a block of more than SLICED_MAX_ROWS, or against a toy's tile of 96 rows),
    and a row's result is the same alone and in company, to summation order."""
    experts, layer, picks = 3, 1, (2, 0, 2)
    rng = np.random.default_rng(n + d)
    lead = {"flat": (), "stacked": (2,)}.get(form, (2, experts))
    qt = q40.quantize(rng.standard_normal((*lead, n, d)).astype(np.float32) * 0.1)
    np_ = qt.qpacked.shape[-2] * 2
    per_expert = form == "chosen-x-an-expert"
    x = jnp.asarray(rng.standard_normal(
        ((len(picks),) if per_expert else ()) + (rows, n)), jnp.bfloat16)
    xp = q40._pad_x(x, n, np_)
    exact = q40._body(rows, (tiles or q40._tiles(np_, d))[0]) != "dot"
    assert exact == (rows == 1 or rows <= R and n != 96)

    def launch(xp):
        if form == "flat":
            return q40._pallas_matmul(xp, qt.qpacked, qt.scales, interpret=True,
                                      tiles=tiles)[None]
        view = q40.QLayerView(qt, jnp.int32(layer))
        if form == "stacked":
            return q40._pallas_matmul_stacked(xp, *view.flat_planes(), view.layer,
                                              interpret=True, tiles=tiles)[None]
        return q40._pallas_matmul_experts(
            xp, *view.flat_planes(), view.layer, experts=experts, interpret=True,
            tiles=tiles, chosen=jnp.asarray(picks))

    out = np.asarray(launch(xp))
    many = np.asarray(launch(jnp.concatenate([xp] * (R // rows + 1), axis=-2))
                      )[..., :rows, :]
    alone = np.asarray(launch(xp[..., :1, :]))
    planes = {"flat": [qt], "stacked": [q40.QLayerView(qt, jnp.int32(layer))]}.get(
        form) or [q40.QLayerView(qt, jnp.int32(layer)).select(jnp.int32(e), experts)
                  for e in picks]
    assert out.shape == (len(planes), rows, d)
    for j, w in enumerate(planes):
        ref = _f32_reference(x[j] if per_expert else x, w)
        err = np.abs(out[j] - ref).max() / np.abs(ref).max()
        assert (err <= GROUPED_TOL) == exact, (j, err)
        assert np.abs(many[j] - ref).max() / np.abs(ref).max() > 10 * GROUPED_TOL
        # a row alone takes the grouped body: the same sums in another order,
        # or (beside the dot body) the bf16 edge D17 names
        gap = np.abs(alone[j] - out[j][:1]).max() / np.abs(ref).max()
        assert (gap <= 2 * GROUPED_TOL) == exact, (j, gap)


@pytest.mark.parametrize("rows,n,body", [
    (1, 512, "grouped-words"), (1, 1408, "grouped-words"), (1, 128, "grouped-words"),
    (1, 96, "grouped-nibbles"), (1, 64, "grouped-nibbles"),
    (2, 512, "sliced-words"), (3, 128, "sliced-words"), (8, 1408, "sliced-words"),
    (16, 512, "sliced-words"), (R, 512, "sliced-words"),
    (2, 96, "dot"), (16, 64, "dot"), (R + 1, 512, "dot"), (256, 512, "dot")])
def test_the_blocks_rows_choose_the_body_and_the_ledger_says_which(rows, n, body, caplog,
                                                                   monkeypatch):
    """Nothing but the block's shape chooses: its row count the body (one row
    ``grouped``, 2 to SLICED_MAX_ROWS ``sliced``, more the ``dot``: one edge,
    monotone), and the tile's rows whether the nibbles become the dot's
    operand as words (wherever ``tile_n`` is a multiple of 128, the activation
    row beside it whole vregs; a toy's whole-axis tile of 64 or 96 rows keeps
    a conversion a nibble at one row and the dot above it).  The ``q40_body``
    counter and the ``body=`` of the ``q40/pallas-fused`` record name both,
    and the kernel's jaxpr builds a block-diagonal left operand (from an iota)
    exactly where they say ``grouped`` or ``sliced``."""
    import logging
    from dllama_tpu.obs import dispatch as obs_dispatch
    monkeypatch.setattr(logging.getLogger("dllama"), "propagate", True)
    tile_n = q40._tiles(n, 256)[0]
    assert q40._body(rows, tile_n) == body.split("-")[0]
    if rows == 1:
        assert "grouped-" + q40._nibbles_as(tile_n) == body
    assert [q40._body(r, tile_n) for r in range(1, 300)] == sorted(
        (q40._body(r, tile_n) for r in range(1, 300)),
        key=("grouped", "sliced", "dot").index)
    qt = q40.quantize(_rand((2, n, 256), seed=2))
    w = q40.QLayerView(qt, jnp.int32(1))
    x = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16)
    before = obs_dispatch.dispatches()
    with caplog.at_level(logging.DEBUG, logger="dllama"):
        jaxpr = jax.make_jaxpr(lambda x: q40.matmul(x, w, impl="pallas_interpret"))(x)
    after = obs_dispatch.dispatches()
    for name in ("grouped-words", "grouped-nibbles", "sliced-words", "dot", "grouped",
                 "sliced"):
        assert after.get(f"q40_body/{name}", 0) == \
            before.get(f"q40_body/{name}", 0) + (name == body), name
    rec, = [r for r in caplog.records if getattr(r, "path", None) == "pallas-fused"]
    assert rec.body == body and rec.rows == rows
    assert (" iota[" in str(jaxpr)) == (body != "dot")


def _kernel_eqns(jaxpr):
    """Every equation inside the ``pallas_call`` kernels of ``jaxpr``, nested
    jaxprs (a ``pl.when``'s branches, an inner ``jit``) included."""
    def walk(j, inside):
        for eqn in j.eqns:
            if inside:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inside or eqn.primitive.name == "pallas_call")
    return walk(jaxpr, False)


@pytest.mark.parametrize("form", ["flat", "stacked", "chosen"])
@pytest.mark.parametrize("rows,n,d,tiles,converts", [
    (1, 1024, 256, None, False), (1, 1024, 256, (256, 128), False),
    (1, 1408, 128, None, False), (1, 96, 128, None, True),
    (16, 1024, 256, None, False), (16, 1024, 256, (256, 128), False),
    (16, 1408, 128, None, False), (16, 96, 128, None, True)], ids=lambda v: str(v))
def test_no_nibble_of_a_words_tile_is_converted_from_an_integer(form, rows, n, d,
                                                               tiles, converts):
    """The mechanism (PR 58; at 2 to SLICED_MAX_ROWS rows since PR 62): a tile
    the word form takes never leaves integer registers until it IS the bf16
    operand: the kernel's jaxpr holds no ``convert_element_type`` from an
    integer to a float on anything as large as the tile (the scales'
    ``(tile_n / 32, tile_d)`` mantissas still are, once a block), and no
    extension of the bytes either.  A tile it cannot take (96 rows) converts
    each nibble plane, as PR 50's body did at one row and the dot body does
    above it."""
    experts = 2
    lead = {"flat": (), "stacked": (2,)}.get(form, (2 * experts,))
    x = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16)
    qp = jax.ShapeDtypeStruct((*lead, n // 2, d), jnp.uint8)
    sc = jax.ShapeDtypeStruct((*lead, n // 32, d), jnp.uint16)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    if form == "flat":
        jaxpr = jax.make_jaxpr(lambda *a: q40._pallas_matmul(
            *a, interpret=True, tiles=tiles))(x, qp, sc)
    elif form == "stacked":
        jaxpr = jax.make_jaxpr(lambda *a: q40._pallas_matmul_stacked(
            *a, interpret=True, tiles=tiles))(x, qp, sc, layer)
    else:
        jaxpr = jax.make_jaxpr(lambda *a: q40._pallas_matmul_experts(
            *a, experts=experts, interpret=True, tiles=tiles,
            chosen=jnp.asarray([1, 0])))(x, qp, sc, layer)
    tn, td = tiles or q40._tiles(n, d)
    eqns = list(_kernel_eqns(jaxpr.jaxpr))
    assert any(e.primitive.name == "dot_general" for e in eqns)
    widened = [e for e in eqns if e.primitive.name == "convert_element_type"
               and jnp.issubdtype(e.invars[0].aval.dtype, jnp.integer)
               and e.invars[0].aval.size >= tn // 8 * td]
    to_float = [e for e in widened if jnp.issubdtype(e.outvars[0].aval.dtype, jnp.floating)]
    assert bool(to_float) == converts and bool(widened) == converts, widened
    assert any(e.primitive.name == "bitcast" for e in eqns) != converts


# (n, tiles): whole-axis tiles of 8, 4 and 12 blocks (two, one and three vregs
# of ``x``), 44 blocks (DeepSeek-V2's expert width), two reduction steps, and
# tiles the word form does not take (2 and 3 blocks: a conversion a nibble)
ONE_HOT_TILES = [(256, None), (128, None), (384, None), (1408, None),
                 (512, (256, 128)), (64, None), (96, None)]


@pytest.mark.parametrize("n,tiles", ONE_HOT_TILES, ids=lambda v: str(v))
def test_every_nibble_of_a_word_lands_on_its_own_logical_row(n, tiles):
    """A one-hot ``x`` at each of the ``n`` positions against weights whose
    nibble is ``(row + column) % 16`` and whose scale is a power of two that
    differs from block to block: over the 128 columns every nibble value 0-15
    stands in every position of every word, and each one-hot row must read its
    OWN logical row of the tile at its own block's scale, exactly (one product,
    ``(16 + v) - 24``, times a power of two).  This is the CPU half of the
    proof that the order in which ``pltpu.bitcast`` sets a word's bytes and
    halves on rows is the one the left operand follows; ``chip_smoke.py``'s
    ``q40.*.f32`` lines are the chip's."""
    d = 128
    rows, cols = np.arange(n)[:, None], np.arange(d)[None, :]
    qvals = ((rows + cols) % 16 - 8).astype(np.int8)
    scales = (2.0 ** ((np.arange(n // 32)[:, None] * 5 + cols) % 7 - 3)).astype(np.float16)
    qt = q40.pack_planes(qvals[None], scales[None])
    # one launch: position j is "expert" j's own activation row, every one of
    # them reading plane 0
    x = jnp.asarray(np.eye(n, dtype=np.float32)[:, None, :], jnp.bfloat16)
    out = np.asarray(q40._pallas_matmul_experts(
        x, qt.qpacked, qt.scales, jnp.int32(0), experts=1, interpret=True,
        tiles=tiles, chosen=jnp.zeros((n,), jnp.int32)))
    assert out.shape == (n, 1, d)
    want = qvals.astype(np.float32) * np.repeat(scales.astype(np.float32), 32, axis=0)
    np.testing.assert_array_equal(out[:, 0, :], want)


@pytest.mark.parametrize("body,rows", [
    ("sliced", 2), ("sliced", 32), ("vpu", 1), ("vpu", 2), ("nibbles", 1),
    ("bytes", 1), ("words128", 1)])
def test_the_sweeps_few_row_bodies_compute_the_matmul(body, rows):
    """``tools/sweep_q40.py --body``'s forms the program does not run (the
    grouped algebra at a few rows a block, with its inner sums on the VPU, and
    the one-row operand made a nibble or a byte at a time or as ``128 + v``)
    are patched into the loaded module for a run: what the sweep times is the
    matmul, within the grouped body's bound of the f32 reference, and the
    module is the program's again afterwards."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("sweep_q40", os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "tools", "sweep_q40.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    qt = q40.quantize(_rand((768, 128), seed=9))
    x = jnp.asarray(_rand((rows, 768), seed=10, scale=1.0), jnp.bfloat16)
    rule = q40._body
    with sweep._body_as(body):
        assert q40._body(rows, 768) == ("sliced" if body == "sliced" else "grouped")
        out = np.asarray(q40._pallas_matmul(x, qt.qpacked, qt.scales, interpret=True))
    assert q40._body is rule and q40._contract_grouped.__module__ == q40.__name__
    ref = _f32_reference(x, qt)
    assert np.abs(out - ref).max() <= GROUPED_TOL * np.abs(ref).max()


def test_the_q40_knobs_stay_gone():
    """One kernel, one layout, one tile rule (PR 28): no environment name
    starting ``DLLAMA_Q40_`` anywhere in the package, and no ``variant``
    argument on the kernel entry points.  That ``auto`` chooses from
    platform and shape alone is TestAutoChoice's to show."""
    import inspect
    import os

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(q40.__file__)))
    hits = []
    for root, _, files in os.walk(pkg):
        for f in files:
            if f.endswith((".py", ".cpp", ".h")):
                with open(os.path.join(root, f), encoding="utf-8") as fh:
                    if "DLLAMA_Q40_" in fh.read():
                        hits.append(os.path.join(root, f))
    assert not hits
    for fn in (q40._pallas_matmul, q40._pallas_matmul_stacked):
        names = set(inspect.signature(fn).parameters)
        assert "variant" not in names and "tiles" in names


# ---- the kernel takes its activation as the caller holds it (PR 41) -------

@pytest.mark.parametrize("rows", [1, 16, 129, 256, 300])
@pytest.mark.parametrize("form,per_expert", [
    ("experts", False), ("experts", True), ("chosen", False), ("chosen", True)],
    ids=["experts-shared-x", "experts-x-an-expert", "chosen-shared-x",
         "chosen-x-an-expert"])
def test_experts_and_chosen_launches_take_whole_x_and_match_xla(form, per_expert,
                                                                rows):
    """The experts and the chosen launch at one block of every row (1, 16),
    in row blocks (129, 256) and with a ragged last row block (300 rows in
    blocks of 128), two reduction steps and a ragged last ``d`` tile: each
    plane's product equals the XLA path's."""
    experts, layer, n, d = 4, 1, 512, 320
    rng = np.random.default_rng(rows)
    qt = q40.quantize(rng.standard_normal((2, experts, n, d)).astype(np.float32) * 0.1)
    view = q40.QLayerView(qt, jnp.int32(layer))
    picks = (3, 0, 3) if form == "chosen" else tuple(range(experts))
    x = jnp.asarray(rng.standard_normal(
        ((len(picks),) if per_expert else ()) + (rows, n)), jnp.bfloat16)
    out = q40._pallas_matmul_experts(
        x, *view.flat_planes(), view.layer, experts=experts, interpret=True,
        tiles=(256, 256), row_block=128 if rows == 300 else None,
        chosen=jnp.asarray(picks) if form == "chosen" else None)
    assert out.shape == (len(picks), rows, d)
    for j, e in enumerate(picks):
        ref, tol = _reference(x[j] if per_expert else x,
                              view.select(jnp.int32(e), experts), tiles=(256, 256))
        np.testing.assert_allclose(np.asarray(out[j]), ref, rtol=0,
                                   atol=tol * np.abs(ref).max(), err_msg=str(j))


def _eqns_outside_kernels(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, a
    ``pallas_call``'s own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("form", ["flat", "stacked"])
def test_no_split_of_x_stands_in_front_of_the_kernel(form):
    """At 256 rows (the served mixed step, the prefill bucket) the launch's
    activation operand is ``x`` itself, cast at most: no ``reshape`` or
    ``slice`` outside the ``pallas_call``.  The nibble-half split that stood
    there cost 8.9 ms of Mistral's 49.6 ms mixed step (PERF.md §6, PR 41)."""
    n, d = 1024, 384
    qt = q40.quantize(_rand((2, n, d), seed=3))
    w = q40.QLayerView(qt, jnp.int32(1)) if form == "stacked" else \
        q40.QTensor(qt.qpacked[0], qt.scales[0], (n, d))
    x = jax.ShapeDtypeStruct((256, n), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x: q40.matmul(x, w, impl="pallas_interpret"))(x).jaxpr
    eqns = list(_eqns_outside_kernels(jaxpr))
    # (the stacked form reshapes its layer index: one int32, not ``x``)
    moved = [e for e in eqns if e.primitive.name in (
        "reshape", "slice", "dynamic_slice", "gather", "concatenate",
        "transpose", "copy") and any(v.aval.size >= 256 for v in e.invars)]
    assert not moved, moved
    call, = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [(256, n)] == [v.aval.shape for v in call.invars
                          if v.aval.dtype == jnp.bfloat16]
