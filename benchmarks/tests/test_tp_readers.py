"""The readers of the tensor-parallel cell on made-up inputs: what counts as
a reduce, the bytes a token needs, and the two-scope roof share."""

import importlib

import pytest

YI = {"hidden_size": 7168, "intermediate_size": 20480, "num_hidden_layers": 60}


def _ctx(ops, collective_s, n_tokens=10, chips=4):
    return {"trace": {"chips": chips, "ops": ops, "collective_s": collective_s},
            "records": [{"ok": True, "cut": False,
                         "times": [100.0 + i for i in range(n_tokens)]}],
            "traced_window": (0.0, 1e9), "chips": chips, "config": YI,
            "peaks": {"ici_bits_per_s": 1600e9, "hbm_bytes_per_s": 819e9}}


def test_reduce_time_is_ring_kernels_plus_xla_collectives():
    mod = importlib.import_module("tp_reduce_ms_per_tok")
    ctx = _ctx({"q40_ring.3": 0.004, "q40_ring": 0.002, "q40_mm_stacked.7": 0.5,
                "all-reduce.1": 0.001}, collective_s=0.001)
    # by-op times already hold the collective; it is counted once, by kind
    assert mod.read(ctx) == pytest.approx((0.006 + 0.001) * 1e3 / 10)
    assert mod.read(_ctx({}, 0.0, n_tokens=0)) is None
    assert mod.read(_ctx({}, 0.0, chips=0)) is None


def test_ici_roof_share_from_the_bytes_a_token_needs():
    mod = importlib.import_module("tp_reduce_ici_roof_pct")
    assert mod.reduce_bytes_per_token(YI, 4) == 120 * 2 * 0.75 * 7168 * 4
    ctx = _ctx({"q40_ring.3": 0.02}, collective_s=0.0)
    need_s = 5160960 / 200e9
    assert mod.read(ctx) == pytest.approx(100 * need_s * 10 / 0.02)
    assert mod.read(_ctx({}, 0.0)) is None           # no reduce in the trace
    assert mod.read(dict(ctx, chips=1)) is None      # nothing to reduce over


def test_w13_share_over_both_scopes(monkeypatch):
    mod = importlib.import_module("tp_w13_roof_pct")
    shares = {"w1": 40.0, "w3": 60.0}
    monkeypatch.setattr(mod, "weight_roof_pct",
                        lambda ctx, scope, values: shares.get(scope))
    # same bytes each: need / (t1 + t3) with t = need / share
    assert mod.read(_ctx({}, 0.0)) == pytest.approx(2 / (1 / 40 + 1 / 60))
    shares.pop("w3")
    assert mod.read(_ctx({}, 0.0)) is None
