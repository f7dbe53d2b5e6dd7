"""``prefill_mfu_pct`` and ``moe_grouped_fill_pct`` on made-up spans and
counters: a chunked prompt's calls carry their own tokens, a one-call prompt's
tokens are the load generator's, and a program without the span or the counter
reads nothing (the parent of the PR that added them)."""

import importlib

import pytest

CFG = {"model": "dense", "hidden_size": 64, "intermediate_size": 128,
       "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "vocab_size": 256}


def _ctx(spans, records=(), peaks=None):
    return {"trace": {"chips": 1}, "traced_window": (100.0, 105.0),
            "records": list(records), "config": CFG, "chips": 1,
            "peaks": peaks or {"bf16_flops_per_s": 197e12}, "_spans": spans}


@pytest.fixture
def prefill(monkeypatch):
    mod = importlib.import_module("_prefill")
    monkeypatch.setattr(mod, "_spans", lambda ctx: ctx["_spans"])
    return mod


def test_chunk_spans_carry_their_tokens(prefill):
    spans = [("engine.prefill", 0, 9_000_000_000, {"pos": 0, "k": 7000}),
             ("engine.prefill_chunk", 10, 200_000_000, {"pos": 512, "k": 512}),
             ("engine.prefill_chunk", 20, 100_000_000, {"pos": 1024, "k": 200})]
    assert prefill.calls(_ctx(spans)) == [(0.2, 512, 512), (0.1, 1024, 200)]


def test_a_one_call_prompt_takes_the_load_generators_tokens(prefill):
    spans = [("engine.prefill", 10, 80_000_000, {"pos": 0, "k": 256}),
             ("engine.prefill", 20, 40_000_000, {"pos": 5, "k": 128})]
    recs = [{"sent": 99.0, "times": [100.5], "n_prompt": 50},      # sent before the trace
            {"sent": 101.0, "times": [101.1], "n_prompt": 200},
            {"sent": 102.0, "times": [102.1], "n_prompt": 90},
            {"sent": 104.9, "times": [105.2], "n_prompt": 70}]     # answered after it
    assert prefill.calls(_ctx(spans, recs)) == [(0.08, 0, 200), (0.04, 5, 85)]
    # counts that differ at the window's edges: the mean, capped at the bucket
    assert prefill.calls(_ctx(spans[:1], recs)) == [(0.08, 0, 145)]
    assert prefill.calls(_ctx(spans, [])) == [] and prefill.calls(_ctx([], recs)) == []


def test_mfu_is_needed_flops_over_span_time_and_peak(prefill):
    from harness import cost
    mfu = importlib.import_module("prefill_mfu_pct")
    spans = [("engine.prefill_chunk", 10, 200_000_000, {"pos": 512, "k": 512})]
    want = 100.0 * cost.step_flops(CFG, 512, 512 * 512 + 512 * 513 / 2, 1) / 0.2 / 197e12
    assert mfu.read(_ctx(spans)) == pytest.approx(want) and 0 < want < 100
    assert mfu.read(_ctx([])) is None


def test_fill_is_pairs_over_slots_of_the_window():
    fill = importlib.import_module("moe_grouped_fill_pct")
    before = {"moe_grouped_rows": {"pairs": 1000, "slots": 2000}}
    after = {"moe_grouped_rows": {"pairs": 4000, "slots": 6000}}
    assert fill.read({"before": before, "after": after}) == 75.0
    assert fill.read({"before": {}, "after": after}) == pytest.approx(100 * 4000 / 6000)
    assert fill.read({"before": {}, "after": {"sched_steps": {"decode": 3}}}) is None
    assert fill.read({"before": after, "after": after}) is None
