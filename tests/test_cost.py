"""Performance-economics plane tests (obs/cost.py).

Two layers of evidence, tier-1 on CPU:

* **hand counts** — the roofline model's FLOPs/bytes for the tiny
  config are recomputed here from first principles as literal
  arithmetic (one prefill chunk, one decode step, one paged-int8
  decode, one tp=2 ring hop, one decode burst) and must match
  ``CostModel`` EXACTLY — the model is only trustworthy because it is
  small enough to check token by token;
* **attribution e2e** — a real staggered scheduler run on the tiny
  engine: ledger counters carry exactly what the tracker carried, every
  flight record gains a cost block, and per-request ``chip_ms`` sums to
  the scheduler's busy (prefill + decode) goodput component within 5%.
"""

import threading

import pytest

from dllama_tpu.obs import cost as obs_cost
from dllama_tpu.obs import dispatch as obs_dispatch
from dllama_tpu.obs import flight as obs_flight
from dllama_tpu.obs import metrics as obs_metrics

pytestmark = pytest.mark.obs

# tiny_config geometry the hand counts below are written against:
# dim=64, hidden_dim=96, n_layers=2, n_heads=4, n_kv_heads=2, vocab=128
# -> head_size=16, kv_dim=32.
#
# per-layer matmul params: wq+wo (2*64*64=8192) + wk+wv (2*64*32=4096)
#                          + w1+w2+w3 (3*64*96=18432) = 30720
# params_per_token = 2 layers * 30720 = 61440;  logits head = 64*128=8192
PARAMS_PER_TOKEN = 61440
HEAD_PARAMS = 8192
# Q40 wire bytes: 18 B per 32 weights
W_READ_Q40 = 61440 // 32 * 18 + 8192 // 32 * 18  # 34560 + 4608 = 39168
KV_POS_F32 = 2 * 32 * 4    # (k+v) * kv_dim * 4 B = 256 B/position/layer
KV_POS_INT8 = 2 * (32 + 4 * 2)  # values + f32 scale planes = 80 B


def tiny_cost_model(**over):
    kw = dict(dim=64, hidden_dim=96, n_layers=2, n_heads=4, n_kv_heads=2,
              vocab_size=128, weight_codec="q40", kv_codec="kv_f32",
              kv_el_bytes=4)
    kw.update(over)
    return obs_cost.CostModel(**kw)


# --- hand-counted unit costs ----------------------------------------------

def test_prefill_chunk_hand_count():
    """One 8-token prefill chunk from position 0, single row."""
    cm = tiny_cost_model()
    out = cm.dispatch_cost([("prefill", 0, 8)])
    mm = out["entries"][("q40", "matmul", "prefill")]
    at = out["entries"][("kv_f32", "attention", "prefill")]
    # matmuls: 2*8*61440; logits: prefill samples ONE position: 2*1*64*128
    assert mm["flops"] == 2 * 8 * PARAMS_PER_TOKEN + 2 * 1 * 64 * 128
    assert mm["flops"] == 999424
    # weights stream once, one occupied row takes the whole read
    assert mm["bytes"] == W_READ_Q40 == 39168
    # attention: 4*dim FLOPs per (query, ctx) pair per layer; ctx lengths
    # 1..8 sum to 36
    assert at["flops"] == 4 * 64 * 2 * 36 == 18432
    # KV: write 8 positions + one block read of the final 8-token context
    assert at["bytes"] == 8 * 2 * KV_POS_F32 + 8 * 2 * KV_POS_F32 == 8192
    assert out["flops"] == 999424 + 18432
    assert out["hbm_bytes"] == 39168 + 8192


def test_decode_step_hand_count():
    """One single-token decode step at cache position 10."""
    cm = tiny_cost_model()
    out = cm.dispatch_cost([("decode", 10, 1)])
    mm = out["entries"][("q40", "matmul", "decode")]
    at = out["entries"][("kv_f32", "attention", "decode")]
    assert mm["flops"] == 2 * 1 * PARAMS_PER_TOKEN + 2 * 1 * 64 * 128
    assert mm["flops"] == 139264
    assert mm["bytes"] == W_READ_Q40
    # the new token attends over 11 positions (10 cached + itself)
    assert at["flops"] == 4 * 64 * 2 * 11 == 5632
    assert at["bytes"] == 1 * 2 * KV_POS_F32 + 11 * 2 * KV_POS_F32 == 6144


def test_paged_int8_decode_hand_count():
    """Decode over an int8 paged pool: reads round up to whole pages and
    pay the per-(head, position) scale planes."""
    cm = tiny_cost_model(kv_codec="kv_int8", kv_el_bytes=1,
                         paged=True, page_size=16)
    out = cm.dispatch_cost([("decode", 10, 1)])
    at = out["entries"][("kv_int8", "paged-decode", "decode")]
    # context 11 rounds up to one whole 16-position page
    assert at["bytes"] == 1 * 2 * KV_POS_INT8 + 16 * 2 * KV_POS_INT8
    assert at["bytes"] == 160 + 2560
    # attention FLOPs stay at the TRUE context, not the page granularity
    assert at["flops"] == 4 * 64 * 2 * 11


def test_tp2_ring_hop_hand_count():
    """tp=2: two f32 all-reduces of dim per layer per token, 2*(tp-1)
    ring hop copies each — tracked on its own path, excluded from HBM."""
    cm = tiny_cost_model(tp=2)
    out = cm.dispatch_cost([("decode", 0, 1)])
    ring = out["entries"][("q40", "tp-ring", "decode")]
    assert ring["bytes"] == 1 * 2 * 2 * (2 * 1) * 64 * 4 == 2048
    assert ring["flops"] == 0
    assert out["hbm_bytes"] == W_READ_Q40 + (
        out["entries"][("kv_f32", "attention", "decode")]["bytes"])
    cm1 = tiny_cost_model(tp=1)
    assert ("q40", "tp-ring", "decode") not in \
        cm1.dispatch_cost([("decode", 0, 1)])["entries"]


def test_decode_burst_rereads_weights_and_context():
    """A 4-step burst is 4 sequential passes: 4 weight streams, each new
    token re-reading its whole (growing) context."""
    cm = tiny_cost_model()
    out = cm.dispatch_cost([("decode", 4, 4)], steps=4)
    mm = out["entries"][("q40", "matmul", "decode")]
    at = out["entries"][("kv_f32", "attention", "decode")]
    assert mm["bytes"] == 4 * W_READ_Q40
    # contexts 5,6,7,8: read 26 positions total, write 4
    assert at["bytes"] == 4 * 2 * KV_POS_F32 + 26 * 2 * KV_POS_F32
    assert at["flops"] == 4 * 64 * 2 * 26
    # every decoded position pays the logits head
    assert mm["flops"] == 2 * 4 * PARAMS_PER_TOKEN + 2 * 4 * 64 * 128


def test_mixed_dispatch_splits_weight_read_across_rows():
    cm = tiny_cost_model()
    out = cm.dispatch_cost([("prefill", 0, 8), ("decode", 10, 1)])
    mm_p = out["entries"][("q40", "matmul", "prefill")]
    mm_d = out["entries"][("q40", "matmul", "decode")]
    assert mm_p["bytes"] == mm_d["bytes"] == W_READ_Q40 / 2
    assert out["per_row"][0]["hbm_bytes"] == W_READ_Q40 / 2 + 8192
    # row totals and entry totals agree
    assert sum(r["flops"] for r in out["per_row"]) == out["flops"]


def test_q8_and_dense_codec_bytes():
    q8 = tiny_cost_model(weight_codec="q8")
    assert q8.weight_read_bytes() == (61440 // 32 + 8192 // 32) * 34
    dense = tiny_cost_model(weight_codec="dense", weight_el_bytes=2)
    assert dense.weight_read_bytes() == (61440 + 8192) * 2


# --- peaks and tracker ----------------------------------------------------

def test_peaks_env_override_and_tpu_table(monkeypatch):
    monkeypatch.setenv("DLLAMA_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("DLLAMA_PEAK_BYTES_S", "1e11")
    obs_cost.reset()
    p = obs_cost.peaks()
    assert p["source"] == "env" and p["flops"] == 1e12
    assert p["bytes_per_s"] == 1e11
    monkeypatch.delenv("DLLAMA_PEAK_FLOPS")
    monkeypatch.delenv("DLLAMA_PEAK_BYTES_S")
    obs_cost.set_backend("TPU v5 lite", "tpu")
    p = obs_cost.peaks()
    assert p["source"] == "table"
    assert p["flops"] == 197e12 and p["bytes_per_s"] == 819e9
    obs_cost.set_backend(None, None)
    obs_cost.reset()


def test_tracker_mfu_mbu_ratio(monkeypatch):
    monkeypatch.setenv("DLLAMA_PEAK_FLOPS", "1e9")
    monkeypatch.setenv("DLLAMA_PEAK_BYTES_S", "1e9")
    obs_cost.reset()
    tr = obs_cost.PerfTracker()
    # 5e8 FLOPs + 2.5e8 bytes over 1000 ms against 1e9/s peaks
    tr.note(5e8, 2.5e8, 1000.0)
    assert tr.mfu() == pytest.approx(0.5)
    assert tr.mbu() == pytest.approx(0.25)
    snap = tr.snapshot()
    assert snap["flops_total"] == 5e8 and snap["chip_wall_ms"] == 1000.0
    obs_cost.reset()


# --- scheduler attribution e2e --------------------------------------------

@pytest.fixture
def clean_obs(monkeypatch):
    # deterministic peaks: MFU/MBU must be computable without the CPU
    # microbenchmark's noise
    monkeypatch.setenv("DLLAMA_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("DLLAMA_PEAK_BYTES_S", "1e11")
    obs_dispatch.reset()
    obs_flight.clear()
    obs_metrics.SCHED_STEP_TIME_MS.reset()
    obs_cost.reset()
    yield
    obs_dispatch.reset()
    obs_flight.clear()
    obs_metrics.SCHED_STEP_TIME_MS.reset()
    obs_cost.reset()


def _run_staggered(slots=4, max_new=24):
    import jax

    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.runtime.scheduler import SlotScheduler
    import time as _time

    from dllama_tpu.obs.log import request_id_var

    cfg = tiny_config(seq_len=64)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=slots)
    sched = SlotScheduler(eng, prefill_chunk=4, max_wait_ms=20.0,
                          decode_burst=4)
    prompts = [[5, 9, 2], [7, 3, 11, 4, 6], [2, 4, 6], [9, 8, 7, 6]]
    rids = [f"cost-e2e-{i}" for i in range(slots)]

    def run(i, delay):
        _time.sleep(delay)
        # the submitting thread's request id rides the ticket into the
        # flight record (same seam the HTTP handler uses)
        request_id_var.set(rids[i])
        t = sched.submit(prompts[i], max_new)
        for _ in t.tokens():
            pass

    ths = [threading.Thread(target=run, args=(i, 0.03 * i))
           for i in range(slots)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    sched.close()
    return rids


def test_scheduler_attribution_e2e(clean_obs):
    rids = _run_staggered()

    # every request's flight record gained a full cost block
    costs = {}
    for rid in rids:
        rec = obs_flight.get(rid)
        assert rec is not None and "cost" in rec, rid
        for k in ("chip_ms", "flops", "hbm_bytes", "kv_page_ms"):
            assert k in rec["cost"]
        assert rec["cost"]["flops"] > 0 and rec["cost"]["chip_ms"] > 0
        costs[rid] = rec["cost"]

    # ledger counters hold exactly what the tracker accumulated
    # (json keys are "codec/path/phase")
    snap = obs_cost.TRACKER.snapshot()
    flops_by_key = obs_metrics.DISPATCH_FLOPS.json_value()
    bytes_by_key = obs_metrics.DISPATCH_BYTES.json_value()
    ledger_flops = sum(flops_by_key.values())
    assert ledger_flops == pytest.approx(snap["flops_total"], rel=1e-9)
    ledger_hbm = sum(v for k, v in bytes_by_key.items()
                     if k.split("/")[1] != "tp-ring")
    assert ledger_hbm == pytest.approx(snap["hbm_bytes_total"], rel=1e-9)
    # tp=1: no ring entries at all
    assert not any(k.split("/")[1] == "tp-ring" for k in bytes_by_key)
    # phases seen: both prefill and decode attributed
    phases = {k.split("/")[2] for k in flops_by_key}
    assert {"prefill", "decode"} <= phases

    # per-request chip_ms telescopes to the busy goodput component
    comp = obs_metrics.SCHED_STEP_TIME_MS.json_value()
    busy = comp.get("prefill", 0.0) + comp.get("decode", 0.0)
    attributed = sum(c["chip_ms"] for c in costs.values())
    assert busy > 0
    assert attributed == pytest.approx(busy, rel=0.05)

    # per-class chip time saw the same milliseconds (default class)
    by_class = obs_metrics.CLASS_CHIP_MS.json_value()
    assert sum(by_class.values()) == pytest.approx(attributed, rel=0.05)
    assert "standard" in by_class

    # MFU/MBU gauges set and present in BOTH expositions
    assert obs_metrics.MFU.value > 0 and obs_metrics.MBU.value > 0
    js = obs_metrics.snapshot_json()
    assert js["mfu"] > 0 and js["mbu"] > 0
    txt = obs_metrics.render_prometheus()
    assert "dllama_mfu" in txt and "dllama_mbu" in txt
    assert "dllama_dispatch_flops_total" in txt
    assert "dllama_class_chip_ms_total" in txt

    # /health perf block carries the same summary
    perf = obs_cost.summary()
    assert perf["flops_total"] == snap["flops_total"]
    assert perf["mfu"] is not None and perf["peaks"]["source"] == "env"
    assert perf["chip_ms_by_class"]


@pytest.mark.parametrize("shape", ["toy", "published"])
def test_a_looped_model_streams_and_caches_once_a_pass(shape, clean_obs):
    """A looped model (Ouro) runs its weight sets ``n_loops`` times a token:
    ``n_loops`` x the parameters a token, and a cached position holds a plane a
    (pass, layer): 1,572,864 B for Ouro-2.6B in bfloat16."""
    if shape == "published":
        kw = dict(dim=2048, hidden_dim=5632, n_layers=48, n_heads=16,
                  n_kv_heads=16, vocab_size=49152, kv_codec="kv_bfloat16",
                  kv_el_bytes=2)
        loops, per_layer, kv_pos = 4, 4 * 2048 * 2048 + 3 * 2048 * 5632, 2 * 2048 * 2
    else:
        kw, loops, per_layer, kv_pos = {}, 3, PARAMS_PER_TOKEN // 2, KV_POS_F32
    once, looped = tiny_cost_model(**kw), tiny_cost_model(n_loops=loops, **kw)
    layers = once.n_layers
    assert looped.params_per_token == loops * layers * per_layer \
        == loops * once.params_per_token
    assert looped.kv_write_bytes(1) == loops * layers * kv_pos
    assert looped.weight_read_bytes() - once.weight_read_bytes() \
        == (loops - 1) * once.codec_bytes(once.params_per_token)
    assert looped.kv_read_bytes(9, 1, True) == loops * once.kv_read_bytes(9, 1, True)
    assert looped.attn_flops(9, 1) == loops * once.attn_flops(9, 1)
    assert looped.logit_flops(1) == once.logit_flops(1)      # the head runs once
    if shape == "published":
        assert looped.kv_write_bytes(1) == 1_572_864
        assert looped.params_per_token == 4 * 2_466_250_752
        return
    # the engine's own model and gauge agree with it
    import jax

    from dllama_tpu.models.config import tiny_ouro
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_ouro()
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    cm = obs_cost.model_from_engine(eng)
    assert (cm.n_loops, cm.n_layers) == (3, 9)
    assert cm.kv_write_bytes(1) == eng.kv_bytes_per_token \
        == obs_metrics.KV_BYTES_PER_TOKEN.json_value() == 9 * 2 * cfg.kv_dim * 4


def test_model_from_engine_sniffs_codecs(clean_obs):
    import jax

    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_config(seq_len=64)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=2)
    cm = obs_cost.model_from_engine(eng)
    assert cm is not None
    assert cm.params_per_token == PARAMS_PER_TOKEN
    assert cm.tp == 1 and not cm.paged
    # an unmodelable engine degrades to None, never raises
    assert obs_cost.model_from_engine(object()) is None


@pytest.mark.parametrize("shape", ["toy", "published"])
def test_a_state_space_mixer_adds_depth_free_bytes_beside_the_kv(shape, clean_obs):
    """A mixer beside attention (Falcon-H1): its two projections join the
    parameters a token, a row's pass reads its state and rings once a layer
    whatever the context's depth, and the same block's keys and values still grow
    with it: 36,864 B a position and 75.5 MB of state a slot at the cell's 18
    blocks."""
    if shape == "published":
        kw = dict(dim=5120, hidden_dim=21504, n_layers=18, n_heads=20,
                  n_kv_heads=4, head_dim=128, vocab_size=261120,
                  kv_codec="kv_bfloat16", kv_el_bytes=2)
        ssm = dict(heads=32, head_dim=128, state=256, groups=2, ring=128)
    else:
        kw, ssm = {}, dict(heads=4, head_dim=16, state=24, groups=2, ring=128)
    plain, mixed = tiny_cost_model(**kw), tiny_cost_model(ssm=ssm, **kw)
    layers, dim = plain.n_layers, plain.dim
    inner, bc = ssm["heads"] * ssm["head_dim"], 2 * ssm["groups"] * ssm["state"]
    assert mixed.params_per_token - plain.params_per_token \
        == layers * (dim * (2 * inner + bc + ssm["heads"]) + inner * dim)
    state = ssm["heads"] * ssm["state"] * ssm["head_dim"] * 4
    ring = 128 * ((inner + bc // 2) * mixed.kv_el_bytes + 4 * ssm["heads"])
    assert mixed.state_read_bytes(1) == layers * (state + ring)
    assert plain.state_read_bytes(1) == plain.state_flops(5) == 0
    # depth-free: the same at every position; the KV is not
    assert mixed.row_cost("decode", 900, 1)["kv_bytes"] \
        - mixed.row_cost("decode", 9, 1)["kv_bytes"] \
        == plain.kv_read_bytes(900, 1, True) - plain.kv_read_bytes(9, 1, True) > 0
    assert mixed.row_cost("decode", 9, 3)["kv_bytes"] \
        - plain.row_cost("decode", 9, 3)["kv_bytes"] == 3 * layers * (state + ring)
    assert mixed.row_cost("prefill", 0, 16)["kv_bytes"] \
        - plain.row_cost("prefill", 0, 16)["kv_bytes"] == layers * (state + ring)
    assert mixed.kv_write_bytes(1) == plain.kv_write_bytes(1)
    if shape == "published":
        assert mixed.kv_write_bytes(1) == 36_864 and layers * state == 75_497_472
        assert mixed.params_per_token == 18 * (429_916_160 + 32 * 5120)
        return
    import jax

    from dllama_tpu.models.config import tiny_falcon_h1
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    cfg = tiny_falcon_h1()
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    cm = obs_cost.model_from_engine(eng)
    assert cm.ssm == dict(heads=4, head_dim=16, state=24, groups=2, ring=128)
    assert cm.kv_write_bytes(1) == eng.kv_bytes_per_token \
        == obs_metrics.KV_BYTES_PER_TOKEN.json_value() == 3 * 2 * cfg.kv_dim * 4
    assert cm.state_read_bytes(1) == sum(
        int(a.nbytes) for n, a in eng.cache.planes().items()
        if n in ("rs", "rk", "rv", "rg"))


def test_a_mixer_as_a_layer_kind_counts_each_kind_by_its_own_depth(clean_obs):
    """Granite-4.0-H: a layer has a mixer OR attention.  The mixer layers bring
    their projections and a state read a pass, the attention layers their
    projections and the keys and values, every layer its token's experts and
    the shared MLP: 8,192 B a cached position over the cell's 2 attention
    layers and 75.5 MB of state matrices a slot over its 18 mixer layers."""
    import jax

    from dllama_tpu.models.config import tiny_granite_hybrid
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    ssm = dict(heads=128, head_dim=64, state=128, groups=1, ring=128)
    cm = tiny_cost_model(
        dim=4096, hidden_dim=1536, n_layers=20, n_heads=32, n_kv_heads=8,
        head_dim=128, vocab_size=100352, kv_codec="kv_bfloat16", kv_el_bytes=2,
        n_experts=72, n_active_experts=10, moe_hidden_dim=768,
        n_shared_experts=2, ssm=ssm, n_ssm_layers=18)
    att = 2 * 4096 * 4096 + 2 * 4096 * 1024
    mixer = 4096 * (2 * 8192 + 256 + 128) + 8192 * 4096
    assert (cm.n_kv_layers, cm.n_ssm_layers) == (2, 18)
    assert cm.params_per_token == 2 * att + 18 * mixer + 20 * 3 * 4096 * 768 * 12
    assert cm.kv_write_bytes(1) == 8_192
    state = 128 * 128 * 64 * 4
    ring = 128 * ((8192 + 128) * 2 + 4 * 128)
    assert cm.state_read_bytes(1) == 18 * (state + ring) and 18 * state == 75_497_472
    assert cm.row_cost("decode", 900, 1)["kv_bytes"] \
        - cm.row_cost("decode", 9, 1)["kv_bytes"] == 2 * (900 - 9) * 4_096
    cfg = tiny_granite_hybrid()
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=1)
    cm = obs_cost.model_from_engine(eng)
    assert (cm.n_kv_layers, cm.n_ssm_layers) == (2, 8)
    assert cm.kv_write_bytes(1) == eng.kv_bytes_per_token \
        == obs_metrics.KV_BYTES_PER_TOKEN.json_value() == 2 * 2 * cfg.kv_dim * 4
    assert cm.state_read_bytes(1) == sum(
        int(a.nbytes) for n, a in eng.cache.planes().items()
        if n in ("rs", "rk", "rv", "rg"))
