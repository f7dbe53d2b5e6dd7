"""The stdlib reader of event metadata (``harness/xmeta.py``) and the table
built on it (``layer_metrics/_scopes.py``), on the traces recorded on the v5e
(``fixtures/``, by ``tools/record_fixture.py`` and
``tools/record_scoped_fixture.py``) and on made-up spans."""

import glob
import json
import os
import re

import pytest

import _scopes
from conftest import BENCH, ROOT
from harness import xmeta, xplane

TINY = sorted(glob.glob(os.path.join(BENCH, "fixtures", "tiny-*.xplane.pb")))
SCOPED = os.path.join(BENCH, "fixtures", "scoped-1chip.xplane.pb")


def test_wire_walk_on_made_up_bytes():
    # field 1 varint 300, field 2 bytes "ab", field 4 int64 -1 (ten bytes)
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x20] + [0xFF] * 9 + [0x01])
    got = [(f, w, v if isinstance(v, int) else bytes(v))
           for f, w, v in xmeta.fields(memoryview(msg))]
    assert got == [(1, 0, 300), (2, 2, b"ab"), (4, 0, (1 << 64) - 1)]
    assert xmeta._stat(memoryview(bytes([0x08, 0x07, 0x20] + [0xFF] * 9 + [0x01])),
                       {7: "seq"}) == ("seq", -1)
    assert xmeta.module_name("jit_step(123)") == ("jit_step", 123)
    assert xmeta.scope_of("jit(f)/while/body/kv_write/scatter", _scopes.SCOPES) \
        == "kv_write"
    assert xmeta.scope_of("jit(f)/moe/w2/dot_general:", _scopes.SCOPES) == "w2"
    assert xmeta.scope_of(None, _scopes.SCOPES) == "unscoped"
    assert xmeta.scope_of("jit(f)/attention/dot", _scopes.SCOPES) == "unscoped"


def test_the_yardsticks_scope_set_is_the_programs():
    with open(os.path.join(ROOT, "dllama_tpu", "ops", "scopes.py")) as f:
        src = f.read()
    body = re.search(r"^SCOPES = \((.*?)^\)", src, re.M | re.S).group(1)
    assert tuple(re.findall(r'^\s*"(\w+)",', body, re.M)) == _scopes.SCOPES


@pytest.mark.parametrize("path", TINY, ids=os.path.basename)
def test_metadata_of_the_recorded_trace(path):
    tr = xmeta.load(path)
    red = xplane.reduce(xplane.load(path))
    assert len(tr["devices"]) == red["chips"]
    n = len(tr["devices"])
    own = sum(o for dev in tr["devices"].values()
              for _, o in xmeta.own_times(dev)) / 1e9 / n
    assert own == pytest.approx(red["busy_s"], rel=1e-9)
    dev = tr["devices"]["/device:TPU:0"]
    by_display = {m["display"]: m for m in dev["meta"].values()}
    assert by_display["fusion"]["tf_op"] == "jit(fusion)/dot_general:" \
        or n > 1  # the 4-chip program has a second fusion (its slice)
    tf_ops = {m.get("tf_op") for m in dev["meta"].values()}
    assert "jit(fusion)/dot_general:" in tf_ops
    assert "jit(pallas_double)/pallas_call:" in tf_ops
    kernel = by_display["pallas_double.1"]
    assert kernel["hlo_category"] == "custom-call"
    assert kernel["source"].endswith("record_fixture.py:32")
    for name in ("copy-start", "copy-done"):
        assert "tf_op" not in by_display[name]
        assert xmeta.scope_of(by_display[name].get("tf_op"),
                              _scopes.SCOPES) == "unscoped"
        assert by_display[name]["bytes_accessed"] > 0
    runs = [xmeta.module_name(m[0])[0] for m in dev["modules"]]
    assert runs.count("jit_pallas_double") == 3 and runs.count("jit_fusion") == 3
    if n == 1:
        assert len(runs) == 6
    pids = {xmeta.module_name(m[0])[1] for m in dev["modules"]}
    assert {m["program_id"] for m in dev["meta"].values()} <= pids
    # host events keep their keyword stats; a filter keeps only what is asked
    done = [e for e in tr["host"] if e[1] == "CompleteCallbacks"]
    assert done and all("run_id" in e[4] for e in done)
    only = xmeta.load(path, keep_host=lambda name: name == "CompleteCallbacks")
    assert {e[1] for e in only["host"]} == {"CompleteCallbacks"}
    # no program scope in these programs: everything is unscoped, no step
    tab = _scopes.build(xmeta.load(path, keep_host=_scopes.is_span))
    assert set(tab["scopes"]) == {"unscoped"} and tab["steps"] == 0
    assert tab["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert tab["scopes"]["unscoped"]["share_pct"] == pytest.approx(100.0)
    assert not _scopes.scoped(tab) and tab["idle_in_span_s"] == {}


def test_innermost_span_and_idle_split_on_made_up_spans():
    spans = [(0.0, 100.0, "api.request"), (10.0, 40.0, "sched.enqueue"),
             (20.0, 30.0, "engine.slot_enqueue"), (50.0, 90.0, "sched.land_wait"),
             (60.0, 62.0, "api.emit")]  # a handler thread beside the wait
    seg = _scopes.innermost_segments(spans)
    assert seg == [(0.0, 10.0, "api.request"), (10.0, 20.0, "sched.enqueue"),
                   (20.0, 30.0, "engine.slot_enqueue"),
                   (30.0, 40.0, "sched.enqueue"), (40.0, 50.0, "api.request"),
                   (50.0, 90.0, "sched.land_wait"), (90.0, 100.0, "api.request")]
    idle = _scopes.idle_by_span([(5.0, 25.0), (85.0, 120.0)], seg)
    assert idle == {"api.request": 5.0 + 10.0, "sched.enqueue": 10.0,
                    "engine.slot_enqueue": 5.0, "sched.land_wait": 5.0}
    assert _scopes.innermost_segments([]) == []


def test_scope_time_and_idle_in_span_on_the_scoped_trace():
    with open(SCOPED.replace(".xplane.pb", ".expected.json")) as f:
        want = json.load(f)
    assert os.path.getsize(SCOPED) == want["bytes"] <= 100 * 1024
    tr = xmeta.load(SCOPED, keep_host=_scopes.is_span)
    dev = tr["devices"]["/device:TPU:0"]
    by_scope = {}
    for m in dev["meta"].values():
        by_scope.setdefault(xmeta.scope_of(m.get("tf_op"), _scopes.SCOPES),
                            []).append(m)
    assert {"w13", "attn"} <= set(by_scope)
    (kernel,) = [m for m in by_scope["w13"] if m["hlo_category"] == "custom-call"]
    assert kernel["display"].startswith("q40_mm")          # the kernel's name=
    assert kernel["tf_op"].startswith("jit(step)/w13/")
    assert any(m["tf_op"].startswith("jit(step)/attn/") for m in by_scope["attn"])
    runs = [xmeta.module_name(m[0])[0] for m in dev["modules"]]
    assert runs == ["jit_step"] * want["launches"]
    # the annotations, with their arguments
    enq = sorted((e for e in tr["host"] if e[1] == "sched.enqueue"),
                 key=lambda e: e[2])
    assert [e[4] for e in enq] == [{"seq": i, "rows": 2, "rids": "a;b"}
                                   for i in range(want["annotated"])]
    assert {e[1] for e in tr["host"]} == {"sched.enqueue", "sched.land_wait",
                                          "sched.idle"}
    tab = _scopes.build(tr)
    red = xplane.reduce(xplane.load(SCOPED))
    assert tab["steps"] == want["annotated"] and _scopes.scoped(tab)
    assert tab["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert sum(r["s"] for r in tab["scopes"].values()) == \
        pytest.approx(tab["busy_s"], rel=1e-6)
    assert tab["scopes"]["w13"]["s"] > 0 and tab["scopes"]["attn"]["s"] > 0
    assert tab["scopes"]["w13"]["runs"] >= want["launches"]
    assert sum(r["share_pct"] for r in tab["scopes"].values()) == \
        pytest.approx(100.0, rel=1e-6)
    # each 20 ms sleep sits in sched.idle; the 10 ms sleep in no span
    idle = tab["idle_in_span_s"]
    assert idle["sched.idle"] >= 0.95 * want["annotated"] * want["sleeps_s"]
    bare = tab["idle_s"] - sum(idle.values())
    assert 0.9 * want["bare_s"] <= bare <= want["bare_s"] + 0.01
    assert (tab["programs"].keys() and
            all(k.startswith("jit_step(") for k in tab["programs"]))
    assert tab["unscoped_ops"] == [o for o in tab["ops"]
                                   if o["scope"] == "unscoped"][:20]


def test_readers_return_nothing_without_a_device_plane(tmp_path, monkeypatch):
    """A rehearsal's trace has no TPU plane: ``xplane.reduce`` says 0 chips and
    every reader built on the table gives ``None``."""
    import importlib
    ctx = {"trace": {"chips": 0, "busy_s": 0.0}, "cell": {}, "records": [],
           "traced_window": (0.0, 1.0), "peaks": None,
           "config": {"hidden_size": 8, "intermediate_size": 16,
                      "num_hidden_layers": 2}, "chips": 1,
           "before": {}, "after": {}}
    for name in ("serve_kv_write_ms_per_step", "serve_attn_ms_per_step",
                 "serve_matmul_ms_per_step", "serve_unscoped_pct",
                 "serve_idle_in_span_pct", "w13_roof_pct", "w2_roof_pct",
                 "attn_ms_per_tok", "unscoped_pct", "idle_in_span_pct",
                 "prefill_span_p50_ms", "serve_mixed_step_share_pct",
                 "serve_mixed_step_ms", "serve_queue_wait_ms"):
        assert importlib.import_module(name).read(ctx) is None, name


def test_counter_readers_on_made_up_metrics():
    import importlib
    ctx = {"before": {"sched_steps": {"decode": 10, "mixed": 2},
                      "sched_step_wall_ms": {"decode": 1500.0, "mixed": 500.0},
                      "queue_wait_seconds": {"count": 4, "sum": 2.0}},
           "after": {"sched_steps": {"decode": 40, "mixed": 12, "verify": 0},
                     "sched_step_wall_ms": {"decode": 6000.0, "mixed": 3000.0},
                     "queue_wait_seconds": {"count": 14, "sum": 32.0}}}
    read = lambda name: importlib.import_module(name).read(ctx)  # noqa: E731
    assert read("serve_mixed_step_share_pct") == pytest.approx(25.0)
    assert read("serve_mixed_step_ms") == pytest.approx(250.0)
    assert read("serve_queue_wait_ms") == pytest.approx(3000.0)
