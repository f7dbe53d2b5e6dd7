"""Flight recorder, slot timeline, and SLO engine tests (PR 7 tentpole:
obs/flight.py + obs/slo.py + the /debug/requests | /debug/timeline
endpoints, docs/OBSERVABILITY.md).

The acceptance contract pinned here:

* a streamed request served by the slot scheduler yields a COMPLETE
  flight record under its client-supplied ``X-Request-Id`` — queue wait,
  admit slot, every prefill chunk and decode burst, retire reason, and a
  ``ttft_s`` that agrees exactly with the TTFT histogram (both are fed
  the same observed value);
* ``/debug/requests`` lists recent records newest-first and an unknown
  ID is a 404, not an empty 200;
* ``/debug/timeline`` exposes the per-dispatch slot phases and the
  goodput decomposition, and ``tools/trace_dump.py --slots`` renders one
  named Perfetto track per scheduler slot from it;
* the flight ring evicts oldest-first at capacity and the SLO engine's
  burn-rate math, verdict transitions, and violation counter follow the
  documented multiwindow semantics.
"""

import json
import logging
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from fixtures import REPO, cpu_env, free_port, write_tiny_model, \
    write_tiny_tokenizer

from dllama_tpu.obs import flight as obs_flight, metrics as obs_metrics, \
    slo as obs_slo, trace as obs_trace

pytestmark = pytest.mark.obs


# --- FlightRecorder unit tests (no server, no jax) ------------------------

def test_flight_ring_evicts_oldest_first():
    fr = obs_flight.FlightRecorder(capacity=3)
    for i in range(5):
        fr.submit(f"r{i}", n_prompt=i)
    assert len(fr) == 3
    assert fr.get("r0") is None and fr.get("r1") is None
    assert [r["request_id"] for r in fr.recent(10)] == ["r4", "r3", "r2"]


def test_flight_submit_merges_and_first_retire_reason_wins():
    fr = obs_flight.FlightRecorder(capacity=8)
    fr.submit("a", path="/v1/completions")          # server handler first
    fr.submit("a", n_prompt=7, source="scheduler")  # scheduler merges in
    fr.admit("a", slot=2, queued_ms=1.5)
    fr.phase("a", "prefill_chunk", tokens=4, ms=3.0)
    fr.phase("a", "decode_burst", steps=2, tokens=2, wall_ms=1.0)
    fr.first_token("a", 0.25)
    fr.inter_token("a", 0.01)
    fr.inter_token("a", 0.03)
    fr.retire("a", "length", produced=3)
    fr.retire("a", "served")                        # handler fallback loses
    rec = fr.get("a")
    assert rec["path"] == "/v1/completions" and rec["n_prompt"] == 7
    assert rec["slot"] == 2 and rec["queued_ms"] == 1.5
    assert [p["kind"] for p in rec["phases"]] == ["prefill_chunk",
                                                  "decode_burst"]
    assert rec["finish"] == "length" and rec["produced"] == 3
    assert rec["ttft_s"] == 0.25
    assert rec["itl"]["count"] == 2
    assert rec["itl"]["avg_s"] == pytest.approx(0.02)
    assert rec["itl"]["max_s"] == 0.03
    assert "degrade_base" not in rec  # internal baseline never exposed


def test_flight_reused_id_starts_fresh_record():
    fr = obs_flight.FlightRecorder(capacity=8)
    fr.submit("dup", n_prompt=3)
    fr.retire("dup", "stop", produced=5)
    fr.submit("dup", n_prompt=9)  # client recycled the ID after retire
    rec = fr.get("dup")
    assert "finish" not in rec and rec["n_prompt"] == 9
    assert len(fr) == 1


def test_flight_resize_keeps_most_recent():
    fr = obs_flight.FlightRecorder(capacity=8)
    for i in range(6):
        fr.submit(f"k{i}")
    fr.resize(2)
    assert len(fr) == 2 and fr.get("k5") is not None and fr.get("k4") is not None


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_buffer_env_malformed_warns_once(monkeypatch):
    """Satellite: a malformed DLLAMA_FLIGHT_BUFFER/DLLAMA_TRACE_BUFFER
    warns ONCE per distinct spec and falls back to the default."""
    h = _Capture()
    logger = logging.getLogger("dllama.obs.trace")
    logger.addHandler(h)
    try:
        monkeypatch.setattr(obs_trace, "_warned_specs", set())
        monkeypatch.setenv("DLLAMA_FLIGHT_BUFFER", "banana")
        for _ in range(3):
            assert obs_trace.parse_buffer_env(
                "DLLAMA_FLIGHT_BUFFER",
                obs_flight.DEFAULT_FLIGHT_CAPACITY) == \
                obs_flight.DEFAULT_FLIGHT_CAPACITY
        warns = [r for r in h.records if "DLLAMA_FLIGHT_BUFFER" in
                 r.getMessage()]
        assert len(warns) == 1, [r.getMessage() for r in h.records]
        # a negative capacity is just as malformed
        monkeypatch.setenv("DLLAMA_TRACE_BUFFER", "-5")
        assert obs_trace.parse_buffer_env(
            "DLLAMA_TRACE_BUFFER", obs_trace.DEFAULT_CAPACITY) == \
            obs_trace.DEFAULT_CAPACITY
    finally:
        logger.removeHandler(h)


def test_buffer_env_has_one_name(monkeypatch):
    """``DLLAMA_TRACE_BUFFER`` is the ring's one variable: the old
    ``DLLAMA_TRACE_CAPACITY`` alias is gone and is not read."""
    monkeypatch.delenv("DLLAMA_TRACE_BUFFER", raising=False)
    monkeypatch.setenv("DLLAMA_TRACE_CAPACITY", "123")
    assert obs_trace._capacity() == obs_trace.DEFAULT_CAPACITY
    monkeypatch.setenv("DLLAMA_TRACE_BUFFER", "456")
    assert obs_trace._capacity() == 456


# --- SLO engine unit tests (no server, no jax) ----------------------------

@pytest.mark.parametrize("spec", [
    "", "ttft_p95", "nonsense_p95=100ms", "ttft_p95=purple",
    "ttft_p0=100ms", "ttft_p100=100ms", "error_rate=150%",
    "ttft_p95=100ms,ttft_p95=200ms",
])
def test_slo_parse_rejects_bad_specs(spec):
    with pytest.raises(ValueError):
        obs_slo.parse_slo(spec)


def test_slo_parse_grammar():
    objs = obs_slo.parse_slo("ttft_p95=1500ms,itl_p99=0.12s,error_rate=0.5%")
    by_key = {o.key: o for o in objs}
    assert by_key["ttft_p95"].allowed == pytest.approx(0.05)
    assert by_key["ttft_p95"].threshold == pytest.approx(1.5)
    # thresholds resolve to the next bucket boundary at or above target
    assert by_key["ttft_p95"].boundary == 2.5
    assert by_key["itl_p99"].threshold == pytest.approx(0.12)
    assert by_key["itl_p99"].boundary == 0.25
    assert by_key["error_rate"].allowed == pytest.approx(0.005)
    assert obs_slo.parse_windows("1h,5m") == [("5m", 300.0), ("1h", 3600.0)]
    with pytest.raises(ValueError):
        obs_slo.parse_windows("5parsecs")


def test_slo_burn_verdicts_and_violation_transitions():
    """Multiwindow burn math on a private histogram with injected time:
    violating needs ALL windows burning; the violations counter bumps on
    the TRANSITION into violating only; recovery walks back through
    at-risk to ok as the bad observations age out of the windows."""
    h = obs_metrics.Histogram("t_slo_lat", "t_slo_lat", (0.1, 1.0))
    obj = obs_slo.Objective("uttft_p90", kind="latency", allowed=0.1,
                            target_display="500ms", hist=h, threshold=0.5)
    assert obj.boundary == 1.0
    eng = obs_slo.SloEngine([obj], obs_slo.parse_windows("10s,100s"))
    t = 1000.0
    assert eng.evaluate(now=t)["status"] == "ok"  # no traffic yet

    for _ in range(10):
        h.observe(2.0)  # every request blows the 1.0s boundary
    res = eng.evaluate(now=t + 1)
    burns = res["objectives"]["uttft_p90"]["burn"]
    assert burns == {"10s": 10.0, "100s": 10.0}  # (10/10)/0.1
    assert res["status"] == "violating"
    viol = obs_metrics.SLO_VIOLATIONS.json_value().get("uttft_p90", 0)
    assert viol >= 1
    assert eng.evaluate(now=t + 2)["status"] == "violating"
    # still violating: the counter must NOT bump again
    assert obs_metrics.SLO_VIOLATIONS.json_value()["uttft_p90"] == viol
    # gauges carry the per-window burns
    assert obs_metrics.SLO_BURN_RATE.get("uttft_p90", "10s") >= 1.0

    for _ in range(5):
        h.observe(0.05)  # recovery traffic, all good
    res = eng.evaluate(now=t + 15)
    burns = res["objectives"]["uttft_p90"]["burn"]
    # short window sees only the clean tail; long window still burns
    assert burns["10s"] == 0.0 and burns["100s"] >= 1.0
    assert res["status"] == "at_risk"

    for _ in range(95):
        h.observe(0.05)
    res = eng.evaluate(now=t + 16)
    assert res["status"] == "ok"
    assert obs_metrics.SLO_VIOLATIONS.json_value()["uttft_p90"] == viol


def test_slo_summary_line_names_every_objective():
    h = obs_metrics.Histogram("t_slo_sum", "t_slo_sum", (0.1, 1.0))
    obj = obs_slo.Objective("usum_p90", kind="latency", allowed=0.1,
                            target_display="500ms", hist=h, threshold=0.5)
    line = obs_slo.SloEngine(
        [obj], obs_slo.parse_windows("10s,100s")).summary_line()
    assert "slo:" in line and "usum_p90<=500ms" in line
    assert "10s/100s" in line


# --- end-to-end: scheduler-served streamed request over HTTP --------------

@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("flight")
    m, t = str(d / "tiny.m"), str(d / "tiny.t")
    write_tiny_model(m)
    write_tiny_tokenizer(t)
    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dllama_tpu.server.api", "--model", m,
         "--tokenizer", t, "--port", str(port), "--temperature", "0",
         "--max-seq-len", "128", "--batch-slots", "2",
         "--slo", "ttft_p95=30s,error_rate=1%"],
        cwd=REPO, env=cpu_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    base = f"http://127.0.0.1:{port}"
    for _ in range(600):
        if proc.poll() is not None:
            raise RuntimeError(f"server died:\n{proc.stdout.read()}")
        try:
            urllib.request.urlopen(base + "/health", timeout=1)
            break
        except OSError:
            time.sleep(0.2)
    else:
        proc.kill()
        raise RuntimeError("server did not come up")
    yield base
    proc.kill()
    proc.wait()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


def _post(base, path, body, headers=None, timeout=240):
    req = urllib.request.Request(
        base + path, json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=timeout)


def test_streamed_scheduler_request_full_flight_record(server):
    """Acceptance: one streamed request through the slot scheduler →
    /debug/requests/<id> holds every lifecycle phase, and the record's
    ttft_s/itl agree with the latency histograms (same observed values
    by construction)."""
    rid = "flight-stream-1"
    before = _get(server, "/metrics")
    with _post(server, "/v1/completions",
               {"prompt": "hello flight", "max_tokens": 8, "stream": True},
               headers={"X-Request-Id": rid}) as r:
        assert r.headers.get("X-Request-Id") == rid
        raw = r.read()
    assert b"[DONE]" in raw
    after = _get(server, "/metrics")

    rec = _get(server, f"/debug/requests/{rid}")
    assert rec["request_id"] == rid
    assert rec["path"] == "/v1/completions"
    assert rec["n_prompt"] >= 1 and rec["max_new"] == 8
    assert rec["source"] == "scheduler"
    assert isinstance(rec["slot"], int) and rec["queued_ms"] >= 0
    kinds = [p["kind"] for p in rec["phases"]]
    assert "prefill_chunk" in kinds and "decode_burst" in kinds
    assert kinds[0] == "prefill_chunk"  # prompt is fed before decode
    pre = [p for p in rec["phases"] if p["kind"] == "prefill_chunk"]
    assert sum(p["tokens"] for p in pre) == rec["n_prompt"]
    for p in rec["phases"]:
        assert (p.get("ms") or p.get("wall_ms")) >= 0
    bursts = [p for p in rec["phases"] if p["kind"] == "decode_burst"]
    emitted = sum(p["emitted"] for p in pre) + \
        sum(p["tokens"] for p in bursts)
    assert emitted == rec["produced"] >= 1
    assert rec["finish"] in ("length", "stop")
    assert "degraded" in rec and isinstance(rec["degrade_events"], dict)
    assert rec["duration_ms"] > 0

    # TTFT / ITL agreement with the histograms: the record stores the
    # exact values the serving layer observed
    d_ttft = after["ttft_seconds"]["sum"] - before["ttft_seconds"]["sum"]
    assert after["ttft_seconds"]["count"] - \
        before["ttft_seconds"]["count"] == 1
    assert rec["ttft_s"] == pytest.approx(d_ttft, abs=5e-6)
    d_itl = after["inter_token_seconds"]["sum"] - \
        before["inter_token_seconds"]["sum"]
    d_itl_n = after["inter_token_seconds"]["count"] - \
        before["inter_token_seconds"]["count"]
    assert rec["itl"]["count"] == d_itl_n >= 1
    assert rec["itl"]["sum_s"] == pytest.approx(d_itl, abs=5e-6)


def test_debug_requests_listing_and_unknown_404(server):
    rid = "flight-list-1"
    with _post(server, "/v1/completions",
               {"prompt": "hi", "max_tokens": 3},
               headers={"X-Request-Id": rid}) as r:
        json.loads(r.read())
    listing = _get(server, "/debug/requests")["requests"]
    assert any(e["request_id"] == rid for e in listing)
    assert listing[0]["request_id"] == rid  # newest first
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/debug/requests/no-such-request")
    assert ei.value.code == 404


def test_timeline_endpoint_phases_and_goodput(server):
    tl = _get(server, "/debug/timeline")
    assert tl["slots"] == 2
    assert tl["steps"], "scheduler traffic must populate the timeline"
    for step in tl["steps"]:
        assert step["wall_ms"] >= 0 and step["steps"] >= 1
        assert len(step["slots"]) == 2
        for s in step["slots"]:
            assert s["phase"] in ("prefill", "decode", "pad")
            if s["phase"] != "pad":
                assert s["request_id"]
    comp = tl["components_ms"]
    assert set(comp) <= {"prefill", "decode", "pad", "host_gap", "idle"}
    assert comp.get("prefill", 0) > 0 and comp.get("decode", 0) > 0
    assert 0 < tl["goodput_ratio"] <= 1


def test_health_slo_verdict_block(server):
    h = _get(server, "/health")
    assert h["slo"] is not None
    assert h["slo"]["status"] in ("ok", "at_risk", "violating")
    assert "ttft_p95" in h["slo"]["objectives"]
    assert "error_rate" in h["slo"]["objectives"]
    assert set(h["slo"]["windows"]) == {"5m", "1h"}


def test_trace_dump_slots_emits_named_track_per_slot(server, tmp_path):
    """Acceptance: the Perfetto export grows one NAMED track per
    scheduler slot, with events named by that slot's per-dispatch
    phase."""
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_dump.py"),
         server, "-o", out, "--slots"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "goodput" in r.stdout
    with open(out) as f:
        doc = json.load(f)
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("pid") == 2
             and e["name"] == "thread_name"}
    assert names == {"slot 0", "slot 1"}
    phases = {e["name"] for e in doc["traceEvents"]
              if e.get("ph") == "X" and e.get("pid") == 2}
    assert phases & {"prefill", "decode"}
    # request spans (pid 1) and slot tracks (pid 2) share one file
    assert any(e.get("pid") == 1 and e.get("ph") == "X"
               for e in doc["traceEvents"])
