"""Observability tests: metric registry, dual exposition, structured
logs with request IDs, and span tracing (docs/OBSERVABILITY.md).

The acceptance contract: one registry feeds both a Prometheus text 0.0.4
scrape and the backward-compatible ``/metrics`` JSON (a superset of every
pre-PR key); every HTTP response carries ``X-Request-Id`` and grepping
captured log records for that ID reconstructs the request's lifecycle
(accept → queue → prefill → decode → finish) including engine-side
records; ``/debug/trace`` (+ tools/trace_dump.py) emits Chrome
trace_event JSON with distinct queue-wait/prefill/decode-chunk spans.
"""

import importlib.util
import io
import json
import logging
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from fixtures import free_port, write_tiny_tokenizer

from dllama_tpu.obs import log as obs_log, metrics as obs_metrics, trace as obs_trace
from dllama_tpu.obs.metrics import Counter, Gauge, Histogram, Registry
from dllama_tpu.runtime.faults import FAULTS, injected

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every key the pre-registry /metrics JSON exported — the JSON path must
#: remain a superset of these forever (dashboards parse them)
PRE_PR_KEYS = {
    "uptime_s", "requests_served", "requests_rejected_429",
    "requests_rejected_503", "read_timeouts_408", "deadline_timeouts",
    "client_disconnects", "server_errors", "avg_request_s",
    "checksum_verified", "checksum_failures", "numeric_faults",
    "snapshot_restores",
}


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.clear()
    yield
    FAULTS.clear()


# --- unit: registry -------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("hits", "help text")
    g = reg.gauge("depth")
    h = reg.histogram("lat_seconds", (0.1, 1.0, 10.0))
    c.inc()
    c.inc(4)
    g.set(2.5)
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert c.value == 5 and c.name == "dllama_hits_total"
    assert g.value == 2.5 and g.name == "dllama_depth"
    hv = h.json_value()
    assert hv["count"] == 4 and hv["sum"] == pytest.approx(55.55)
    assert hv["buckets"] == {"0.1": 1, "1": 2, "10": 3, "+Inf": 4}
    reg.reset()
    assert c.value == 0 and g.value == 0.0 and h.count == 0


def test_registry_get_or_create_and_kind_collision():
    reg = Registry()
    a = reg.counter("x")
    assert reg.counter("x") is a          # same key → same object
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")                    # key exists as another kind
    j = reg.snapshot_json()
    assert j["schema_version"] == obs_metrics.SCHEMA_VERSION
    assert j["x"] == 0 and "uptime_s" in j


def test_boundary_values_land_in_le_buckets():
    """Prometheus ``le`` is less-or-EQUAL: an observation exactly on a
    bucket upper bound belongs in that bucket."""
    h = Histogram("dllama_b", "b", (1.0, 2.0))
    h.observe(1.0)
    h.observe(2.0)
    assert h.json_value()["buckets"] == {"1": 1, "2": 2, "+Inf": 2}


def _parse_prom(text):
    """Minimal Prometheus text-format parser: returns ({name: type},
    {name: [(labels, value)]}) and fails on any unparseable line."""
    types, samples = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, t = line.split(" ", 3)
            types[name] = t.strip()
        elif line.startswith("# HELP "):
            assert len(line.split(" ", 3)) == 4, f"bare HELP line: {line!r}"
        elif line.startswith("#"):
            pytest.fail(f"unknown comment line: {line!r}")
        else:
            m = re.match(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})? "
                         r"(-?(?:[0-9.eE+-]+|\+Inf))$", line)
            assert m, f"unparseable sample line: {line!r}"
            samples.setdefault(m.group(1), []).append(
                (m.group(2) or "", float(m.group(3).replace("+Inf", "inf"))))
    return types, samples


def _check_histogram_invariants(name, types, samples):
    base = name[: -len("_bucket")] if name.endswith("_bucket") else name
    buckets = samples[f"{base}_bucket"]
    les = [float(lbl[len('{le="'):-2].replace("+Inf", "inf"))
           for lbl, _ in buckets]
    counts = [v for _, v in buckets]
    assert les == sorted(les) and les[-1] == math.inf
    assert counts == sorted(counts), f"{base} buckets must be cumulative"
    (_, total_count), = samples[f"{base}_count"]
    assert counts[-1] == total_count, f"{base} +Inf bucket != count"
    assert f"{base}_sum" in samples


def test_prometheus_text_parses_with_invariants():
    obs_metrics.TTFT.observe(0.3)
    obs_metrics.REQUESTS_SERVED.inc(0)  # present even at zero
    text = obs_metrics.render_prometheus()
    types, samples = _parse_prom(text)
    # counters end _total, gauges/histograms don't; HELP+TYPE present
    assert types["dllama_requests_served_total"] == "counter"
    assert types["dllama_uptime_seconds"] == "gauge"
    assert types["dllama_ttft_seconds"] == "histogram"
    for name, t in types.items():
        if t == "histogram":
            _check_histogram_invariants(name, types, samples)
            continue
        rows = samples.get(name, [])
        if rows and rows[0][0] == "":
            # plain scalar family: exactly one unlabeled sample
            assert len(rows) == 1, name
        else:
            # labeled family (matmul_dispatch, q40_degrade, hbm gauges):
            # zero samples until first touch, then one per distinct label
            # set — duplicates would make scrapers sum silently
            labels = [lbl for lbl, _ in rows]
            assert all(labels), f"{name} mixes labeled and unlabeled samples"
            assert len(set(labels)) == len(labels), f"{name} duplicate labels"


def test_module_json_is_superset_of_pre_pr_keys():
    j = obs_metrics.snapshot_json()
    missing = (PRE_PR_KEYS - {"avg_request_s", "uptime_s"}) - set(j)
    assert not missing, f"registry JSON lost pre-PR keys: {missing}"
    assert "schema_version" in j and "ttft_seconds" in j


def test_concurrent_bump_vs_snapshot():
    """Counters and histograms stay exact and internally consistent while
    scrapes run concurrently with bumps from several threads."""
    reg = Registry()
    c = reg.counter("n")
    h = reg.histogram("lat", (1, 2, 4))
    N, T = 5000, 4

    def bump():
        for i in range(N):
            c.inc()
            h.observe(i % 6)

    threads = [threading.Thread(target=bump) for _ in range(T)]
    for t in threads:
        t.start()
    for _ in range(200):  # scrape while the writers run
        s = reg.snapshot_json()
        hv = s["lat"]
        assert hv["buckets"]["+Inf"] == hv["count"]
        cum = list(hv["buckets"].values())
        assert cum == sorted(cum)
        reg.render_prometheus()
    for t in threads:
        t.join()
    assert c.value == N * T and h.count == N * T


def test_integrity_counters_ride_the_registry():
    """io/integrity.py's counter API is now a view over the registry: a
    bump is visible in BOTH exposition paths and reset still zeroes."""
    from dllama_tpu.io import integrity
    integrity.reset_counters()
    integrity.bump_counter("checksum_failures", 3)
    assert integrity.counters()["checksum_failures"] == 3
    assert obs_metrics.snapshot_json()["checksum_failures"] == 3
    assert "dllama_checksum_failures_total 3" in obs_metrics.render_prometheus()
    integrity.reset_counters()
    assert all(v == 0 for v in integrity.counters().values())


# --- unit: structured logging --------------------------------------------

def test_json_log_line_shape():
    buf = io.StringIO()
    obs_log.configure("json", "debug", stream=buf, force=True)
    lg = obs_log.get_logger("test.shape")
    obs_log.set_request_id("rid-json-1")
    try:
        lg.info("hello", extra={"k": 1, "path": "/x"})
    finally:
        obs_log.set_request_id(None)
    rec = json.loads(buf.getvalue().strip())
    assert rec["event"] == "hello" and rec["level"] == "INFO"
    assert rec["logger"] == "dllama.test.shape"
    assert rec["request_id"] == "rid-json-1"
    assert rec["k"] == 1 and rec["path"] == "/x" and "ts" in rec


def test_human_format_and_no_request_id():
    buf = io.StringIO()
    obs_log.configure("human", "info", stream=buf, force=True)
    obs_log.get_logger("test.h").warning("boom", extra={"n": 2})
    line = buf.getvalue().strip()
    assert "WARNING" in line and "dllama.test.h" in line
    assert "boom" in line and "n=2" in line
    assert "[" not in line.split("boom")[0].split("dllama.test.h")[1], \
        "no [rid] bracket when no request id is set"


def test_env_spec_parsing():
    assert obs_log._parse_env("json:debug") == ("json", "debug")
    assert obs_log._parse_env("debug,json") == ("json", "debug")
    assert obs_log._parse_env("human") == ("human", None)
    assert obs_log._parse_env("") == (None, None)
    assert obs_log._parse_env("bogus:nope") == (None, None)


# --- unit: tracer ---------------------------------------------------------

def test_tracer_ring_capacity_and_span():
    tr = obs_trace.Tracer(capacity=4)
    for i in range(10):
        tr.record("s", float(i), float(i) + 0.5, i=i)
    spans = tr.snapshot()
    assert len(spans) == 4
    assert [s["args"]["i"] for s in spans] == [6, 7, 8, 9]
    with tr.span("timed", x=1):
        time.sleep(0.01)
    last = tr.snapshot()[-1]
    assert last["name"] == "timed" and last["dur"] >= 0.009


def test_trace_events_chrome_format_and_rid_filter():
    tr = obs_trace.Tracer(capacity=64)
    for rid in ("r1", "r2", "r3"):
        obs_log.set_request_id(rid)
        tr.record("request", 1.0, 2.0)
    obs_log.set_request_id(None)
    doc = tr.trace_json(last_requests=2)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["args"]["request_id"] for e in xs} == {"r2", "r3"}
    assert metas and metas[0]["name"] == "thread_name"
    e = xs[0]
    assert e["ts"] == pytest.approx(1.0 * 1e6)
    assert e["dur"] == pytest.approx(1.0 * 1e6)
    assert e["cat"] == "dllama" and isinstance(e["tid"], int)


# --- satellite: RunStats running sums ------------------------------------

def test_runstats_running_sums_match_numpy():
    import numpy as np
    from dllama_tpu.runtime.engine import RunStats, StepStats

    rng = np.random.RandomState(7)
    stats = [StepStats(*(rng.rand(5) * 10)) for _ in range(200)]
    rs = RunStats()
    for s in stats:
        rs.add(s)
    assert rs.avg_generation_ms == pytest.approx(
        np.mean([s.generation_ms for s in stats]))
    assert rs.avg_inference_ms == pytest.approx(
        np.mean([s.inference_ms for s in stats]))
    assert rs.avg_transfer_ms == pytest.approx(
        np.mean([s.transfer_ms for s in stats]))
    assert rs.avg_sent_bytes == pytest.approx(
        np.mean([s.sent_bytes for s in stats]))
    assert rs.avg_recv_bytes == pytest.approx(
        np.mean([s.recv_bytes for s in stats]))
    assert rs.tokens_per_second == pytest.approx(
        1000.0 / rs.avg_generation_ms)
    empty = RunStats()
    assert empty.avg_generation_ms == 0.0 and empty.tokens_per_second == 0.0


# --- live in-process server ----------------------------------------------

@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    import jax

    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.tokenizer.bpe import Tokenizer

    d = tmp_path_factory.mktemp("obs")
    tok = Tokenizer(write_tiny_tokenizer(str(d / "tok.t")))
    cfg = tiny_config(seq_len=128, vocab_size=300)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]))
    return eng, tok


@pytest.fixture
def api(stack):
    from dllama_tpu.server.api import ApiState, serve

    servers = []

    def make(**kw):
        eng, tok = stack
        state = ApiState(eng, tok, default_temperature=0.0, chunk=2, **kw)
        srv = serve(state, host="127.0.0.1", port=free_port(), block=False)
        servers.append(srv)
        return state, f"http://127.0.0.1:{srv.server_address[1]}"

    yield make
    for s in servers:
        s.shutdown()
        s.server_close()


CHAT = "/v1/chat/completions"
BODY = {"messages": [{"role": "user", "content": "hello"}], "seed": 3}


def post(base, path, body, headers=None, timeout=240):
    req = urllib.request.Request(
        base + path, json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=timeout)


def get(base, path, headers=None):
    req = urllib.request.Request(base + path, headers=headers or {})
    return urllib.request.urlopen(req, timeout=30)


def test_metrics_dual_exposition_live(api):
    state, base = api()
    with post(base, CHAT, dict(BODY, stream=True)) as r:
        assert r.headers["X-Request-Id"]
        assert b"[DONE]" in r.read()

    # default JSON stays a superset of every pre-PR key, plus the new
    # schema_version and histogram objects
    with get(base, "/metrics") as r:
        assert "application/json" in r.headers["Content-Type"]
        j = json.loads(r.read())
    missing = PRE_PR_KEYS - set(j)
    assert not missing, f"/metrics JSON lost pre-PR keys: {missing}"
    assert j["schema_version"] == obs_metrics.SCHEMA_VERSION
    assert j["requests_served"] == 1            # per-instance view
    assert j["ttft_seconds"]["count"] >= 1      # populated by the request
    assert j["inter_token_seconds"]["count"] >= 1

    # Accept negotiation → Prometheus text 0.0.4 with populated latency
    # histograms from the live request
    with get(base, "/metrics", headers={"Accept": "text/plain"}) as r:
        assert "version=0.0.4" in r.headers["Content-Type"]
        text = r.read().decode()
    types, samples = _parse_prom(text)
    assert types["dllama_ttft_seconds"] == "histogram"
    assert types["dllama_inter_token_seconds"] == "histogram"
    for name, t in types.items():
        if t == "histogram":
            _check_histogram_invariants(name, types, samples)
    (_, ttft_count), = samples["dllama_ttft_seconds_count"]
    assert ttft_count >= 1
    (_, it_count), = samples["dllama_inter_token_seconds_count"]
    assert it_count >= 1
    # engine-side step histograms populated too
    (_, g_count), = samples["dllama_engine_generation_ms_count"]
    assert g_count >= 1

    # ?format=prometheus works without the Accept header
    with get(base, "/metrics?format=prometheus") as r:
        assert "version=0.0.4" in r.headers["Content-Type"]


def test_request_id_lifecycle_in_logs(api):
    obs_log.configure("json", "debug", stream=io.StringIO(), force=True)
    records = []

    class Cap(logging.Handler):
        def emit(self, record):
            records.append(record)

    cap = Cap(level=logging.DEBUG)
    root = logging.getLogger("dllama")
    root.addHandler(cap)
    try:
        state, base = api()
        rid = "lifecycle.test-123"
        with post(base, CHAT, BODY, headers={"X-Request-Id": rid}) as r:
            assert r.headers["X-Request-Id"] == rid  # echoed, not regenerated
            json.loads(r.read())
        # "finish" is logged on the server thread AFTER the last response
        # byte, so the client can observe the full body a hair before the
        # record lands — wait for it rather than racing it
        want = {"accept", "queue", "prefill", "decode", "finish"}
        deadline = time.monotonic() + 5.0
        while True:
            mine = [r for r in records
                    if getattr(r, "request_id", None) == rid]
            events = {r.getMessage() for r in mine}
            if want <= events or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        # full lifecycle under ONE grep key: server accept/queue/finish
        # AND engine-side prefill/decode records
        assert want <= events, events
        assert any(r.name.startswith("dllama.runtime") for r in mine)
        assert any(r.name.startswith("dllama.server") for r in mine)
    finally:
        root.removeHandler(cap)


def test_client_request_id_sanitized(api):
    state, base = api()
    dirty = "abc<script>!{}$#123"
    with post(base, CHAT, BODY, headers={"X-Request-Id": dirty}) as r:
        assert r.headers["X-Request-Id"] == "abcscript123"
        json.loads(r.read())


def test_request_id_on_429(api):
    state, base = api(max_pending=0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(base, CHAT, BODY)
    assert ei.value.code == 429
    assert ei.value.headers["X-Request-Id"]
    assert state.metrics.requests_rejected_429 == 1


def test_request_id_on_500(api):
    state, base = api()
    with injected("engine.device_step=raise:RuntimeError:kaboomx1"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(base, CHAT, BODY)
    assert ei.value.code == 500
    assert ei.value.headers["X-Request-Id"]
    assert state.metrics.server_errors == 1
    state.engine.reset()          # don't leak a mid-prefill position
    state.naive_cache.clear()


def test_debug_trace_endpoint(api):
    state, base = api()
    obs_trace.clear()
    with post(base, CHAT, BODY) as r:
        rid = r.headers["X-Request-Id"]
        json.loads(r.read())
    # the handler closes ``api.request`` after it has written the response,
    # so this GET can overtake it: ask until the request's own span is there
    for _ in range(500):
        with get(base, "/debug/trace?last=5") as r:
            doc = json.loads(r.read())
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        if any(e["name"] == "api.request"
               and e["args"].get("request_id") == rid for e in xs):
            break
        time.sleep(0.01)
    names = {e["name"] for e in xs}
    assert {"api.request", "api.lock_wait", "engine.prefill"} <= names, names
    assert "engine.chunk_fetch" in names or "engine.decode_step" in names, \
        names
    for e in xs:  # chrome trace_event essentials
        assert e["ph"] == "X" and "ts" in e and "dur" in e
        assert e["pid"] == 1 and isinstance(e["tid"], int)


def test_trace_dump_cli(api, tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "trace_dump", os.path.join(REPO, "tools", "trace_dump.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    state, base = api()
    with post(base, CHAT, BODY) as r:
        json.loads(r.read())
    out = tmp_path / "trace.json"
    assert tool.main([base, "-o", str(out), "-n", "5"]) == 0
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"api.request", "api.lock_wait"} <= names
    printed = capsys.readouterr().out
    assert "spans across" in printed
    # unreachable server → clean failure, not a traceback
    assert tool.main(["http://127.0.0.1:1", "-o", str(out)]) == 1


def test_metric_catalog_matches_docs():
    """Doc-drift guard (PR-7 satellite): every module-level metric family
    in obs/metrics.py has a row in the docs/OBSERVABILITY.md catalog, and
    every catalog row names a real family — both directions.  Ad-hoc
    metrics registered by tests don't count (module attributes only);
    dllama_uptime_seconds is rendered inline by the registry."""
    from dllama_tpu.obs.metrics import LabeledCounter, LabeledGauge
    code = {"dllama_uptime_seconds"}
    for obj in vars(obs_metrics).values():
        if isinstance(obj, (Counter, Gauge, Histogram,
                            LabeledCounter, LabeledGauge)):
            code.add(obj.name)
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"),
              encoding="utf-8") as f:
        text = f.read()
    documented = set(re.findall(r"^\| `(dllama_[a-z0-9_]+)", text, re.M))
    assert code <= documented, \
        f"metric families missing a catalog row: {sorted(code - documented)}"
    assert documented <= code, \
        f"catalog rows naming no metric family: {sorted(documented - code)}"
