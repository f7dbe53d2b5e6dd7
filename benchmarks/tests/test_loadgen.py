"""The load generator against a stub server that streams tokens at a fixed
pace: closed and open loops, the cut at the window's end, and that a seed
fixes what is sent."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from harness import e2e, loadgen, tokens

GAP_S = 0.01


class Stub(BaseHTTPRequestHandler):
    seen: list = []

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        chat = self.path.endswith("/chat/completions")
        text = body["messages"][0]["content"] if chat else body["prompt"]
        Stub.seen.append((text, body["max_tokens"]))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        try:
            for i in range(body["max_tokens"]):
                time.sleep(GAP_S)
                ch = {"delta": {"content": text[0]}} if chat else {"text": text[0]}
                self.wfile.write(b"data: " + json.dumps(
                    {"choices": [dict(ch, finish_reason=None)]}).encode() + b"\n\n")
                self.wfile.flush()
            self.wfile.write(b"data: [DONE]\n\n")
        except OSError:
            pass


@pytest.fixture()
def base():
    Stub.seen = []
    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()


MIX = {"loop": "closed", "clients": 3, "endpoint": "completions",
       "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
       "output_tokens": {"dist": "fixed", "value": 40}, "preroll_s": 0.3}


def _run(base, mix, seed, tmp_path, seconds=1.2):
    out = tmp_path / f"r{seed}-{len(Stub.seen)}.jsonl"
    loadgen.run_window(base, mix, seed, 4096, seconds, str(out))
    return e2e.read_records(str(out))


def test_closed_loop_counts_cuts_and_times(base, tmp_path):
    head, recs = _run(base, MIX, 5, tmp_path)
    s = e2e.summarize(head, recs)
    assert s["failed"] == 0 and s["attempted"] >= 6
    assert any(r["cut"] for r in recs) and any(r["ok"] for r in recs)
    # three clients, a token every GAP_S each
    assert 0.6 * 3 / GAP_S < s["out_tok_s"] <= 3 / GAP_S
    assert GAP_S * 1e3 <= s["tpot_p50_ms"] < 3 * GAP_S * 1e3
    assert s["loadgen_late_p95_ms"] < 50


def test_a_seed_fixes_what_is_sent(base, tmp_path):
    one = dict(MIX, clients=1)
    _run(base, one, 5, tmp_path, 0.6)
    first = list(Stub.seen)
    Stub.seen = []
    _run(base, one, 5, tmp_path, 0.6)
    n = min(len(first), len(Stub.seen))
    assert n >= 2 and first[:n] == Stub.seen[:n]
    Stub.seen = []
    _run(base, one, 6, tmp_path, 0.6)
    assert first[:2] != Stub.seen[:2]


def test_open_loop_sends_on_schedule(base, tmp_path):
    mix = dict(MIX, loop="open", endpoint="chat",
               arrivals={"kind": "poisson", "rate_per_s": 20.0}, max_inflight=64)
    head, recs = _run(base, mix, 9, tmp_path)
    s = e2e.summarize(head, recs)
    assert s["failed"] == 0 and 10 <= s["attempted"] <= 50
    assert s["loadgen_late_p95_ms"] < 100
    dues = sorted(r["due"] for r in recs)
    assert dues[0] >= head["launch"] and dues[-1] < head["window"][1]


def test_a_refused_request_is_a_failure(tmp_path):
    rec = loadgen.send_request("http://127.0.0.1:9", "completions", [5, 6], 4)
    assert not rec["ok"] and not rec["cut"] and "error" in rec
    assert rec["n_prompt"] == 2 + tokens.overhead("completions")
