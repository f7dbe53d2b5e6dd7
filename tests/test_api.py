"""API server tests: NaiveCache unit semantics + live HTTP integration on a
tiny fixture model (the reference has NO api test — SURVEY §4 gap, closed
here)."""

import json
import subprocess
import sys
import time
import urllib.request

import pytest

from fixtures import REPO, cpu_env, free_port, write_tiny_model, write_tiny_tokenizer
from dllama_tpu.server.api import ChatMessage, NaiveCache, parse_request


# --- unit: NaiveCache (dllama-api.cpp:187-232 semantics) ---

def msgs(*pairs):
    return [ChatMessage(r, c) for r, c in pairs]


def test_cache_empty_returns_full_prompt():
    c = NaiveCache()
    start, delta = c.resolve_delta_prompt(msgs(("user", "hi")))
    assert start == 0 and len(delta) == 1


def test_cache_prefix_hit_resumes():
    c = NaiveCache()
    c.push(10, ChatMessage("user", "hi"))
    c.push(20, ChatMessage("assistant", "hello!"))
    start, delta = c.resolve_delta_prompt(
        msgs(("user", "hi"), ("assistant", "hello!"), ("user", "more")))
    assert start == 20
    assert [m.content for m in delta] == ["more"]


def test_cache_mismatch_clears():
    c = NaiveCache()
    c.push(10, ChatMessage("user", "hi"))
    c.push(20, ChatMessage("assistant", "hello!"))
    start, delta = c.resolve_delta_prompt(
        msgs(("user", "DIFFERENT"), ("assistant", "hello!"), ("user", "more")))
    assert start == 0 and len(delta) == 3
    assert c.items == []


def test_cache_equal_length_is_miss():
    # reference requires messages.size() > cacheSize (dllama-api.cpp:214)
    c = NaiveCache()
    c.push(10, ChatMessage("user", "hi"))
    start, delta = c.resolve_delta_prompt(msgs(("user", "hi")))
    assert start == 0 and len(delta) == 1


def test_parse_request_fields():
    p = parse_request({
        "messages": [{"role": "user", "content": "x"}],
        "temperature": 0.1, "top_p": 0.5, "max_tokens": 7,
        "stream": True, "seed": 42, "stop": ["##"],
    }, 0.7, 0.9)
    assert p.temperature == 0.1 and p.top_p == 0.5 and p.max_tokens == 7
    assert p.stream and p.seed == 42 and p.stop == ["##"]
    assert parse_request({"stop": "single"}, 0.7, 0.9).stop == ["single"]


# --- integration: live server on a tiny model ---

@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("api")
    m, t = str(d / "tiny.m"), str(d / "tiny.t")
    write_tiny_model(m)
    write_tiny_tokenizer(t)
    port = free_port()
    # the server's output goes to a file: nobody drains a pipe while the
    # module's tests run, and a full pipe (64 KB: the log lines of a few
    # dozen requests, or of a warm compile cache's loads) blocks the server
    log = open(d / "server.log", "w+")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dllama_tpu.server.api", "--model", m,
         "--tokenizer", t, "--port", str(port), "--temperature", "0",
         "--max-seq-len", "128", "--batch-slots", "3"],
        cwd=REPO, env=cpu_env(), stdout=log, stderr=subprocess.STDOUT,
        text=True)
    base = f"http://127.0.0.1:{port}"
    for _ in range(600):
        if proc.poll() is not None:
            log.seek(0)
            raise RuntimeError(f"server died:\n{log.read()}")
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
    log.close()


def post(base, path, body, timeout=240):
    req = urllib.request.Request(
        base + path, json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_models_endpoint(server):
    with urllib.request.urlopen(server + "/v1/models", timeout=10) as r:
        data = json.loads(r.read())
    assert data["object"] == "list" and data["data"][0]["object"] == "model"


def test_chat_completion_non_stream(server):
    body = {"messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 8, "temperature": 0, "seed": 1}
    with post(server, "/v1/chat/completions", body) as r:
        data = json.loads(r.read())
    assert data["object"] == "chat.completion"
    assert data["choices"][0]["message"]["role"] == "assistant"
    u = data["usage"]
    assert u["prompt_tokens"] > 0
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]


def test_health_perf_block_and_metrics_gauges(server):
    """Performance-economics plane over the real HTTP surface: after a
    served completion, /health carries the roofline summary and both
    /metrics expositions carry the MFU/MBU gauges (obs/cost.py)."""
    # /v1/completions rides the slot scheduler (the attribution seam);
    # an uncontended chat request would take the mutex path instead
    body = {"prompt": "hello", "max_tokens": 6, "temperature": 0}
    with post(server, "/v1/completions", body) as r:
        r.read()
    with urllib.request.urlopen(server + "/health", timeout=10) as r:
        health = json.loads(r.read())
    perf = health["perf"]
    assert perf["flops_total"] > 0 and perf["hbm_bytes_total"] > 0
    assert "mfu" in perf and "mbu" in perf and "peaks" in perf
    assert perf["chip_ms_by_class"]  # the served request bought chip time
    with urllib.request.urlopen(server + "/metrics", timeout=10) as r:
        js = json.loads(r.read())
    assert "mfu" in js and "mbu" in js
    assert js["dispatch_flops"] and js["class_chip_ms"]
    with urllib.request.urlopen(server + "/metrics?format=prometheus",
                                timeout=10) as r:
        txt = r.read().decode()
    assert "dllama_mfu" in txt and "dllama_mbu" in txt
    assert "dllama_dispatch_flops_total" in txt
    assert "dllama_class_chip_ms_total{" in txt


def test_chat_completion_stream_sse(server):
    body = {"messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 8, "temperature": 0, "stream": True, "seed": 1}
    with post(server, "/v1/chat/completions", body) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = r.read().decode()
    events = [l[6:] for l in raw.splitlines() if l.startswith("data: ")]
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    assert parsed[-1]["choices"][0]["finish_reason"] == "stop"
    assert all(p["object"] == "chat.completion.chunk" for p in parsed)


def test_followup_uses_naive_cache(server):
    first = {"messages": [{"role": "user", "content": "cache me"}],
             "max_tokens": 6, "temperature": 0, "seed": 1}
    with post(server, "/v1/chat/completions", first) as r:
        d1 = json.loads(r.read())
    reply = d1["choices"][0]["message"]["content"]
    p1 = d1["usage"]["prompt_tokens"]
    follow = {"messages": [
        {"role": "user", "content": "cache me"},
        {"role": "assistant", "content": reply},
        {"role": "user", "content": "again"}],
        "max_tokens": 6, "temperature": 0, "seed": 1}
    with post(server, "/v1/chat/completions", follow) as r:
        data = json.loads(r.read())
    assert data["choices"][0]["message"]["role"] == "assistant"
    # cache hit → only the delta (one user message + generation prompt) is
    # tokenized: about the size of the first one-message prompt, far smaller
    # than re-encoding the whole 3-message history
    assert data["usage"]["prompt_tokens"] <= p1 + 10


def test_bad_json_is_400(server):
    req = urllib.request.Request(
        server + "/v1/chat/completions", b"{not json",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_missing_messages_is_400(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/chat/completions", {"messages": []})
    assert e.value.code == 400


def test_unknown_route_404(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/other", {})
    assert e.value.code == 404


# --- batched /v1/completions (beyond reference: batch=1, tasks.cpp:199-210) ---

def test_completions_batched_matches_individual(server):
    """A list-valued prompt runs as one lockstep batch; each row's greedy
    text must equal the same prompt served alone."""
    body = {"prompt": ["the sky", "one two three"], "max_tokens": 6,
            "temperature": 0, "seed": 1}
    with post(server, "/v1/completions", body) as r:
        batched = json.loads(r.read())
    assert batched["object"] == "text_completion"
    assert [c["index"] for c in batched["choices"]] == [0, 1]
    u = batched["usage"]
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]
    for prompt, choice in zip(body["prompt"], batched["choices"]):
        single = {"prompt": prompt, "max_tokens": 6, "temperature": 0, "seed": 1}
        with post(server, "/v1/completions", single) as r:
            alone = json.loads(r.read())
        assert alone["choices"][0]["text"] == choice["text"]


def test_completions_n_greedy_identical(server):
    with post(server, "/v1/completions",
              {"prompt": "hello", "n": 3, "max_tokens": 5,
               "temperature": 0}) as r:
        data = json.loads(r.read())
    texts = [c["text"] for c in data["choices"]]
    assert len(texts) == 3 and len(set(texts)) == 1  # greedy → identical rows


def test_completions_logprobs(server):
    """OpenAI logprobs: chosen-token log-probs + top-k alternatives from
    one teacher-forced scoring forward.  Greedy decode means every chosen
    token IS the argmax, so its logprob must equal the top-1 logprob."""
    body = {"prompt": ["the sky", "one two"], "max_tokens": 5,
            "temperature": 0, "seed": 1, "logprobs": 2}
    with post(server, "/v1/completions", body) as r:
        data = json.loads(r.read())
    for c in data["choices"]:
        lp = c["logprobs"]
        assert lp is not None
        n = len(lp["tokens"])
        assert n > 0
        assert len(lp["token_logprobs"]) == n == len(lp["top_logprobs"]) \
            == len(lp["text_offset"])
        assert "".join(lp["tokens"]) == c["text"]
        assert all(v <= 0.0 for v in lp["token_logprobs"])
        for chosen, tops in zip(lp["token_logprobs"], lp["top_logprobs"]):
            # distinct token ids may render to one piece string (byte
            # fallback), so ≤ k entries survive the text keying
            assert 1 <= len(tops) <= 2
            assert abs(chosen - max(tops.values())) < 1e-4  # greedy = argmax


def test_completions_logprobs_echo_and_stop_alignment(server):
    """echo=true leads with the prompt's tokens (first logprob null, no
    conditional for position 0); a stop-string truncation drops the
    scored tokens past the cut so the list aligns with the text."""
    body = {"prompt": "the sky", "max_tokens": 6, "temperature": 0,
            "seed": 1, "logprobs": 0, "echo": True}
    with post(server, "/v1/completions", body) as r:
        c = json.loads(r.read())["choices"][0]
    lp = c["logprobs"]
    # the fixture tokenizer adds BOS, so even the first displayed token
    # has a real conditional (the OpenAI null applies only to a truly
    # context-free position 0); prompt tokens lead the list
    assert all(v is not None for v in lp["token_logprobs"])
    assert len(lp["tokens"]) > 6 // 2  # prompt pieces + completion pieces
    assert "".join(lp["tokens"]) == c["text"]
    assert lp["text_offset"] == sorted(lp["text_offset"])

    plain = {"prompt": "the sky", "max_tokens": 8, "temperature": 0, "seed": 1}
    with post(server, "/v1/completions", plain) as r:
        full = json.loads(r.read())["choices"][0]["text"]
    if len(full) < 4:
        pytest.skip("fixture generated too little text to cut")
    stop = full[len(full) // 2:len(full) // 2 + 2]
    with post(server, "/v1/completions",
              {**plain, "stop": [stop], "logprobs": 0}) as r:
        c = json.loads(r.read())["choices"][0]
    joined = "".join(c["logprobs"]["tokens"])
    assert c["text"].startswith(joined)  # a stop can cut mid-piece
    assert stop not in joined
    assert len(c["logprobs"]["token_logprobs"]) == len(c["logprobs"]["tokens"])


def test_completions_over_slots_is_400(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/completions",
             {"prompt": ["a", "b", "c", "d"], "max_tokens": 2})
    assert e.value.code == 400


def test_completions_streaming_matches_non_stream(server):
    """SSE completions stream per-row deltas tagged by choice index from
    the one lockstep batch; reassembled text must equal the
    non-streaming response for the same request."""
    base = {"prompt": ["the sky", "one two three"], "max_tokens": 6,
            "temperature": 0, "seed": 1}
    with post(server, "/v1/completions", base) as r:
        plain = json.loads(r.read())
    with post(server, "/v1/completions", {**base, "stream": True}) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = r.read().decode()
    events = [l[6:] for l in raw.splitlines() if l.startswith("data: ")]
    assert events[-1] == "[DONE]"
    texts, finishes = {0: "", 1: ""}, {}
    for e in events[:-1]:
        c = json.loads(e)["choices"][0]
        texts[c["index"]] += c["text"]
        if c["finish_reason"]:
            finishes[c["index"]] = c["finish_reason"]
    for i, choice in enumerate(plain["choices"]):
        assert texts[i] == choice["text"], (i, texts, plain)
        assert finishes[i] == choice["finish_reason"]


def test_chat_n_choices(server):
    """chat completions with n>1 run the templated prompt as one lockstep
    batch and return n choices (greedy → identical contents)."""
    body = {"messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 6, "temperature": 0, "seed": 1, "n": 2}
    with post(server, "/v1/chat/completions", body) as r:
        data = json.loads(r.read())
    assert [c["index"] for c in data["choices"]] == [0, 1]
    # truncation must be visible per choice (not a hardcoded "stop")
    assert all(c["finish_reason"] in ("stop", "length")
               for c in data["choices"])
    contents = [c["message"]["content"] for c in data["choices"]]
    assert len(set(contents)) == 1  # greedy rows identical
    # and the single-choice reply matches choice 0
    single = {**body, "n": 1}
    with post(server, "/v1/chat/completions", single) as r:
        one = json.loads(r.read())
    assert one["choices"][0]["message"]["content"] == contents[0]


def test_chat_n_stream_rejected(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/chat/completions",
             {"messages": [{"role": "user", "content": "x"}],
              "n": 2, "stream": True})
    assert e.value.code == 400


def test_completions_stop_string_stream_parity(server):
    """A stop string buried inside the generated text must truncate the
    stream exactly where the non-streaming post-hoc find() truncates."""
    base = {"prompt": "the sky", "max_tokens": 10, "temperature": 0, "seed": 1}
    with post(server, "/v1/completions", base) as r:
        full = json.loads(r.read())["choices"][0]["text"]
    if len(full) < 4:
        pytest.skip("fixture generated too little text to cut")
    stop = full[len(full) // 2:len(full) // 2 + 2]
    body = {**base, "stop": [stop]}
    with post(server, "/v1/completions", body) as r:
        plain = json.loads(r.read())["choices"][0]
    with post(server, "/v1/completions", {**body, "stream": True}) as r:
        raw = r.read().decode()
    text, finish = "", None
    for e in [l[6:] for l in raw.splitlines() if l.startswith("data: ")][:-1]:
        c = json.loads(e)["choices"][0]
        text += c["text"]
        finish = c["finish_reason"] or finish
    assert stop not in text
    assert text == plain["text"]
    assert finish == plain["finish_reason"] == "stop"


def test_concurrent_requests_serialize(server):
    """Two clients at once: the accept queue serializes them; both must get
    complete, independent answers (documented queue semantics)."""
    import threading
    results = {}

    def worker(name, content):
        body = {"messages": [{"role": "user", "content": content}],
                "max_tokens": 5, "temperature": 0, "seed": 1}
        with post(server, "/v1/chat/completions", body) as r:
            results[name] = json.loads(r.read())

    threads = [threading.Thread(target=worker, args=(i, f"prompt {i}"))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert sorted(results) == [0, 1]
    for d in results.values():
        assert d["choices"][0]["message"]["role"] == "assistant"
        assert d["usage"]["completion_tokens"] > 0


def test_completions_echo_empty_completion_logprobs(tmp_path, monkeypatch):
    """echo=true with an EOS-first (empty) completion still returns the
    prompt's logprobs (OpenAI echo semantics), and a non-echo empty
    completion gets empty lists — never a silent null."""
    import jax

    from dllama_tpu.models.config import tiny_config
    from dllama_tpu.models.params import init_params
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine
    from dllama_tpu.server.api import ApiState
    from dllama_tpu.tokenizer.bpe import Tokenizer

    tok = Tokenizer(write_tiny_tokenizer(str(tmp_path / "tok.t")))
    cfg = tiny_config(seq_len=64, vocab_size=300)
    eng = Engine(cfg, init_params(cfg, seed=4),
                 mesh=make_mesh(tp=1, devices=jax.devices()[:1]), batch=2)
    state = ApiState(eng, tok, batch_engine=eng)
    eos = tok.eos_id

    import numpy as np

    def eos_first(id_lists, budget, **kw):  # every row: EOS immediately
        yield np.array([eos] * len(id_lists))

    monkeypatch.setattr(eng, "generate_batch_stream", eos_first)
    kw = dict(temperature=0.0, top_p=1.0, max_tokens=4, seed=1, stop=[])

    choices, _, n_completion = state.complete_batch(
        ["hello", "hi"], echo=True, logprobs=0, **kw)
    assert n_completion == 0
    for c, prompt in zip(choices, ["hello", "hi"]):
        assert c["text"] == prompt and c["finish_reason"] == "stop"
        lp = c["logprobs"]
        assert lp is not None
        assert "".join(lp["tokens"]) == prompt
        # fixture adds BOS, so every displayed prompt token has a real
        # conditional — no leading null
        assert len(lp["token_logprobs"]) == len(lp["tokens"]) > 0
        assert all(v is not None and v <= 0.0 for v in lp["token_logprobs"])

    choices, _, _ = state.complete_batch(["hello", "hi"], logprobs=0, **kw)
    for c in choices:
        assert c["text"] == "" and c["logprobs"] == {
            "tokens": [], "token_logprobs": [], "top_logprobs": None,
            "text_offset": []}
