#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dllama-tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width (and by default the full depth) of Llama-2-7B with seeded random
Q40 weights:

  kernels  each main-path Pallas kernel against its plain reference on the
           chip, at the real shapes (Q40 matmul, fused paged attention, a
           pure-decode step's ring writes at Falcon-H1's and Granite's widths:
           one launch a plane against the windows, bit for bit)
  cli      ``python -m dllama_tpu inference`` on a synthesized .m/.t pair
  server   ``python -m dllama_tpu.server.api`` with a paged slot scheduler:
           concurrent completions, a streamed chat, /metrics, SIGTERM drain
  packed   one mixed slot step (16 slots x 16 tokens, 2 prefilling) with its
           row-local regions over the 46 valid rows packed into 64
           (models/packing.py) against the step over all 256, same pool:
           logits at each slot's last valid row within the Q40 tolerance
  moe      the mixture-of-experts path at OLMoE-1B-7B's widths and 2 layers:
           a seeded .m through the loader, ``moe_ffn``'s one launch over the
           row's chosen experts (``q40_mm_chosen``) at 1 row against the XLA
           loop, its all-experts launches (``q40_mm_experts``, 64 packed
           experts) at 16 rows and its grouped ones (``q40_mm_grouped``, the
           rows sorted by expert) at 256 against the XLA-dequantized scan,
           then the same paged server on that file

``--chips 4`` runs, instead, only the tensor-parallel path and what it is
compared with: the same files decoded greedily at tp=4 and tp=1.

This parent never imports JAX.  Each phase is one child process that holds
the chip alone, checks ``jax.devices()[0].platform == "tpu"`` first, and is
reaped before the next starts.  Children's stdout is captured and re-printed
here on earlier lines (one JSON object per line); diagnostics go to stderr.
The LAST stdout line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and nothing prints after it.  Any failed phase, or no TPU, exits non-zero
without a line containing ``"ok": true``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = "llama2-7b"
MOE_MODEL, MOE_LAYERS = "olmoe-1b-7b", 2
MOE_TOL = 2e-2             # max |pallas - xla| / max |xla| of a layer's moe_ffn
BUDGET_S = 1150            # the driver allows 1200 s, compilation included
Q40_TOL = 1e-2             # max |pallas - xla| / max |xla|
Q40_F32_TOL = 5e-6         # the one-row (grouped) and the sliced body against x @ dequantize(float32)
ATTN_TOL = 2e-2            # max |fused - gather| / max |gather| (bf16 out)
TP_LOGIT_TOL = 5e-2        # max |tp4 - tp1| / max |tp1| on first-step logits
CHILD_MARK = "CHIP_SMOKE "  # prefix of the result lines a child prints


class PhaseFailed(Exception):
    pass


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def emit(obj: dict) -> None:
    """An EARLIER stdout line (never the last): one JSON object."""
    print(json.dumps(obj), flush=True)


def last_line(device: dict) -> str:
    """The one line the driver reads: exactly ``ok`` and ``device``, and in
    ``device`` exactly ``platform``, ``kind``, ``count``."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# Parent side: spawn, reap, check
# ---------------------------------------------------------------------------

def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _child_cmd(phase: str, *args: str, rehearse: bool = False) -> list[str]:
    """``rehearse`` (tests only, never set by ``main``): the child may run
    off-TPU at a toy size, so a phase's control flow is checked on the CPU."""
    return [sys.executable, os.path.abspath(__file__), "--phase", phase,
            *(["--rehearse"] if rehearse else []), *args]


def _results(stdout: str, phase: str) -> tuple[list[dict], dict]:
    """The result rows a child printed, and apart from them its compile
    row (re-printed here; every phase returns it for the closing line)."""
    rows = [json.loads(ln[len(CHILD_MARK):]) for ln in stdout.splitlines()
            if ln.startswith(CHILD_MARK)]
    comp = next((r for r in rows if r.get("what") == "compile"), {})
    if comp:
        emit(dict(comp, phase=phase))
    return [r for r in rows if r is not comp], comp


def run_child(phase: str, args: list[str], timeout: float,
              rehearse: bool = False) -> tuple[int, str]:
    """Run one child to its end with stdout captured (never inherited);
    a child past its timeout is killed and reaped."""
    log(f"phase {phase}: starting (timeout {timeout:.0f}s)")
    p = subprocess.Popen(_child_cmd(phase, *args, rehearse=rehearse),
                         stdout=subprocess.PIPE, text=True, env=_child_env(),
                         cwd=HERE)
    try:
        out, _ = p.communicate(timeout=max(timeout, 1))
    except subprocess.TimeoutExpired:
        p.kill()
        out, _ = p.communicate()
        raise PhaseFailed(f"{phase}: no result within {timeout:.0f}s (killed)")
    return p.returncode, out


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


def phase_kernels(timeout: float, rehearse: bool = False) -> dict:
    """Child: kernels vs references on the device.  Returns the device the
    child held; every kernel's error is re-printed on an earlier line."""
    rc, out = run_child("kernels", [], timeout, rehearse)
    rows, comp = _results(out, "kernels")
    for r in rows:
        emit(dict(r, phase="kernels"))
    require(rc == 0, f"kernels: child exited {rc}")
    dev = next((r for r in rows if r.get("what") == "device"), None)
    require(dev is not None, "kernels: child reported no device")
    errs = [r for r in rows if "rel_err" in r]
    require(errs, "kernels: no kernel was compared")
    bad = [r for r in errs if not r["rel_err"] <= r["tol"]]
    require(not bad, f"kernels: above tolerance: {bad}")
    if not rehearse:
        require(dev["platform"] == "tpu", f"kernels: ran on {dev['platform']}")
        require(dev["peaks_source"] == "table",
                f"kernels: peaks source {dev['peaks_source']!r}, not the table")
    return dict(dev, compile=comp)


def phase_moe(mpath: str, timeout: float, rehearse: bool = False) -> dict:
    """Child: ``moe_ffn`` on a loaded file, kernel path against the XLA
    path, at one row (select-chosen; the XLA path's form of it is select) and
    at 16 (all-experts) and 256 (grouped: the rows sorted by expert, PR 53;
    the XLA path's form of both is the scan)."""
    rc, out = run_child("moe", ["--model", mpath], timeout, rehearse)
    rows, comp = _results(out, "moe")
    for r in rows:
        emit(dict(r, phase="moe"))
    require(rc == 0, f"moe: child exited {rc}")
    errs = {(r["strategy"], r["rows"]): r for r in rows if "rel_err" in r}
    require(set(errs) == {("select-chosen", 1), ("all-experts", 16),
                          ("grouped", 256)}, f"moe: compared {sorted(errs)}")
    bad = [r for r in errs.values() if not r["rel_err"] <= r["tol"]]
    require(not bad, f"moe: above tolerance: {bad}")
    ledger = next(r for r in rows if r.get("what") == "ledger")["ledger"]
    require(all(f"moe/{p}×" in ledger for p in
                ("select-chosen", "select", "all-experts", "grouped", "scan")),
            f"moe: strategies absent from the ledger: {ledger}")
    if not rehearse:
        require("q40/pallas-fused" in ledger and "DEGRADED" not in ledger,
                f"moe: {ledger}")
    return {"phase": "moe", "compile": comp}


def phase_packed(mpath: str, timeout: float, rehearse: bool = False) -> dict:
    """Child: one mixed slot step (16 slots x 16 tokens, 2 of them
    prefilling: 46 rows of 256 hold a token) with its row-local regions over
    the valid rows packed into 64 (models/packing.py) against the same step
    over every row, from the same pool: the logits at each slot's last valid
    row."""
    rc, out = run_child("packed", ["--model", mpath], timeout, rehearse)
    rows, comp = _results(out, "packed")
    for r in rows:
        emit(dict(r, phase="packed"))
    require(rc == 0, f"packed: child exited {rc}")
    cmp_ = next((r for r in rows if r.get("what") == "packed_step"), None)
    require(cmp_ is not None, "packed: nothing was compared")
    require((cmp_["valid_rows"], cmp_["run_rows"], cmp_["slot_rows"])
            == (46, 64, 256), f"packed: rows {cmp_}")
    require(cmp_["rel_err"] <= cmp_["tol"], f"packed: above tolerance: {cmp_}")
    require(cmp_["greedy_equal"], f"packed: greedy tokens differ: {cmp_}")
    return {"phase": "packed", "compile": comp}


def phase_cli(mpath: str, tpath: str, timeout: float, steps: int = 64,
              rehearse: bool = False) -> dict:
    """Child: ``python -m dllama_tpu inference`` (the module is run as
    ``__main__`` after the platform check)."""
    args = ["inference", "--model", mpath, "--tokenizer", tpath, "--prompt",
            "hello hello hello", "--steps", str(steps), "--warmup", str(steps),
            "--workers", "tpu:1", "--temperature", "0", "--seed", "0"]
    rc, out = run_child("cli", args, timeout, rehearse)
    _, comp = _results(out, "cli")
    require(rc == 0, f"cli: exited {rc}; tail: {out[-600:]!r}")
    lines = out.splitlines()

    def grab(prefix):
        hit = [ln for ln in lines if ln.startswith(prefix)]
        require(hit, f"cli: no {prefix!r} line")
        return hit[-1][len(prefix):].strip()

    n_tokens = int(grab("Generated tokens:"))
    tok_s = float(grab("Avg tokens / second:"))
    ledger = next((ln for ln in lines if "kernel dispatch:" in ln), "")
    warm = next((ln for ln in lines if "warmup:" in ln), "")
    res = {"phase": "cli", "generated_tokens": n_tokens,
           "ledger": ledger.strip()}
    if not rehearse:  # a CPU timing is never printed as a reading
        res.update(warmup_incl_compile=warm.split("warmup:")[-1].strip(),
                   smoke_tok_s=tok_s, smoke_tok_s_note="a smoke reading "
                   "after warm-up, not a benchmark")
    emit(res)
    require(n_tokens == steps, f"cli: {n_tokens} tokens, wanted {steps}")
    require(ledger, "cli: no dispatch ledger line")
    require("DEGRADED" not in ledger, f"cli: degraded run: {ledger}")
    if not rehearse:
        require("q40/pallas-fused" in ledger, f"cli: no pallas-fused: {ledger}")
        require("q40/xla-dequant" not in ledger,
                f"cli: xla-dequant at decode width: {ledger}")
    return dict(res, compile=comp)


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 300):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_server(mpath: str, tpath: str, timeout: float, tmp: str,
                 slots: int = 4, ctx: int = 512, page: int = 16,
                 max_tokens: int = 32, rehearse: bool = False) -> dict:
    """Child: ``python -m dllama_tpu.server.api`` on the same files with a
    paged slot scheduler.  Everything the parent starts here (the child,
    the request threads) is joined before this returns."""
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    kv_pages = slots * (-(-ctx // page)) + 8
    args = ["--model", mpath, "--tokenizer", tpath, "--port", str(port),
            "--host", "127.0.0.1", "--workers", "tpu:1",
            "--batch-slots", str(slots), "--kv-pages", str(kv_pages),
            "--kv-page-size", str(page), "--temperature", "0"]
    out_path = os.path.join(tmp, "server.stdout")
    deadline = time.monotonic() + timeout
    log(f"phase server: starting on port {port} (timeout {timeout:.0f}s)")
    with open(out_path, "w") as out_f:
        p = subprocess.Popen(_child_cmd("server", *args, rehearse=rehearse),
                             stdout=out_f, env=_child_env(), cwd=HERE)
    try:
        health = None
        while time.monotonic() < deadline:
            require(p.poll() is None, f"server: exited {p.returncode} at boot")
            try:
                st, body = _http("GET", base + "/health", timeout=5)
                if st == 200 and json.loads(body).get("ready", True):
                    health = json.loads(body)
                    break
            except OSError:
                pass
            time.sleep(1.0)
        require(health is not None, "server: /health never came up")
        t_ready = time.monotonic()

        def left():
            return max(deadline - time.monotonic(), 1)

        greedy = [{"prompt": f"hello {'hi ' * (i + 1)}", "max_tokens":
                   max_tokens, "temperature": 0} for i in range(slots)]
        results: list = [None] * (slots + 1)

        def post(i, path, body):
            try:
                results[i] = _http("POST", base + path, body, timeout=left())
            except Exception as e:  # noqa: BLE001 — reported by the checks
                results[i] = (0, repr(e).encode())

        # the streamed request is sampled (no seed): it is what puts
        # sample/sample-dev in the ledger; the greedy ones compile argmax
        chat = {"messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 24, "temperature": 0.7, "top_p": 0.9,
                "stream": True}
        ths = [threading.Thread(target=post, args=(i, "/v1/completions", b))
               for i, b in enumerate(greedy)]
        ths.append(threading.Thread(
            target=post, args=(slots, "/v1/chat/completions", chat)))
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        texts = []
        for i in range(slots):
            st, body = results[i]
            require(st == 200, f"server: completion {i} → {st} {body[:200]!r}")
            texts.append(json.loads(body)["choices"][0]["text"])
            require(texts[-1], f"server: completion {i} returned empty text")
        st, body = results[slots]
        require(st == 200, f"server: streamed chat → {st} {body[:200]!r}")
        deltas = [json.loads(ln[6:]) for ln in body.decode().splitlines()
                  if ln.startswith("data: {")]
        require(not any("error" in d for d in deltas),
                f"server: streamed chat carried an error: {deltas[-1]}")
        streamed = "".join(d["choices"][0].get("delta", {}).get("content") or ""
                           for d in deltas)
        require(streamed, "server: streamed chat produced no text")
        require(b"data: [DONE]" in body, "server: stream did not finish")
        # the same greedy request twice more, one at a time: same bytes
        t_solo = time.monotonic()
        again = [json.loads(_http("POST", base + "/v1/completions", greedy[0],
                                  timeout=left())[1])["choices"][0]["text"]
                 for _ in range(2)]
        solo_s = (time.monotonic() - t_solo) / 2  # programs compiled by now
        require(again[0] == again[1] == texts[0],
                f"server: greedy replay differs: {texts[0]!r} {again!r}")
        st, body = _http("GET", base + "/metrics", timeout=30)
        metrics = json.loads(body)
        st, body = _http("GET", base + "/health", timeout=30)
        health2 = json.loads(body)
        t_served = time.monotonic()
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=left())
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    paths = metrics.get("matmul_dispatch", {})
    degrades = {k: metrics.get(k) or {} for k in ("q40_degrade", "attn_degrade")}
    peaks = (health2.get("perf") or {}).get("peaks") or {}
    res = {"phase": "server", "backend": health.get("backend"),
           "completions": slots, "streamed_chars": len(streamed),
           "greedy_replay_identical": True, "dispatch": paths,
           "degrades": degrades, "peaks_source": peaks.get("source"),
           "serve_seconds": round(t_served - t_ready, 1), "drain_rc": rc}
    if not rehearse:  # a CPU timing is never printed as a reading
        res.update(smoke_solo_request_seconds=round(solo_s, 3),
                   smoke_solo_tok_s=round(max_tokens / solo_s, 2),
                   smoke_note="one greedy request alone on the scheduler, "
                   "a smoke reading, not a benchmark")
    emit(res)
    with open(out_path) as f:
        _, comp = _results(f.read(), "server")
    require(rc == 0, f"server: exit code {rc} after SIGTERM")
    require(not any(degrades.values()), f"server: degrades {degrades}")
    if not rehearse:
        require(health.get("backend") == "tpu",
                f"server: backend {health.get('backend')!r}")
        for fam in ("q40/pallas-fused", "kv_dense/paged-fused",
                    "sample/sample-dev"):
            require(paths.get(fam, 0) > 0, f"server: {fam} absent: {paths}")
        require(peaks.get("source") == "table",
                f"server: peaks source {peaks.get('source')!r}")
    return dict(res, compile=comp)


def phase_tp(mpath: str, tpath: str, timeout: float, tp: int = 4,
             rehearse: bool = False) -> dict:
    """Child (one process owning all chips): greedy decode at tp=N and at
    tp=1 on the same files; agreement is judged here."""
    rc, out = run_child("tp", ["--model", mpath, "--tokenizer", tpath,
                               "--tp", str(tp)], timeout, rehearse)
    rows, comp = _results(out, "tp")
    for r in rows:
        emit(dict(r, phase="tp"))
    require(rc == 0, f"tp: child exited {rc}; tail: {out[-600:]!r}")
    dev = next((r for r in rows if r.get("what") == "device"), None)
    cmp_ = next((r for r in rows if r.get("what") == "compare"), None)
    require(dev and cmp_, "tp: child reported no device or no comparison")
    require(cmp_["logits_rel_err"] <= TP_LOGIT_TOL,
            f"tp: first-step logits differ by {cmp_['logits_rel_err']}")
    div = cmp_["first_divergence"]
    require(div is None or cmp_["divergence_within_tol"],
            f"tp: greedy streams diverge at {div} beyond tolerance: {cmp_}")
    require(cmp_["weight_devices"] == tp and cmp_["cache_devices"] == tp,
            f"tp: state not spread over {tp} devices: {cmp_}")
    if not rehearse:  # off-TPU the psum reduce is a recorded degrade
        require("DEGRADED" not in cmp_["ledger_tp"],
                f"tp: degraded: {cmp_['ledger_tp']}")
        require(dev["platform"] == "tpu", f"tp: ran on {dev['platform']}")
        require(dev["count"] >= tp, f"tp: only {dev['count']} devices")
        require("q40/pallas-fused" in cmp_["ledger_tp"],
                f"tp: no pallas-fused at tp={tp}: {cmp_['ledger_tp']}")
        # 4096 % 256 == 0, so the rule (ops/q40.py _fused_reduce_ok) picks
        # the ring for both column matmuls: it is the only reduce path
        require(cmp_["reduce"] == "tp_fused_reduce"
                and "tp_psum" not in cmp_["ledger_tp"],
                f"tp: reduce path is not the fused ring alone: {cmp_}")
        peaks = cmp_["load_peak_bytes"]
        rest = max(peaks[1:])
        require(rest > 0 and abs(peaks[0] - rest) <= 0.1 * rest,
                f"tp: device 0 peaked at {peaks[0]} B after the load, the "
                f"others at most {rest} B: a weight was staged whole")
    return dict(dev, compile=comp)


# ---------------------------------------------------------------------------
# Child side: each runs in its own process and owns the chip
# ---------------------------------------------------------------------------

def _say(obj: dict) -> None:
    print(CHILD_MARK + json.dumps(obj), flush=True)


def _report_compiles_at_exit() -> None:
    """Sum the seconds this process spends in XLA's backend compile (a
    persistent-cache hit costs only its read) and say so when it exits."""
    import atexit

    from jax import monitoring
    tot = {"what": "compile", "backend_compile_seconds": 0.0, "programs": 0,
           "persistent_cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tot["backend_compile_seconds"] += secs
            tot["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tot["persistent_cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    atexit.register(lambda: _say(dict(
        tot, backend_compile_seconds=round(tot["backend_compile_seconds"], 2))))


def _claim_device(rehearse: bool) -> dict:
    """A child's first act: the platform check."""
    from dllama_tpu.hostenv import configure_compile_cache
    cache = configure_compile_cache()
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(f"chip_smoke child: JAX found no TPU "
                         f"(platform {d.platform!r})")
    _report_compiles_at_exit()
    return {"what": "device", "platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "compile_cache": cache}


def child_kernels(rehearse: bool) -> None:
    dev = _claim_device(rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu import native
    from dllama_tpu.obs import cost, dispatch as obs_dispatch
    from dllama_tpu.ops import attention as att, q40
    from dllama_tpu.synth import model_cfg

    cost.set_backend(dev["kind"], dev["platform"])
    _say(dict(dev, peaks_source=cost.peaks()["source"],
              native_loader=native.have_native()))
    cfg = model_cfg("cpu-tiny" if rehearse else MODEL)
    pallas = "pallas_interpret" if rehearse else "pallas"
    D, H, V = cfg.dim, cfg.hidden_dim, cfg.vocab_size
    qkv = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_size
    # (name, n, d, stacked) — as the single-chip loader lays the model out
    # moe_w2: DeepSeek-V2's expert width, one whole-axis tile of 44
    # quantization blocks: the one-row body folds its partials at every step
    # there (q40._partial_rows), on eight sublanes at the other shapes
    shapes = [("wqkv", D, qkv, True), ("wo", D, D, True),
              ("w13", D, 2 * H, True), ("w2", H, D, True),
              ("wcls", D, V, False), ("moe_w2", 1408, D, True)]
    key = jax.random.PRNGKey(0)

    def rel_err(a_, b_):
        a_, b_ = np.asarray(a_, np.float32), np.asarray(b_, np.float32)
        require(np.isfinite(a_).all() and np.isfinite(b_).all(),
                "non-finite kernel output")
        return float(np.abs(a_ - b_).max() / max(np.abs(b_).max(), 1e-9))

    def random_q40(k1, k2, lead, n, d):
        np_ = q40.padded_n(n)
        qp = jax.random.bits(k1, (*lead, np_ // 2, d), jnp.uint8)
        sc = (0.004 + 0.008 * jax.random.uniform(k2, (*lead, np_ // 32, d))
              ).astype(jnp.float16)
        sc = sc * (jnp.arange(np_ // 32)[:, None] < n // 32)  # zero pad rows
        return q40.QTensor(qp, jax.lax.bitcast_convert_type(sc, jnp.uint16),
                           (n, d))

    for name, n, d, stacked in shapes:
        key, k1, k2, k3 = jax.random.split(key, 4)
        qt = random_q40(k1, k2, (2,) if stacked else (), n, d)
        w = q40.QLayerView(qt, jnp.int32(1)) if stacked else qt
        for rows in (1, 8, 16, 256):  # 256: the row-blocked form
            x = jax.random.normal(jax.random.fold_in(k3, rows), (rows, n),
                                  jnp.bfloat16)
            t0 = time.perf_counter()
            got = q40.matmul(x, w, impl=pallas, out_dtype=jnp.float32)
            ref = q40.matmul(x, w, impl="xla", out_dtype=jnp.float32)
            _say({"kernel": f"q40.{name}", "shape": [n, d], "rows": rows,
                  "stacked": stacked, "rel_err": rel_err(got, ref),
                  "tol": Q40_TOL,
                  "seconds": round(time.perf_counter() - t0, 2)})
            if q40._body(rows, q40._tiles(q40.padded_n(n), d)[0]) != "dot":
                # one row is contracted a quantization block at a time with no
                # weight rounded to bf16 (PR 50), the tile's bytes made bf16
                # as 32-bit words (PR 58: the chip's half of the proof that
                # pltpu.bitcast sets a word's bytes on rows as the interpreter
                # does), and 2 to SLICED_MAX_ROWS rows a 128-row slice at a
                # time by the same algebra (PR 62): both are held to the
                # float32 dequantization
                dense = q40.dequantize(w.sliced() if stacked else w)
                ref32 = jnp.dot(x.astype(jnp.float32), dense,
                                precision=jax.lax.Precision.HIGHEST)
                _say({"kernel": f"q40.{name}.f32", "shape": [n, d], "rows": rows,
                      "stacked": stacked, "rel_err": rel_err(got, ref32),
                      "tol": Q40_F32_TOL})

    # a decoded row's chosen experts in one launch (q40_mm_chosen), at
    # SmallThinker's gate (6 of a layer's 64 experts of 2560 x 768, one row;
    # the stack's last plane and a repeat among them), against one XLA
    # matmul an expert
    n, d, experts = (64, 96, 8) if rehearse else (2560, 768, 64)
    key, k1, k2, k3 = jax.random.split(key, 4)
    view = q40.QLayerView(random_q40(k1, k2, (2, experts), n, d), jnp.int32(1))
    chosen = jnp.asarray([experts - 1, 0, 5, 3, experts - 1, 2], jnp.int32)
    x1 = jax.random.normal(k3, (1, n), jnp.bfloat16)
    t0 = time.perf_counter()
    got = q40.matmul_experts(x1, view, experts, pallas, out_dtype=jnp.float32,
                             chosen=chosen)
    ref = jnp.stack([q40.matmul(x1, view.select(e, experts), impl="xla",
                                out_dtype=jnp.float32) for e in chosen])
    _say({"kernel": "q40.chosen_experts", "shape": [n, d], "rows": 1,
          "experts": experts, "chosen": len(chosen),
          "rel_err": rel_err(got, ref), "tol": Q40_TOL,
          "seconds": round(time.perf_counter() - t0, 2)})
    ref32 = jnp.stack([jnp.dot(
        x1.astype(jnp.float32), q40.dequantize(view.select(e, experts).sliced()),
        precision=jax.lax.Precision.HIGHEST) for e in chosen])
    _say({"kernel": "q40.chosen_experts.f32", "shape": [n, d], "rows": 1,
          "experts": experts, "chosen": len(chosen),
          "rel_err": rel_err(got, ref32), "tol": Q40_F32_TOL})

    # the auto choice inside a jit trace must be the Pallas kernel on a TPU
    # (w, x: the last pair of the loop above — wcls, 8 rows)
    obs_dispatch.reset()
    jax.block_until_ready(jax.jit(
        lambda v: q40.matmul(v, w, impl="auto"))(x))
    _say({"what": "auto_in_jit", "ledger": obs_dispatch.summary_line()})
    if not rehearse:
        require("q40/pallas-fused" in obs_dispatch.summary_line()
                and "DEGRADED" not in obs_dispatch.summary_line(),
                f"auto in jit: {obs_dispatch.summary_line()}")

    hq, hkv, dh, ps = cfg.n_heads, cfg.n_kv_heads, cfg.head_size, 16
    b, maxp = 4, cfg.seq_len // ps
    n_pages = 1 + b * maxp
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        b, maxp).astype(np.int32))
    pos = jnp.asarray([maxp * ps - 1, 2 * ps + 5, (maxp // 2) * ps, 0],
                      jnp.int32)  # ragged rows, incl. a full and a 1-token row
    key, kq, kk, kv = jax.random.split(key, 4)
    q = (jax.random.normal(kq, (b, hq, 1, dh)) * 0.5).astype(cfg.dtype)
    pool_shape = (2, n_pages, ps, hkv, dh)  # a page is token-major
    pk = jax.random.normal(kk, pool_shape, jnp.float32) * 0.5
    pv = jax.random.normal(kv, pool_shape, jnp.float32) * 0.5
    layer = jnp.int32(1)
    for quantized in (False, True):
        if quantized:
            (k_, sk), (v_, sv) = att.quantize_kv(pk), att.quantize_kv(pv)
            scales = (sk, sv)
        else:
            k_, v_, scales = pk.astype(cfg.dtype), pv.astype(cfg.dtype), None
        t0 = time.perf_counter()
        # a dense pool is the fused kernel's; an int8 pool's read is the
        # XLA live walk (its scale plane cannot be copied by the page)
        got = att.paged_decode_attention(
            q, k_, v_, layer, table, pos, scales=scales) if quantized else \
            att.fused_paged_attention(q, k_, v_, layer, table, pos,
                                      interpret=rehearse)
        ks, vs = scales if quantized else (None, None)
        ref = att._rows_ceiling_attention(
            q, att.paged_gather_layer(k_, layer, table, scale_pool=ks),
            att.paged_gather_layer(v_, layer, table, scale_pool=vs), pos)
        _say({"kernel": "paged_decode_attention" if quantized
              else "fused_paged_attention",
              "kv": "int8" if quantized else "dense",
              "geometry": {"hq": hq, "hkv": hkv, "dh": dh, "page": ps,
                           "rows": b, "max_pages": maxp},
              "rel_err": rel_err(got, ref), "tol": ATTN_TOL,
              "seconds": round(time.perf_counter() - t0, 2)})

    # the same kernel at a mixed step's 16 tokens a slot, at the served cells'
    # head geometry: a row whose block ends on the table's last position, one
    # that crosses a chunk of the walk, one inside it, one at 0.  LFM2's heads
    # of 64 lie two to a row of the pool (att.pool_rows), and the walk reads
    # such a row as it lies: checked at the pure-decode step's one token too
    for name, hq_, hkv_, dh_, ts in (("mistral-7b", 32, 8, dh, (16,)),
                                     ("olmoe-1b-7b", 16, 16, dh, (16,)),
                                     ("lfm2-24b-a2b", 32, 8, 64, (1, 16))):
        if rehearse:
            hq_, hkv_ = hq_ // 4, max(1, hkv_ // 4)
        key, kq, kk, kv = jax.random.split(key, 4)
        k_, v_ = (
            (jax.random.normal(kx, (2, n_pages, ps) + att.pool_rows(hkv_, dh_))
             * 0.5).astype(cfg.dtype) for kx in (kk, kv))
        for t in ts:
            q = (jax.random.normal(jax.random.fold_in(kq, t), (b, hq_, t, dh_))
                 * 0.5).astype(cfg.dtype)
            pos_t = jnp.asarray(
                [maxp * ps - t, att._WALK_PAGES * ps - 3, 2 * ps + 5, 0],
                jnp.int32)
            t0 = time.perf_counter()
            got = att.fused_paged_attention(q, k_, v_, layer, table, pos_t,
                                            interpret=rehearse)
            ref = att._rows_ceiling_attention(
                q, att.paged_gather_layer(k_, layer, table, dh=dh_),
                att.paged_gather_layer(v_, layer, table, dh=dh_), pos_t)
            _say({"kernel": "fused_paged_attention", "kv": "dense", "t": t,
                  "geometry": {"heads": name, "hq": hq_, "hkv": hkv_,
                               "dh": dh_, "pool_row": list(k_.shape[3:]),
                               "page": ps, "rows": b, "max_pages": maxp},
                  "rel_err": rel_err(got, ref), "tol": ATTN_TOL,
                  "seconds": round(time.perf_counter() - t0, 2)})
            # a slot's first chunk is copied behind the slot before it, into
            # the buffer a word carried across the grid's steps names (PR 64):
            # each slot read alone must give the same bits (tolerance 0)
            alone = jnp.concatenate([att.fused_paged_attention(
                q[i:i + 1], k_, v_, layer, table[i:i + 1], pos_t[i:i + 1],
                interpret=rehearse) for i in range(b)])
            _say({"kernel": "fused_paged_attention.slot-alone", "t": t,
                  "geometry": {"heads": name, "rows": b},
                  "rel_err": rel_err(got, alone), "tol": 0.0})

    # the live walk at prefill rows against one-shot attention over the whole
    # contiguous cache, at the one-stream cells' 32k context: a prompt at
    # position 0 (one block) and deep in the cache (many)
    s_len, t = (cfg.seq_len, 16) if rehearse else (32768, 256)
    key, kq, kk, kv = jax.random.split(key, 4)
    q = (jax.random.normal(kq, (1, hq, t, dh)) * 0.5).astype(cfg.dtype)
    ck = jax.random.normal(kk, (2, 1, hkv, s_len, dh), jnp.float32) * 0.5
    cv = jax.random.normal(kv, (2, 1, hkv, s_len, dh), jnp.float32) * 0.5
    for quantized in (False, True):
        if quantized:
            (k_, sk), (v_, sv) = att.quantize_kv(ck), att.quantize_kv(cv)
            scales = (sk, sv)
            k_ref, v_ref = att.dequant_kv(k_[1], sk[1]), att.dequant_kv(v_[1], sv[1])
        else:
            k_, v_, scales = ck.astype(cfg.dtype), cv.astype(cfg.dtype), None
            k_ref, v_ref = k_[1], v_[1]
        for p in (0, 20000 * s_len // 32768):
            t0 = time.perf_counter()
            got = att.live_gqa_attention(q, k_, v_, jnp.int32(p), layer=layer,
                                         scales=scales)
            ref = att._rows_ceiling_attention(q, k_ref, v_ref,
                                              jnp.asarray([p], jnp.int32))
            _say({"kernel": "live_gqa_attention",
                  "kv": "int8" if quantized else "dense",
                  "geometry": {"hq": hq, "hkv": hkv, "dh": dh, "s": s_len,
                               "t": t, "pos": p,
                               "block": att._kv_chunk(s_len)},
                  "rel_err": rel_err(got, ref), "tol": ATTN_TOL,
                  "seconds": round(time.perf_counter() - t0, 2)})

    _ring_writes(rehearse)
    _ring_reads(rehearse)


def _ring_reads(rehearse: bool) -> None:
    """A decoded token's recent rows (``ops/ssm.py``, PR 67) at Falcon-H1's and
    Granite's widths, depth and slots: the launch over the live positions
    (``recent_walk``) against the XLA form over the whole ring (``_recent``),
    at 1, 33, 64, 65 and 96 live positions a slot, watermarks in both halves of
    the ring, some rows' ``dt`` 0, and through ``ssm.read`` as the rule has it.
    The tolerance is twice the unit test's 1e-6 of the output's largest value:
    on the chip each form rounds the decay's exponent its own way, and the
    sweep's largest reading over 24 points was 1.04e-6 (96 live rows)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import ssm

    lives = np.array([1, 33, 64, 65, 96])
    for name, layers, b, h, p, g, n in (
            ("falcon-h1-34b", 18, 32, 32, 128, 2, 256),
            ("granite-4.0-h-small", 18, 16, 128, 64, 1, 128)):
        if rehearse:
            layers, b, h, n = 2, 5, h // 8, n // 8
        f = ssm.heads_a_row(h, p)
        rng = np.random.RandomState(11)

        def noise(shape, dtype, lo=None, hi=None):
            # a drawn vector of prime length repeated over the plane: one small
            # program a shape where a generator's is a second of every rehearsal
            v = rng.standard_normal(8191) if lo is None else rng.uniform(lo, hi, 8191)
            return jnp.resize(jnp.asarray(v, dtype), shape)

        rs = noise((layers, b, h, n, p), jnp.float32)
        rk = noise((layers, b, g, ssm.RING, n), jnp.bfloat16)
        rv = noise((layers, b, h // f, ssm.RING, f * p), jnp.bfloat16)
        rg = noise((layers, b, 1, ssm.RING, h), jnp.float32, 0.01, 0.1) * (
            jnp.arange(ssm.RING) % 7 != 3)[:, None]
        c = noise((b, g, 1, n), jnp.bfloat16)
        a = -noise((h,), jnp.float32, 0.5, 2.0)
        base = jnp.asarray(ssm.FOLD * (np.arange(b) % 5 > 1) * (1 + np.arange(b) % 4),
                           jnp.int32)
        pos = base + jnp.asarray(lives[np.arange(b) % 5] - 1, jnp.int32)
        layer = jnp.int32(layers - 1)
        t0 = time.perf_counter()
        want = jax.jit(ssm._recent)(c.astype(jnp.float32), rk, rv, rg, a, layer,
                                    pos, base)
        got = jax.jit(functools.partial(ssm.recent_walk, interpret=rehearse))(
            c, rk, rv, rg, a, layer, pos, base)
        geo = {"slots": b, "heads": h, "p": p, "groups": g, "n": n,
               "live": sorted(set(lives[np.arange(b) % 5].tolist()))}
        for what, x, y in (("y", got[0], want[0]), ("gq", got[1], want[1])):
            _say({"kernel": "ssm_recent_walk", "mixer": name, "what": what,
                  "geometry": geo, "tol": 2e-6,
                  "rel_err": float(jnp.max(jnp.abs(x - y))
                                   / jnp.maximum(jnp.max(jnp.abs(y)), 1e-30)),
                  "seconds": round(time.perf_counter() - t0, 2)})
        # the whole read as the rule has it on this backend: the launch beside
        # the state's product, against the XLA form beside the same product
        obs_dispatch.reset()
        whole = jax.jit(ssm.read)(c, rs, rk, rv, rg, a, layer, pos, base)
        paths = {k for k in obs_dispatch.dispatches() if k.startswith("ssm/")}
        if not rehearse:
            require(paths == {"ssm/state-read", "ssm/recent-walk"},
                    f"the read of {name}: the rule chose {paths}")
        keep, ssm.WALK_MIN_ROWS = ssm.WALK_MIN_ROWS, 1 << 30
        try:
            ref = jax.jit(ssm.read)(c, rs, rk, rv, rg, a, layer, pos, base)
        finally:
            ssm.WALK_MIN_ROWS = keep
        _say({"kernel": "ssm_recent_walk", "mixer": name, "what": "read",
              "geometry": geo, "tol": 2e-6,
              "rel_err": float(jnp.max(jnp.abs(whole - ref)) / jnp.max(jnp.abs(ref))),
              "seconds": round(time.perf_counter() - t0, 2)})


def _ring_writes(rehearse: bool) -> None:
    """A pure-decode step's ring writes (``ops/window.py``, PR 66) at
    Falcon-H1's and Granite's widths, depth and slots: the same tokens through
    the mixer's ``ssm.write`` and ``conv.state_write`` as the rule puts them
    (one launch a plane; one slab for Falcon-H1's ``dt`` ring) and as windows a
    row, four steps from slots that cross an aligned window's edge (15 -> 16) and the ring's end
    (127 -> 0, 63 -> 0 in the convolution's), some rows' ``dt`` masked: the
    four planes bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import conv, ssm, window

    # (name, mixer layers, slots, heads, head size, groups, state rows, channels)
    for name, layers, b, h, p, g, n, ch in (
            ("falcon-h1-34b", 18, 32, 32, 128, 2, 256, 5120),
            ("granite-4.0-h-small", 18, 16, 128, 64, 1, 128, 8448)):
        if rehearse:
            layers, b, h, n, ch = 2, 4, h // 8, n // 8, ch // 8
        f = ssm.heads_a_row(h, p)
        shapes = {"rk": ((layers, b, g, ssm.RING, n), jnp.bfloat16),
                  "rv": ((layers, b, h // f, ssm.RING, f * p), jnp.bfloat16),
                  "rg": ((layers, b, 1, ssm.RING, h), jnp.float32),
                  "cz": ((layers, b, 1, conv.RING, ch), jnp.bfloat16)}
        edge = np.array([14, 126, 62, 0, 15, 127, 63, 1])
        pos0 = jnp.asarray(edge[np.arange(b) % 8] + 128 * (np.arange(b) // 8),
                           jnp.int32)

        def steps(planes, seed):
            def one(planes, i):
                k = jax.random.fold_in(jax.random.PRNGKey(seed), i)
                kb, kx, kd, kz = jax.random.split(k, 4)
                layer = (i * (layers - 1)) % layers
                dt = jax.random.uniform(kd, (b, 1, h)) * (
                    jnp.arange(b) % 3 != 2)[:, None, None]
                rk, rv, rg = ssm.write(
                    planes["rk"], planes["rv"], planes["rg"],
                    jax.random.normal(kb, (b, g, 1, n), jnp.bfloat16),
                    jax.random.normal(kx, (b, h, 1, p), jnp.bfloat16), dt,
                    layer, pos0 + i)
                cz = conv.state_write(
                    planes["cz"], jax.random.normal(kz, (b, 1, ch), jnp.bfloat16),
                    layer, pos0 + i, 4)
                return dict(rk=rk, rv=rv, rg=rg, cz=cz), None
            return jax.lax.scan(one, planes, jnp.arange(4))[0]

        got = {}
        for form, min_rows in (("rule", window.PUT_MIN_ROWS), ("windows", 1 << 30)):
            keep, window.PUT_MIN_ROWS = window.PUT_MIN_ROWS, min_rows
            obs_dispatch.reset()
            t0 = time.perf_counter()
            try:
                planes = {k: jax.random.normal(
                    jax.random.PRNGKey(7), sh, dt) for k, (sh, dt) in shapes.items()}
                got[form] = jax.block_until_ready(jax.jit(
                    lambda pl_: steps(pl_, 3), donate_argnums=0)(planes))
            finally:
                window.PUT_MIN_ROWS = keep
            paths = {k: v for k, v in obs_dispatch.dispatches().items()
                     if k.startswith("ring/")}
            if form == "rule" and not rehearse:   # the dt ring of 32 heads: the slab
                want = {"ring/put-kernel": 3, "ring/put-slab": 1} if h % 128 \
                    else {"ring/put-kernel": 4}
                require(paths == want,
                        f"ring writes of {name}: the rule chose {paths}")
            if form == "windows":
                require(set(paths) == {"ring/windows"}, f"forced windows: {paths}")
        for k in shapes:
            same = bool(jnp.array_equal(got["rule"][k], got["windows"][k]))
            _say({"kernel": "ring_put", "plane": f"{name}.{k}",
                  "geometry": {"shape": list(shapes[k][0]), "rows": b,
                               "slots": "15->16, 127->0 (63->0 in cz)"},
                  "rel_err": 0.0 if same else 1.0, "tol": 0.0,
                  "seconds": round(time.perf_counter() - t0, 2)})
        del got


def child_moe(argv: list[str], rehearse: bool) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    a = ap.parse_args(argv)
    _say(_claim_device(rehearse))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.io import mfile
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import moe_ffn
    from dllama_tpu.obs import dispatch as obs_dispatch
    from dllama_tpu.ops import q40

    mf = mfile.MFile(a.model)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                              dtype=dtype, keep_quantized=True)
    params = jax.device_put({k: params[k] for k in ("router", "up", "gate", "down")})
    lp = {k: (q40.QLayerView(v, jnp.int32(1)) if isinstance(v, q40.QTensor)
              else v[1]) for k, v in params.items()}
    _say({"what": "moe_model", "arch": mf.spec.arch_name,
          "experts": cfg.n_experts, "active": cfg.n_active_experts,
          "dim": cfg.dim, "expert_width": cfg.hidden_dim})
    obs_dispatch.reset()
    for rows, strategy in ((1, "select-chosen"), (16, "all-experts"),
                           (256, "grouped")):
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim), dtype)
        t0 = time.perf_counter()
        got = jax.jit(lambda v: moe_ffn(v, lp, cfg.with_(
            quant_impl="pallas_interpret" if rehearse else "auto")))(x)
        ref = jax.jit(lambda v: moe_ffn(v, lp, cfg.with_(quant_impl="xla")))(x)
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        require(np.isfinite(got).all() and np.isfinite(ref).all(),
                "non-finite moe_ffn output")
        _say({"kernel": "moe_ffn", "strategy": strategy, "rows": rows,
              "rel_err": float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-9)),
              "tol": MOE_TOL, "seconds": round(time.perf_counter() - t0, 2)})
    _say({"what": "ledger", "ledger": obs_dispatch.summary_line()})


def child_packed(argv: list[str], rehearse: bool) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    a = ap.parse_args(argv)
    _say(_claim_device(rehearse))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.io import mfile
    from dllama_tpu.models import packing
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.params import load_params
    from dllama_tpu.models.transformer import forward_slots
    from dllama_tpu.parallel.mesh import make_mesh
    from dllama_tpu.runtime.engine import Engine

    mf = mfile.MFile(a.model)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    cfg, params = load_params(mf, ModelConfig.from_spec(mf.spec, dtype=dtype),
                              dtype=dtype, keep_quantized=True)
    b, t, ps, ctx = 16, 16, 16, 64
    maxp = ctx // ps
    eng = Engine(cfg, params, mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
                 batch=b, seq_len=ctx, kv_pages=b * maxp + 1, kv_page_size=ps)
    table = jnp.asarray(1 + np.arange(b * maxp, dtype=np.int32).reshape(b, maxp))
    rng = np.random.RandomState(7)
    toks = [jnp.asarray(rng.randint(3, cfg.vocab_size, (b, t)), jnp.int32)
            for _ in range(2)]

    def step(p, c, tok, pos, nv):
        return forward_slots(p, cfg, tok, c, pos, nv, table)

    packed_fn, every_fn = jax.jit(step), jax.jit(lambda *xs: step(*xs))
    # every slot holds 16 positions; then slots 3 and 11 feed a chunk of 16
    # and the other fourteen one token: 14 + 2 x 16 = 46 of 256 rows
    full = jnp.full((b,), t, jnp.int32)
    _, pool = packed_fn(eng.params, eng.cache, toks[0], 0 * full, full)
    nv = np.ones((b,), np.int32)
    nv[[3, 11]] = t
    t0 = time.perf_counter()
    got, _ = packed_fn(eng.params, pool, toks[1], full, jnp.asarray(nv))
    kept, packing.BUCKETS = packing.BUCKETS, ()  # no bucket: every row
    try:
        want, _ = every_fn(eng.params, pool, toks[1], full, jnp.asarray(nv))
    finally:
        packing.BUCKETS = kept
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    require(np.isfinite(got).all() and np.isfinite(want).all(),
            "non-finite logits")
    valid = int(np.minimum(nv, t).sum())
    _say({"what": "packed_step", "slots": b, "t": t, "valid_rows": valid,
          "run_rows": packing.run_rows(valid, b, t), "slot_rows": b * t,
          "rel_err": float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9)),
          "tol": Q40_TOL,
          "greedy_equal": bool((got.argmax(-1) == want.argmax(-1)).all()),
          "seconds": round(time.perf_counter() - t0, 2)})


def child_module(module: str, argv: list[str], rehearse: bool) -> None:
    """Platform check, then the user's entry point as ``__main__``."""
    _claim_device(rehearse)
    import runpy
    sys.argv = [module, *argv]
    runpy.run_module(module, run_name="__main__", alter_sys=True)


def child_tp(argv: list[str], rehearse: bool) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--tp", type=int, default=4)
    a = ap.parse_args(argv)
    dev = _claim_device(rehearse)
    _say(dev)
    import numpy as np

    from dllama_tpu import cli
    from dllama_tpu.obs import dispatch as obs_dispatch

    steps = 16

    def run(tp: int):
        obs_dispatch.reset()
        args = cli.build_parser().parse_args(
            ["inference", "--model", a.model, "--tokenizer", a.tokenizer,
             "--workers", f"tpu:{tp}", "--temperature", "0"])
        engine, tok = cli.load_stack(args)
        # per-device peak right after the load: a loader that stages a
        # whole stack on device 0 before sharding it shows here
        load_peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in engine.mesh.devices.flat]
        ids = tok.encode("hello hello hello", add_bos=engine.cfg.add_bos)
        logits, _ = engine.prefill(ids)
        for _ in range(2):  # the second pass is timed with programs compiled
            engine.reset()
            t0 = time.perf_counter()
            toks = [t for t, _ in engine.generate_stream(
                ids, len(ids) + steps, temperature=0.0, topp=0.9, seed=0,
                chunk=steps)][len(ids):]
            secs = time.perf_counter() - t0
        wname = "wq" if "wq" in engine.params else "wqkv"
        leaf = engine.params[wname].qpacked
        spread = (len(leaf.sharding.device_set),
                  len(engine.cache.k.sharding.device_set),
                  [int(s) for s in leaf.addressable_shards[0].data.shape],
                  load_peaks)
        return engine, ids, np.asarray(logits, np.float32)[0], toks, spread, \
            secs, obs_dispatch.summary_line()

    eng, ids, lg_tp, toks_tp, spread, secs_tp, ledger_tp = run(a.tp)
    reduce = ("tp_fused_reduce" if "tp_fused_reduce" in ledger_tp else
              "tp_psum" if "tp_psum" in ledger_tp else None)
    del eng
    eng1, _, lg_1, toks_1, _, secs_1, ledger_1 = run(1)
    rel = float(np.abs(lg_tp - lg_1).max() / max(np.abs(lg_1).max(), 1e-9))
    div = next((i for i, (x, y) in enumerate(zip(toks_tp, toks_1)) if x != y),
               None)
    margin = within = None
    if div is not None:
        # the logit margin at the step where the streams part: a split
        # inside the numeric noise between the two reduction orders is a
        # tie-break, not a fault
        eng1.reset()
        lg, _ = eng1.prefill(list(ids) + toks_1[:div])
        lg = np.asarray(lg, np.float32)[0]
        margin = float(abs(lg[toks_1[div]] - lg[toks_tp[div]])
                       / max(np.abs(lg).max(), 1e-9))
        within = margin <= TP_LOGIT_TOL
    _say({"what": "compare", "tp": a.tp, "logits_rel_err": rel,
          "logits_tol": TP_LOGIT_TOL, "tokens_tp": toks_tp, "tokens_tp1": toks_1,
          "first_divergence": div, "divergence_margin": margin,
          "divergence_within_tol": within, "weight_devices": spread[0],
          "cache_devices": spread[1], "weight_shard_shape": spread[2],
          "load_peak_bytes": spread[3],
          "reduce": reduce, "ledger_tp": ledger_tp, "ledger_tp1": ledger_1,
          "smoke_decode_seconds_tp": round(secs_tp, 3),
          "smoke_decode_seconds_tp1": round(secs_1, 3), "decode_tokens": steps})


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tp=4 path and the tp=1 run it is "
                         "compared with (needs four chips)")
    a = ap.parse_args(argv)
    t_start = time.monotonic()

    def left(reserve: float = 20) -> float:
        return BUDGET_S - (time.monotonic() - t_start) - reserve

    if not os.path.isdir(os.path.join(HERE, "dllama_tpu")):
        log("the dllama_tpu package is not next to this script")
        return 2
    sys.path.insert(0, HERE)
    from dllama_tpu.hostenv import compile_cache_dir  # no JAX in there
    from dllama_tpu.synth import synth_model_files

    cache = compile_cache_dir()
    n_before = cache_entries(cache)
    emit({"what": "setup", "model": MODEL, "chips": a.chips,
          "depth": "full (32 layers), no cut", "compile_cache": cache,
          "cache_entries_before": n_before,
          "cache": "warm" if n_before else "cold"})
    # model files live outside the checkout (a 4 GB file in the tree can
    # make it too large to copy) and are removed at the end
    tmp = tempfile.mkdtemp(prefix="dllama_smoke_")
    device = None
    compiles = []  # one row per child that held the chip

    def timed(name, fn, *args):
        t0 = time.monotonic()
        out = fn(*args)
        if isinstance(out, dict) and out.get("compile"):
            compiles.append(out["compile"])
        emit({"what": "phase_done", "phase": name,
              "seconds": round(time.monotonic() - t0, 1)})
        return out

    try:
        if a.chips == 1:
            device = timed("kernels", phase_kernels, min(420, left()))
        mpath, tpath = timed("synth", synth_model_files, MODEL, tmp)
        emit({"what": "model", "bytes": os.path.getsize(mpath)})
        require("jax" not in sys.modules, "the parent imported JAX")
        if a.chips == 1:
            timed("cli", phase_cli, mpath, tpath, min(600, left()))
            timed("server", phase_server, mpath, tpath, min(600, left()), tmp)
            timed("packed", phase_packed, mpath, min(300, left()))
            os.remove(mpath)  # room for the next file
            mpath, tpath = timed("synth_moe", synth_model_files, MOE_MODEL,
                                 tmp, MOE_LAYERS)
            timed("moe", phase_moe, mpath, min(420, left()))
            timed("moe_server", lambda: phase_server(
                mpath, tpath, min(420, left()), tmp, max_tokens=16))
        else:
            device = timed("tp", phase_tp, mpath, tpath, min(420, left()), 4)
        require(device["platform"] == "tpu", "no TPU held the run")
        require(device["count"] >= a.chips,
                f"{device['count']} chips, wanted {a.chips}")
        device = dict(device, count=a.chips)
    except PhaseFailed as e:
        log(f"FAILED: {e}")
        emit({"what": "failed", "error": str(e)})
        return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit({"what": "done", "cache_entries_after": cache_entries(cache),
          "cache_entries_before": n_before,
          "backend_compile_seconds": round(sum(
              c["backend_compile_seconds"] for c in compiles), 2),
          "persistent_cache_hits": sum(
              c["persistent_cache_hits"] for c in compiles),
          "wall_seconds": round(time.monotonic() - t_start, 1)})
    print(last_line(device), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--phase":
        phase, rest = sys.argv[2], sys.argv[3:]
        rehearse = rest[:1] == ["--rehearse"]
        rest = rest[1:] if rehearse else rest
        if phase == "kernels":
            child_kernels(rehearse)
        elif phase == "moe":
            child_moe(rest, rehearse)
        elif phase == "packed":
            child_packed(rest, rehearse)
        elif phase == "cli":
            child_module("dllama_tpu", rest, rehearse)
        elif phase == "server":
            child_module("dllama_tpu.server.api", rest, rehearse)
        elif phase == "tp":
            child_tp(rest, rehearse)
        else:
            raise SystemExit(f"unknown phase {phase!r}")
    else:
        sys.exit(main())
