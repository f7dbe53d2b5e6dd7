#!/usr/bin/env python3
"""One cell, one run: ``python3 benchmarks/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Loads the cell's data (``cells/``, ``configs/``, ``traffic/``) and the
configuration's architecture (``models/<name>.py``, found by
``harness/models.py``), makes the model files from the configuration's seed if
this checkout has none yet, starts the server through its normal entry point on
a thread of this process, warms up every shape the cell's traffic uses, drives
the measured window from a JAX-free child (``harness/loadgen.py``), checks the
outputs, and prints one JSON object as the last line of its standard output.  Needs a TPU; ``--rehearse`` runs the
same control flow on the CPU at toy widths and reports no device metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import correct, e2e, loadgen, mformat, models, tokens  # noqa: E402
from harness.server import Server, counter_total  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
OUT = os.path.join(HERE, "out")
TRACE_S = 5.0        # length of the traced sub-window, mid-run
SAMPLE_HZ = 5.0      # /metrics gauges are sampled at this rate in a traced run
N_CHECK, CHECK_LEN = 8, 32


def log(msg: str) -> None:
    print(f"benchmark[{time.time() - T_START:7.1f}s]: {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The manifest entry and the three data files it names."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    mix_path = os.path.join(HERE, "traffic", entry["traffic"] + ".json")
    return {"name": name, "chips": entry["chips"], "config_name": entry["config"],
            "cell": load_json(os.path.join(HERE, "cells", name + ".json")),
            "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
            "mix": load_json(mix_path), "mix_path": mix_path,
            "end_to_end": [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]}


def model_shape(model, config: dict, rehearse: bool) -> dict:
    """The ``.m`` header's sizes as the configuration's architecture reads them
    from the file's (published) keys; a rehearsal gets its toy widths."""
    shape = model.shape(config)
    return dict(shape, **model.REHEARSE) if rehearse else shape


def ensure_files(name: str, model, shape: dict, seed: int) -> tuple[str, str]:
    """The model and tokenizer of this checkout, made once from the
    configuration's seed and reused by every later run (weights are not made
    from ``--seed``: a 4-21 GB file per run would be most of every run)."""
    os.makedirs(CACHE, exist_ok=True)
    stem = os.path.join(CACHE, f"{name}-L{shape['n_layers']}-d{shape['dim']}-s{seed}")
    if not os.path.exists(stem + ".t"):
        tokens.write_tokenizer(stem + ".t", shape["vocab_size"])
    if not os.path.exists(stem + ".m"):
        log(f"synthesizing {stem}.m")
        mformat.synthesize(stem + ".m", model, shape, seed)
    return stem + ".m", stem + ".t"


def reference_logits(model, mpath: str, prompts: list[list[int]], endpoint: str):
    """The float32 reference's logits for the check prompts, computed once per
    checkout and kept beside the model file."""
    import numpy as np
    path = f"{mpath[:-2]}.ref-{endpoint}-{len(prompts)}x{len(prompts[0])}.npy"
    if os.path.exists(path):
        return np.load(path)
    log("running the float32 reference (first run in this checkout)")
    full = [tokens.encode_text(tokens.text_of(p), endpoint) for p in prompts]
    logits = model.last_logits(mpath, full)
    np.save(path + ".part.npy", logits)
    os.replace(path + ".part.npy", path)
    return logits


def device_info(chips: int) -> dict:
    import jax
    devs = jax.local_devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


class Sampler(threading.Thread):
    """Reads ``/metrics`` at SAMPLE_HZ during a traced run."""

    def __init__(self, srv):
        super().__init__(daemon=True)
        self.srv, self.rows, self._stop_ev = srv, [], threading.Event()

    def run(self):
        while not self._stop_ev.wait(1.0 / SAMPLE_HZ):
            try:
                self.rows.append((time.time(), self.srv.metrics()))
            except (OSError, ValueError):
                pass

    def stop(self):
        self._stop_ev.set()
        self.join(5)


def read_layer_metrics(specs: list[dict], ctx: dict) -> dict:
    """Each per-layer metric is a reader of its own under ``layer_metrics/``,
    found by its name; one that finds nothing to read is left out."""
    out = {}
    sys.path.insert(0, os.path.join(HERE, "layer_metrics"))
    for spec in specs:
        path = os.path.join(HERE, "layer_metrics", spec["name"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(spec["name"], path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        if ctx["summary"].get(e2e.SUMMARY_KEY.get(spec["moves"], spec["moves"])) is None:
            continue  # reported only where the metric it moves is
        value = mod.read(ctx)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy widths: control flow only, no device metric")
    a = ap.parse_args(argv)
    real_stdout = sys.stdout
    sys.stdout = sys.stderr  # the server prints; the last line is ours alone

    if not os.path.isdir(os.path.join(ROOT, "dllama_tpu")):
        raise SystemExit("the program (dllama_tpu/) is not in this checkout")
    c = load_cell(a.workload)
    cfg, mix, chips = c["config"], c["mix"], c["chips"]
    endpoint = mix["endpoint"]
    model = models.for_config(cfg)
    shape = model_shape(model, cfg, a.rehearse)

    if a.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    # one fixed directory inside the checkout for every program this process
    # compiles, the reference's included; it is where the program would put its
    # own (hostenv.configure_compile_cache), which leaves a set variable alone
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "build", "xla_cache"))
    import jax
    devs = jax.devices()
    if a.rehearse:
        if devs[0].platform != "cpu":
            raise SystemExit("--rehearse is for the CPU")
    elif devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"this cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} x {devs[0].platform}")
    from harness import peaks as peaks_mod
    peak = None if a.rehearse else peaks_mod.peaks(devs[0].device_kind)

    mpath, tpath = ensure_files(
        c["config_name"] + ("-rehearse" if a.rehearse else ""), model, shape,
        int(cfg["weights_seed"]))
    vocab = shape["vocab_size"]
    checks = correct.check_prompts(int(cfg["weights_seed"]), N_CHECK, CHECK_LEN, vocab)
    ref = reference_logits(model, mpath, checks, endpoint)

    srv = Server(["--model", mpath, "--tokenizer", tpath, "--temperature", "0",
                  *c["cell"]["argv"]])
    log(f"starting server: {' '.join(srv.argv)}")
    srv.start()
    health = srv.wait_ready(1100)
    log(f"server ready (backend {health.get('backend')})")

    notes: list[str] = []
    # ---- set-up: length probe, reference check, every shape, solo request
    probe = loadgen.send_request(srv.base, endpoint, checks[0], 1, stream=False)
    usage = (probe.get("usage") or {}).get("prompt_tokens")
    if usage != CHECK_LEN + tokens.overhead(endpoint):
        notes.append(f"usage.prompt_tokens {usage} != "
                     f"{CHECK_LEN + tokens.overhead(endpoint)}")
    served = []
    for p in checks:
        r = loadgen.send_request(srv.base, endpoint, p, 1, keep_text=True)
        served.append(correct.served_token(r.get("text", "")) if r["ok"] else None)
    verdict = correct.compare(ref, served)
    log(f"reference check: {verdict['exact']}/{N_CHECK} exact, worst "
        f"{max(9.0 if r['below_max_sigma'] is None else r['below_max_sigma'] for r in verdict['prompts']):.4f} sigma below the reference's maximum")
    if not verdict["ok"]:
        notes.append("served first tokens disagree with the float32 reference")

    rng = random.Random(f"{a.seed}/setup")
    warm = mix["warmup"]
    over = tokens.overhead(endpoint)
    for n, n_out in warm["requests"]:
        ids = [rng.randrange(3, vocab) for _ in range(n - over)]
        r = loadgen.send_request(srv.base, endpoint, ids, n_out)
        if not r["ok"]:
            notes.append(f"warm-up request failed: {r.get('error') or r['status']}")
    # the solo request (a warm-up shape again) is repeated until a pass
    # compiles nothing; the last pass is the "before" of check (b)
    solo_n, solo_out = warm.get("solo") or warm["requests"][-1]
    solo_ids = [rng.randrange(3, vocab) for _ in range(solo_n - over)]
    for rnd in range(4):
        before = counter_total(srv.metrics(), "engine_recompiles")
        solo = [loadgen.send_request(srv.base, endpoint, solo_ids, solo_out,
                                     keep_text=True)]
        rose = counter_total(srv.metrics(), "engine_recompiles") - before
        log(f"solo pass {rnd}: {solo[0]['n_out']} tokens, {rose:.0f} new programs")
        if not rose:
            break

    # ---- the window: pre-roll, then --seconds, driven by the JAX-free child
    os.makedirs(OUT, exist_ok=True)
    rec_path = os.path.join(OUT, f"{a.workload}.s{a.seed}.t{a.trace}.requests.jsonl")
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
         "--base", srv.base, "--mix", c["mix_path"], "--seed", str(a.seed),
         "--vocab", str(vocab), "--seconds", str(a.seconds), "--out", rec_path],
        stdout=sys.stderr, stderr=sys.stderr, env=dict(os.environ, PYTHONPATH=""))
    preroll = float(mix.get("preroll_s", 0))
    try:
        t_launch = time.time()
        time.sleep(max(preroll - 0.05, 0))
        m_before = srv.metrics()
        sampler, traced = None, None
        if a.trace:
            sampler = Sampler(srv)
            sampler.start()
            trace_s = min(TRACE_S, a.seconds / 2)
            time.sleep(max((a.seconds - trace_s) / 2, 0))
            trace_dir = os.path.join(OUT, f"trace-{a.workload}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            t_tr0 = time.time()
            time.sleep(trace_s)
            t_tr1 = time.time()
            jax.profiler.stop_trace()
            traced = (trace_dir, t_tr0, t_tr1)
        rc = child.wait(timeout=preroll + a.seconds + 120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if sampler is not None:
        sampler.stop()
    m_after = srv.metrics()
    with open(os.path.join(OUT, f"{a.workload}.metrics-after.json"), "w") as f:
        json.dump(m_after, f, indent=1)
    if rc != 0:
        notes.append(f"load generator exited {rc}")
    head, recs = e2e.read_records(rec_path)
    summary = e2e.summarize(head, recs)
    summary["setup_s"] = head["window"][0] - T_START
    log(f"window done: {json.dumps({k: v for k, v in summary.items() if k != 'errors'})}")

    # ---- after the window: same solo request, counters
    for _ in range(60):  # cut streams retire at the scheduler's next step
        if not srv.metrics().get("sched_slots_occupied"):
            break
        time.sleep(0.25)
    solo.append(loadgen.send_request(srv.base, endpoint, solo_ids, solo_out,
                                     keep_text=True))
    if not (solo[0]["ok"] and solo[1]["ok"] and solo[0]["text"] == solo[1]["text"]):
        notes.append("the solo greedy request differs before and after the window")
    compiled = counter_total(m_after, "engine_recompiles") \
        - counter_total(m_before, "engine_recompiles")
    if compiled:
        notes.append(f"{compiled:.0f} program(s) compiled inside the window")
    # off the TPU the program records its psum reduce and XLA paths as
    # degrades by design, so a rehearsal does not judge them
    m_end = srv.metrics()
    for key in () if a.rehearse else ("q40_degrade", "attn_degrade"):
        if counter_total(m_end, key):
            notes.append(f"{key} is not 0: {m_end.get(key)}")
    if summary["failed"]:
        notes.append(f"{summary['failed']} request(s) failed: {summary['errors']}")

    device = device_info(chips)
    result = {"correct": not notes, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": {}, "device": device,
              "notes": notes, "reference": verdict,
              "counts": {k: summary[k] for k in (
                  "n_ttft", "n_whole", "n_completed", "n_short", "n_early_eos",
                  "out_tokens_in_window")}}
    if a.trace:
        from harness import xplane
        tr = xplane.load(xplane.find_xplane(traced[0]))
        red = xplane.reduce(tr)
        ctx = {"summary": summary, "before": m_before, "after": m_after,
               "samples": sampler.rows, "trace": red, "config": cfg,
               "cell": c["cell"], "mix": mix, "chips": chips, "peaks": peak,
               "records": recs, "window": head["window"],
               "traced_window": traced[1:], "device": device}
        result["metrics"] = read_layer_metrics(c["per_layer"], ctx)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {
            "device_ops": xplane.top_ops(red, 10),
            "idle_gaps": xplane.attribute_gaps(tr, red["idle_gaps"], 5)}
        with open(os.path.join(OUT, f"{a.workload}.trace-summary.json"), "w") as f:
            json.dump({"reduced": dict(red, ops=xplane.top_ops(red, 60)),
                       "host_window_s": traced[2] - traced[1],
                       "planes": tr["planes"]}, f, indent=1)
    else:
        for spec in c["end_to_end"]:
            value = summary.get(e2e.SUMMARY_KEY.get(spec["name"], spec["name"]))
            if value is not None:
                result["metrics"][spec["name"]] = {"value": float(value),
                                                   "unit": spec["unit"]}
    if a.rehearse:  # a CPU run carries no number under a device metric's name
        log(f"rehearsal only, not reported: {json.dumps(result['metrics'])}")
        result["metrics"] = {}
        result["device"] = {"platform": devs[0].platform,
                            "kind": devs[0].device_kind, "count": chips}
    stopped = srv.stop()
    log(f"server stopped: {stopped}; notes: {notes}")
    real_stdout.write(json.dumps(result) + "\n")
    real_stdout.flush()
    sys.stderr.flush()
    os._exit(0)  # the server's daemon threads may still hold the device


if __name__ == "__main__":
    main()
