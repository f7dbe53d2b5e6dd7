#!/usr/bin/env python3
"""Dump a running dllama-api server's span ring as a Chrome trace file.

Fetches ``GET /debug/trace?last=N`` (dllama_tpu/obs/trace.py) and writes
the Chrome ``trace_event`` JSON to a file loadable in ``chrome://tracing``
or https://ui.perfetto.dev — the cheap first-line latency attribution for
a live server (api.request / api.lock_wait / engine.prefill /
engine.chunk_fetch / api.emit / sched.* spans
per request ID), no restart and no ``--profile-split`` XLA tracer needed.

With ``--slots`` it also fetches ``GET /debug/timeline`` (the scheduler's
per-dispatch slot timeline, obs/flight.py) and appends one named Perfetto
track per scheduler slot (pid 2): every dispatch becomes one event per
slot, named by that slot's phase (``prefill``/``decode``/``pad``), so the
goodput decomposition is visible as colored bars next to the request
spans — both use the same ``perf_counter`` clock.

With ``--fleet`` the base URL is a *router* (or serve-pod front door)
and the dump comes from ``GET /debug/trace?scope=fleet``: the router
stitches every replica's span ring plus its own into one wall-clock-
aligned Perfetto timeline — one named process track per replica, pod
journal entries (spawn/death/respawn/hand-off/resume…) as instant
markers — so a request that migrated across replicas shows up as one
trace id spanning multiple tracks. ``--trace ID`` filters to one
request's trace across the whole fleet.

Usage:
    python tools/trace_dump.py http://127.0.0.1:9090 [-o trace.json] [-n 20]
    python tools/trace_dump.py http://127.0.0.1:9090 --slots
    python tools/trace_dump.py http://127.0.0.1:8080 --fleet [--trace ID]
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request
from collections import Counter


def fetch_trace(base: str, last: int, timeout: float = 10.0) -> dict:
    url = f"{base.rstrip('/')}/debug/trace?last={last}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def fetch_timeline(base: str, n: int = 256, timeout: float = 10.0) -> dict:
    url = f"{base.rstrip('/')}/debug/timeline?n={n}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def fetch_fleet(base: str, trace: str | None,
                timeout: float = 10.0) -> dict:
    url = f"{base.rstrip('/')}/debug/trace?scope=fleet"
    if trace:
        url += f"&trace={trace}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def summarize_fleet(doc: dict) -> str:
    """Per-replica span/up table plus the distinct trace ids that span
    more than one process — the migrated requests worth opening."""
    fleet = doc.get("fleet", {})
    spans = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    marks = [e for e in doc.get("traceEvents", []) if e.get("ph") == "i"]
    lines = [f"{len(spans)} spans + {len(marks)} journal markers "
             f"from {len(fleet)} process(es):"]
    for name, info in sorted(fleet.items()):
        up = "up" if info.get("up") else "DOWN"
        lines.append(f"  {name:<22} {up:<5} {info.get('spans', 0):>5} spans")
    # trace ids seen on more than one pid = cross-replica requests
    procs: dict = {}
    for e in spans:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            procs.setdefault(tid, set()).add(e.get("pid"))
    crossed = sorted(t for t, p in procs.items() if len(p) > 1)
    if crossed:
        lines.append(f"  {len(crossed)} trace(s) span multiple replicas:")
        for t in crossed[:8]:
            lines.append(f"    {t}")
    return "\n".join(lines)


def slot_events(doc: dict) -> list[dict]:
    """Chrome ``trace_event`` array for the slot timeline: pid 2, one
    named thread per scheduler slot, one X event per (dispatch, slot)
    named by the slot's phase in that dispatch."""
    steps = doc.get("steps", [])
    nslots = doc.get("slots", 0) or max(
        (len(e.get("slots", [])) for e in steps), default=0)
    events = [{"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
               "args": {"name": "slot timeline"}}]
    for s in range(nslots):
        events.append({"name": "thread_name", "ph": "M", "pid": 2,
                       "tid": s, "args": {"name": f"slot {s}"}})
    for e in steps:
        ts = round(e["ts"] * 1e6, 3)
        dur = round(e["wall_ms"] * 1e3, 3)
        for slot in e.get("slots", []):
            args = {"tokens": slot.get("tokens", 0),
                    "steps": e.get("steps"), "t_width": e.get("t_width")}
            if slot.get("request_id"):
                args["request_id"] = slot["request_id"]
            if e.get("error"):
                args["error"] = True
            events.append({"name": slot.get("phase", "?"), "cat": "sched",
                           "ph": "X", "ts": ts, "dur": dur,
                           "pid": 2, "tid": slot.get("slot", 0),
                           "args": args})
    return events


def summarize(doc: dict) -> str:
    """Per-span-name count + total ms, so the terminal shows where the
    time went before anyone opens Perfetto."""
    spans = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    counts = Counter(e["name"] for e in spans)
    total_ms: Counter = Counter()
    for e in spans:
        total_ms[e["name"]] += e.get("dur", 0.0) / 1000.0
    rids = {e["args"]["request_id"] for e in spans
            if e.get("args", {}).get("request_id")}
    lines = [f"{len(spans)} spans across {len(rids)} request(s):"]
    for name, n in counts.most_common():
        lines.append(f"  {name:<16} x{n:<5} {total_ms[name]:9.1f} ms total")
    # QoS story: admissions per priority class, plus the preempt/resume
    # pairs with time spent parked (sched_preempt / sched_resume spans
    # carry priority, reason and parked_ms in their args)
    admits = Counter(e["args"].get("priority") or "?"
                     for e in spans if e["name"] == "sched_admit")
    preempts = Counter(e["args"].get("reason") or "?"
                       for e in spans if e["name"] == "sched_preempt")
    parked_ms = sum(e["args"].get("parked_ms") or 0.0
                    for e in spans if e["name"] == "sched_resume")
    if admits:
        mix = " ".join(f"{k}={v}" for k, v in admits.most_common())
        lines.append(f"  admits by class: {mix}")
    if preempts:
        why = " ".join(f"{k}={v}" for k, v in preempts.most_common())
        lines.append(f"  preemptions: {why}; "
                     f"{parked_ms:.0f} ms total parked")
    # speculative decoding story: sched_verify spans carry per-dispatch
    # proposed/accepted draft counts (--spec; runtime/spec.py)
    verifies = [e for e in spans if e["name"] == "sched_verify"]
    proposed = sum(e["args"].get("proposed") or 0 for e in verifies)
    accepted = sum(e["args"].get("accepted") or 0 for e in verifies)
    if proposed:
        lines.append(f"  speculation: {accepted}/{proposed} drafts "
                     f"accepted ({accepted / proposed:.2f}) over "
                     f"{len(verifies)} verify dispatches")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("base", help="server base URL, e.g. http://127.0.0.1:9090")
    ap.add_argument("-o", "--out", default="trace.json",
                    help="output file (default trace.json)")
    ap.add_argument("-n", "--last", type=int, default=20,
                    help="number of most-recent requests to include")
    ap.add_argument("--slots", action="store_true",
                    help="also fetch /debug/timeline and add one Perfetto "
                         "track per scheduler slot (phase-named events)")
    ap.add_argument("--timeline-n", type=int, default=256,
                    help="with --slots: number of most-recent dispatches")
    ap.add_argument("--fleet", action="store_true",
                    help="base is a router/pod: fetch the stitched "
                         "fleet-wide trace (/debug/trace?scope=fleet)")
    ap.add_argument("--trace", default=None,
                    help="with --fleet: filter to one trace id")
    ap.add_argument("--timeout", type=float, default=10.0)
    args = ap.parse_args(argv)

    if args.fleet:
        try:
            doc = fetch_fleet(args.base, args.trace, args.timeout)
        except Exception as e:
            print(f"trace_dump: fleet fetch failed: {e}", file=sys.stderr)
            return 1
        with open(args.out, "w") as f:
            json.dump(doc, f)
        print(f"wrote {args.out} — load it in chrome://tracing or "
              f"https://ui.perfetto.dev")
        print(summarize_fleet(doc))
        return 0

    try:
        doc = fetch_trace(args.base, args.last, args.timeout)
    except Exception as e:
        print(f"trace_dump: fetch failed: {e}", file=sys.stderr)
        return 1
    if not doc.get("traceEvents"):
        print("trace_dump: no spans recorded yet (serve a request first)",
              file=sys.stderr)
    if args.slots:
        try:
            tl = fetch_timeline(args.base, args.timeline_n, args.timeout)
        except Exception as e:
            print(f"trace_dump: timeline fetch failed: {e}", file=sys.stderr)
            return 1
        doc["traceEvents"] = doc.get("traceEvents", []) + slot_events(tl)
        gp = tl.get("goodput_ratio")
        comp = tl.get("components_ms") or {}
        if comp:
            split = " ".join(f"{k}={v:.0f}ms"
                             for k, v in sorted(comp.items()))
            print(f"goodput {gp:.3f} over {len(tl.get('steps', []))} "
                  f"dispatches: {split}")
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print(f"wrote {args.out} — load it in chrome://tracing or "
          f"https://ui.perfetto.dev")
    print(summarize(doc))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
