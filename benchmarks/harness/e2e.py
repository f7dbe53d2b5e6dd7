"""End-to-end metrics from the load generator's request records: the
arithmetic every PR is judged by.  Stdlib only.

A request counts toward a latency metric by where its evidence lies: its first
token inside the window (``ttft``), its last token inside the window and at
least ``MIN_OUT`` tokens (``tpot``, ``stall``).  Throughput counts every token
event stamped inside the window from any request that did not fail, whether it
ended inside the window, was cut at the window's end or had begun in the
pre-roll: in a closed loop that is the steady rate, while counting only whole
requests would drop a batch-full of partial streams at either edge.
"""

from __future__ import annotations

import json
from statistics import median

MIN_OUT = 33  # a tpot from fewer tokens is quantized by 16-token bursts
# manifest name -> key of summarize(), where they differ
SUMMARY_KEY = {"serve_itl_p50_ms": "itl_p50_ms"}


def percentile(vals: list[float], q: float) -> float | None:
    if not vals:
        return None
    s = sorted(vals)
    k = (len(s) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def read_records(path: str) -> tuple[dict, list[dict]]:
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    return lines[0], lines[1:]


def summarize(head: dict, recs: list[dict]) -> dict:
    t0, t1 = head["window"]
    inside = [r for r in recs if r.get("sent") and r["sent"] < t1
              and (r.get("end") or t1) > t0]
    failed = [r for r in inside if not r["ok"] and not r["cut"]]
    good = [r for r in inside if r["ok"] or r["cut"]]
    tokens_in = sum(1 for r in good for t in r["times"] if t0 <= t < t1)
    ttft = [(r["times"][0] - r["due"]) * 1e3 for r in good
            if r["times"] and r["due"] >= t0 and r["times"][0] < t1]
    whole = [r for r in good if r["ok"] and r["n_out"] >= MIN_OUT
             and r["due"] >= t0 and r["times"][-1] < t1]
    tpot = [(r["times"][-1] - r["times"][0]) / (r["n_out"] - 1) * 1e3
            for r in whole]
    stall = [max(b - a for a, b in zip(r["times"], r["times"][1:])) * 1e3
             for r in whole]
    # every gap between successive tokens of a request, both inside the window:
    # in a served cell its median is the pure-decode step, steady to 0.2% from
    # run to run where tokens/s (the share of prefill steps) spreads by 3-7%
    gaps = [(b - a) * 1e3 for r in good for a, b in zip(r["times"], r["times"][1:])
            if t0 <= a and b < t1]
    late = [(r["sent"] - r["due"]) * 1e3 for r in inside if r["due"] >= t0]
    done = [r for r in good if r["ok"] and r["times"] and r["times"][-1] < t1
            and r["due"] >= t0]
    return {
        "attempted": len(inside), "failed": len(failed),
        "errors": [r.get("error") or r.get("status") for r in failed][:3],
        "window_s": t1 - t0,
        "out_tok_s": tokens_in / (t1 - t0),
        "ttft_p50_ms": median(ttft) if ttft else None,
        "tpot_p50_ms": median(tpot) if tpot else None,
        "stall_p50_ms": median(stall) if stall else None,
        "itl_p50_ms": median(gaps) if gaps else None,
        "loadgen_late_p95_ms": percentile(late, 0.95),
        "n_ttft": len(ttft), "n_whole": len(whole),
        "n_completed": len(done),
        "n_short": sum(1 for r in done if r["n_out"] < MIN_OUT),
        "n_early_eos": sum(1 for r in done if r["n_out"] < r["max_tokens"]),
        "out_tokens_in_window": tokens_in,
    }
