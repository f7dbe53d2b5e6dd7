"""The one traffic generator.  A mix is a data file (``traffic/<mix>.json``);
this module turns it and a seed into jobs.  Stdlib only.

A *job* is one client's unit of work: a session of ``turns`` requests sent one
after another (one turn = one request; a plain mix has one turn).  Job ``j``
is the same for a given mix and seed whoever issues it and whenever:

* lengths are stratified: every block of ``STRATA`` consecutive jobs holds the
  ``STRATA`` evenly spaced quantiles of the length distribution, in an order
  shuffled by the seed, so any window of a run carries nearly the same work
  whatever the seed (the spread of a run comes from the system, not the draw);
* token ids come from ``random.Random`` keyed by (seed, job), never 0..2;
* ``sharing.prefix_tokens`` ids are common to all jobs of a group
  (``j % sharing.groups``), the rest are the job's own.

Mix keys (all lengths in prompt tokens as the server counts them, BOS and
chat template included):

  loop           "closed" (``clients`` callers, each waits for its reply) or
                 "open" (``arrivals``: kind poisson | onoff, rate_per_s, and
                 for onoff period_s, on_s, on_factor; ``max_inflight``)
  endpoint       "completions" (/v1/completions) or "chat" (/v1/chat/completions)
  prompt_tokens  a distribution: {"dist": "fixed"|"uniform"|"lognormal", ...}
  output_tokens  a distribution; sent as max_tokens
  sharing        optional {"prefix_tokens": n, "groups": g}
  session        optional {"turns": k, "turn_tokens": dist, "think_s": s}
  preroll_s      seconds of the same traffic before the measured window
  warmup         {"requests": [[prompt_tokens, output_tokens], ...]}: sent one
                 at a time in set-up so that every shape is compiled; the last
                 one is also the request sent alone before and after the window
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

STRATA = 16


def quantile(dist: dict, u: float) -> int:
    """The ``u`` quantile (0 < u < 1) of a length distribution, clipped."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def _stratum(seed: int, what: str, j: int) -> float:
    """Job ``j``'s quantile: block j // STRATA is a seeded shuffle of the
    STRATA mid-quantiles."""
    block, k = divmod(j, STRATA)
    order = list(range(STRATA))
    random.Random(f"{seed}/{what}/{block}").shuffle(order)
    return (order[k] + 0.5) / STRATA


def job(mix: dict, seed: int, j: int, vocab_size: int, overhead: int) -> list[dict]:
    """The turns of job ``j``: each ``{"ids": [...], "max_tokens": n}`` where
    ``ids`` are the prompt's own token ids (the server adds ``overhead``)."""
    n_prompt = quantile(mix["prompt_tokens"], _stratum(seed, "prompt", j))
    n_out = quantile(mix["output_tokens"], _stratum(seed, "output", j))
    share = mix.get("sharing") or {}
    n_shared = min(int(share.get("prefix_tokens", 0)), n_prompt - overhead - 1)
    group = j % max(int(share.get("groups", 1)), 1)
    pre = random.Random(f"{seed}/prefix/{group}")
    own = random.Random(f"{seed}/job/{j}")
    ids = [pre.randrange(3, vocab_size) for _ in range(max(n_shared, 0))]
    ids += [own.randrange(3, vocab_size)
            for _ in range(max(n_prompt - overhead - len(ids), 1))]
    turns = [{"ids": list(ids), "max_tokens": n_out}]
    ses = mix.get("session") or {}
    for k in range(1, int(ses.get("turns", 1))):
        more = quantile(ses["turn_tokens"], own.random() * 0.998 + 0.001)
        ids = ids + [own.randrange(3, vocab_size) for _ in range(more)]
        turns.append({"ids": list(ids), "max_tokens": n_out})
    return turns


def arrivals(mix: dict, seed: int, horizon_s: float) -> list[float]:
    """Open loop: due times of jobs in [0, horizon), by thinning a Poisson
    process at the peak rate."""
    a = mix["arrivals"]
    rate = float(a["rate_per_s"])
    peak = rate * float(a.get("on_factor", 1.0)) if a["kind"] == "onoff" else rate

    def rate_at(t: float) -> float:
        if a["kind"] == "poisson":
            return rate
        if a["kind"] == "onoff":
            return peak if (t % a["period_s"]) < a["on_s"] else rate
        raise ValueError(f"unknown arrival kind {a['kind']!r}")

    rng = random.Random(f"{seed}/arrivals")
    t, out = 0.0, []
    while True:
        t += rng.expovariate(peak)
        if t >= horizon_s:
            return out
        if rng.random() * peak <= rate_at(t):
            out.append(t)
