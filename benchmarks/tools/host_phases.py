#!/usr/bin/env python3
"""The host's part of a served step by phase and step kind, from the
program's own counters: ``python3 benchmarks/tools/host_phases.py
benchmarks/out/<cell>.metrics-after.json [BEFORE.json]``.

``run.py`` leaves the program's ``/metrics`` as the window's last instant had
them; with one file the table is cumulative (warm-up and pre-roll included),
with a second, earlier snapshot it is the difference.  Printed as JSON:
``steps`` landed by kind; ``ms_per_step``, each ``sched_host_ms`` cell over
the steps of its kind (the ``round`` cells, which end before a step's shape
is decided, over all steps); ``work_ms_per_step``, the working phases of all
kinds over all steps (``serve_host_ms_per_step``'s sum); and the goodput
clock's view of the same host time, ``clock_ms_per_step`` = (``host_gap`` the
device waited for + ``hidden`` it did not) / steps.  PERF.md section 5's host
tables are this output."""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from _host import WORK, phase_ms_per_step  # noqa: E402
from _scopes import _kinds  # noqa: E402


def host_table(after: dict, before: dict | None = None) -> dict | None:
    ctx = {"after": after, "before": before or {}}
    steps, ms = _kinds(ctx, "sched_steps"), _kinds(ctx, "sched_host_ms")
    landed = sum(steps.values())
    if not ms or not landed:
        return None
    gap = _kinds(ctx, "sched_step_time_ms").get("host_gap", 0.0)
    hidden = after.get("sched_host_gap_hidden_ms", 0.0) \
        - ctx["before"].get("sched_host_gap_hidden_ms", 0.0)
    return {"steps": steps,
            "ms_per_step": {
                cell: v / (steps.get(cell.split("/")[1]) or landed)
                for cell, v in sorted(ms.items())},
            "work_ms_per_step": phase_ms_per_step(ctx, *WORK),
            "clock_ms_per_step": (gap + hidden) / landed}


def main(argv: list[str]) -> int:
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    snaps = []
    for path in argv:
        with open(path) as f:
            snaps.append(json.load(f))
    json.dump(host_table(*snaps), sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
