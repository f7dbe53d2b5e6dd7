#!/usr/bin/env python3
"""Print the memory account of a server from its ``/metrics`` JSON: who holds
the chip's memory, which program set its peak, what the process holds on the
host (docs/OBSERVABILITY.md, ``hbm_account_bytes`` and the rows below it).

    python tools/memory_account.py http://127.0.0.1:9990          # live
    python tools/memory_account.py benchmarks/out/<cell>.metrics-after.json

A benchmark run keeps the ``/metrics`` of its window's end in
``benchmarks/out/<cell>.metrics-after.json``.  No JAX, no third-party import.
"""

from __future__ import annotations

import json
import sys
import urllib.request

OWNERS = ("found", "params", "cache", "programs", "resident_idle", "limit")


def load(source: str) -> dict:
    if source.startswith(("http://", "https://")):
        with urllib.request.urlopen(source.rstrip("/") + "/metrics",
                                    timeout=10) as r:
            return json.loads(r.read())
    with open(source) as f:
        return json.load(f)


def _gb(nbytes) -> str:
    return "        -" if nbytes is None else f"{nbytes / 1e9:9.4f}"


def render(snap: dict) -> str:
    """The account as text; a family the program does not have (or a backend
    without allocator statistics) prints as ``-``."""
    account = snap.get("hbm_account_bytes") or {}
    peaks = snap.get("hbm_bytes_peak") or {}
    peak = max(peaks.values()) if peaks else None
    idle, limit = account.get("resident_idle"), account.get("limit")
    lines = ["the fullest device, GB"]
    lines += [f"  {owner:<14}{_gb(account.get(owner))}" for owner in OWNERS]
    lines.append(f"  {'peak':<14}{_gb(peak)}")
    lines.append(f"  {'temp peak':<14}"
                 f"{_gb(None if None in (peak, idle) else peak - idle)}"
                 "   (peak - resident_idle)")
    lines.append(f"  {'headroom':<14}"
                 f"{_gb(None if None in (peak, limit) else limit - peak)}"
                 "   (limit - peak)")
    set_by = snap.get("hbm_peak_set_by_bytes") or {}
    lines.append("the peak was set by: " + (", ".join(set_by) or "-")
                 + "   (found: what ran in the process before the engine)")
    raised = snap.get("hbm_peak_raised_bytes") or {}
    lines.append("first runs that raised the peak, GB")
    lines += [f"  {_gb(v)}  {key}" for key, v in
              sorted(raised.items(), key=lambda kv: -kv[1])] or ["  none"]
    rss = snap.get("host_rss_bytes") or {}
    lines.append("the host's resident set, GB")
    lines += [f"  {phase:<14}{_gb(rss.get(phase))}"
              for phase in ("read", "placed", "ready", "now")]
    lines.append(f"  {'high water':<14}{_gb(snap.get('host_rss_peak_bytes'))}")
    reads = snap.get("memory_account_reads") or {}
    secs = snap.get("memory_account_read_seconds") or {}
    lines.append("the account's own reads: " + ", ".join(
        f"{src} {reads[src]} in {1e3 * secs.get(src, 0.0):.3f} ms"
        for src in sorted(reads)) if reads else "the account's own reads: none")
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1 or argv[0] in ("-h", "--help"):
        print(__doc__, file=sys.stderr)
        return 2
    print(render(load(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
