"""Kernel-dispatch ledger: which matmul path actually ran, and why not.

The perf contract of this codebase is the fused Pallas dequant-matmul
(ops/q40.py, ops/q8.py); every dispatch that silently falls off it —
a weight that doesn't shard over the mesh, a reduce off the fused ring —
used to announce itself as one scrollback
``print`` and then vanish.  A production run could misreport a
several-×-slower XLA-dequant decode as a clean number (VERDICT r05).

This module is the single funnel those decisions flow through:

* :func:`record_dispatch` — every resolved matmul dispatch bumps the
  ``matmul_dispatch`` family (labels ``codec``/``path``).  Dispatches
  are recorded at *trace time* (q40.matmul runs inside ``jax.jit``
  tracing), so counts are per compiled call site, not per decode step —
  exactly the granularity at which a path decision exists.
* :func:`record_degrade` — every fallback off the requested/fast path
  bumps ``q40_degrade_total{reason=...}`` (or the q8 twin), emits ONE
  structured log record per distinct site (warn-once keyed by
  ``warn_key``, replacing the old ``_FALLBACK_WARNED`` prints), and
  flips the process-wide :func:`degraded` flag that ``/health``,
  ``/metrics`` and the end-of-run CLI summary surface.

Stdlib-only (obs package contract: importable without jax).
"""

from __future__ import annotations

import threading

from . import metrics as obs_metrics
from .log import get_logger

_log = get_logger("obs.dispatch")

_lock = threading.Lock()
_degraded = False
_reasons: dict[str, int] = {}        # "codec:reason" -> occurrences
_dispatches: dict[str, int] = {}     # "codec/path"   -> occurrences
_warned: set = set()                 # (codec, reason, warn_key) logged once


def record_dispatch(codec: str, path: str, **ctx) -> None:
    """Record one resolved matmul dispatch.

    ``codec`` is the weight storage ("q40", "q8", "dense"); ``path`` the
    executed implementation ("pallas-fused", "xla-dequant", "dense").
    Extra keyword context (rows, kind, tp; from the Q40 kernel also the
    tile pair it got and the stored input dim) rides on the debug log
    record only.
    """
    obs_metrics.MATMUL_DISPATCH.inc(codec, path)
    with _lock:
        key = f"{codec}/{path}"
        _dispatches[key] = _dispatches.get(key, 0) + 1
    _log.debug("dispatch", extra={"codec": codec, "path": path, **ctx})


def record_degrade(codec: str, reason: str, *, warn_key=None, **ctx) -> None:
    """Record one degrade off the fast path: labeled counter + degraded
    flag always; a WARNING log record once per (codec, reason, warn_key)
    so a degrade firing on every layer of every forward logs once, while
    the counter keeps the true occurrence count."""
    global _degraded
    if codec == "q8":
        counter = obs_metrics.Q8_DEGRADE
    elif codec == "attn":
        counter = obs_metrics.ATTN_DEGRADE
    else:
        counter = obs_metrics.Q40_DEGRADE
    counter.inc(reason)
    with _lock:
        _degraded = True
        rk = f"{codec}:{reason}"
        _reasons[rk] = _reasons.get(rk, 0) + 1
        wk = (codec, reason, warn_key)
        first = wk not in _warned
        _warned.add(wk)
    if first:
        _log.warning("kernel_degrade",
                     extra={"codec": codec, "reason": reason, **ctx})


def record_cost(entries: dict) -> None:
    """Bump the analytic roofline counters for one landed dispatch.

    ``entries`` is :meth:`obs.cost.CostModel.dispatch_cost`'s
    ``{(codec, path, phase): {"flops": n, "bytes": n}}`` map — the
    runtime side of the ledger: :func:`record_dispatch` says which path
    a call site *compiled*, this says what the landed dispatches *cost*.
    """
    for (codec, path, phase), e in entries.items():
        if e.get("flops"):
            obs_metrics.DISPATCH_FLOPS.inc(codec, path, phase,
                                           n=e["flops"])
        if e.get("bytes"):
            obs_metrics.DISPATCH_BYTES.inc(codec, path, phase,
                                           n=e["bytes"])


def degraded() -> bool:
    """True once any dispatch degraded off its fast path this process."""
    with _lock:
        return _degraded


def reasons() -> dict[str, int]:
    """``{"codec:reason": occurrences}`` for every degrade recorded."""
    with _lock:
        return dict(_reasons)


def dispatches() -> dict[str, int]:
    """``{"codec/path": occurrences}`` for every dispatch recorded."""
    with _lock:
        return dict(_dispatches)


def summary() -> dict:
    """One JSON-able view of the ledger (health endpoint, tools)."""
    with _lock:
        return {"degraded": _degraded,
                "degrades": dict(_reasons),
                "dispatches": dict(_dispatches)}


def summary_line() -> str:
    """The end-of-run CLI summary: one line that makes a degraded run
    impossible to read as a clean number."""
    with _lock:
        deg = dict(_reasons)
        paths = dict(_dispatches)
    path_part = " ".join(f"{k}×{v}" for k, v in sorted(paths.items())) \
        or "none recorded"
    if deg:
        deg_part = " ".join(f"{k}×{v}" for k, v in sorted(deg.items()))
        return (f"⚠️  kernel dispatch: DEGRADED ({deg_part}); "
                f"paths: {path_part}")
    return f"💡 kernel dispatch: clean; paths: {path_part}"


def collective_line() -> str | None:
    """End-of-run collective-overlap share: of the tp-sharded col-matmul
    call sites this process compiled, how many took the fused RDMA ring
    (transfer overlapped with accumulate) vs the plain-psum fallback.
    None when no tp collective was dispatched at all (tp=1 runs stay
    silent)."""
    with _lock:
        fused = _dispatches.get("q40/tp_fused_reduce", 0)
        psum = _dispatches.get("q40/tp_psum", 0)
    total = fused + psum
    if not total:
        return None
    return (f"🔗 tp collectives: {fused}/{total} sharded matmul sites "
            f"fused (overlap share {fused / total:.2f})")


def reset() -> None:
    """Clear the ledger AND its registry counters (test isolation)."""
    global _degraded
    with _lock:
        _degraded = False
        _reasons.clear()
        _dispatches.clear()
        _warned.clear()
    obs_metrics.MATMUL_DISPATCH.reset()
    obs_metrics.Q40_DEGRADE.reset()
    obs_metrics.Q8_DEGRADE.reset()
    obs_metrics.ATTN_DEGRADE.reset()
    obs_metrics.DISPATCH_FLOPS.reset()
    obs_metrics.DISPATCH_BYTES.reset()
    obs_metrics.CLASS_CHIP_MS.reset()
    obs_metrics.MFU.reset()
    obs_metrics.MBU.reset()
    from . import cost as obs_cost
    obs_cost.TRACKER.reset()
