"""The memory account: who holds the chip's memory, which program set its
peak, and what the process holds on the host.

One account a process (:data:`ACCOUNT`), fed at EDGES only and never inside a
step: the entry and close of the parameter placement, a cache or pool built,
a fresh program's first call and the first wait that follows it, and the
instant an engine knows nothing is in flight (the scheduler about to park, a
one-stream request closed), the last only while something was compiled or
built since the previous reading.  A warm engine reads nothing.  It writes
the gauge families ``hbm_account_bytes{owner}``, ``hbm_peak_raised_bytes{key}``,
``hbm_peak_set_by_bytes{key}``, ``host_rss_bytes{phase}`` and
``host_rss_peak_bytes`` of ``obs/metrics.py`` (docs/OBSERVABILITY.md has the
catalog) and, where an allocation fails, the one log line ``hbm_exhausted``.

The package stays jax-free: ``runtime/engine.py`` donates the device reader
(:attr:`MemoryAccount.stats`, ``{device id: memory_stats()}`` over the local
devices) at import.  A backend without allocator statistics (the CPU) reads
``{}`` and the device families stay empty; a host without ``/proc`` leaves
the host families empty.  Tests inject both readers.
"""

from __future__ import annotations

import contextlib
import resource
import threading
import time

from . import metrics as obs_metrics
from .log import get_logger

_log = get_logger("obs.memory")

PROC_STATUS = "/proc/self/status"
#: the status lines read, and the name each goes by here
_PROC_FIELDS = {"VmRSS": "rss", "VmHWM": "rss_peak"}


def read_proc_status() -> str:
    """The text of ``/proc/self/status``, or ``""`` where there is none."""
    try:
        with open(PROC_STATUS) as f:
            return f.read()
    except OSError:
        return ""


def parse_proc_status(text: str) -> dict[str, int]:
    """``{"rss": bytes, "rss_peak": bytes}`` from the status text's ``VmRSS``
    and ``VmHWM`` lines (kB there); a missing line is a missing key."""
    out: dict[str, int] = {}
    for line in text.splitlines():
        field, _, rest = line.partition(":")
        if field in _PROC_FIELDS:
            out[_PROC_FIELDS[field]] = int(rest.split()[0]) * 1024
    return out


def read_maxrss() -> int:
    """``getrusage``'s high water of the resident set, in bytes (Linux counts
    it in kB): what stands in for ``VmHWM`` under a kernel whose status file
    leaves that line out (gVisor's does)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _fullest(st: dict[str, dict], stat: str) -> int:
    """``stat`` of the fullest device of a reading (0 for an empty one)."""
    return max((int(s.get(stat, 0)) for s in st.values()), default=0)


def _in_use(st: dict[str, dict]) -> dict[str, int]:
    return {d: int(s.get("bytes_in_use", 0)) for d, s in st.items()}


class MemoryAccount:
    """The process's account of device and host memory (module docstring).

    ``stats`` returns ``{device id: memory_stats dict}`` for the local devices
    (``None`` until the engine donates it: a process without JAX); ``proc``
    returns the text of ``/proc/self/status``; ``maxrss`` returns the
    resident high water in bytes where that text has ``VmRSS`` and no
    ``VmHWM``.
    """

    def __init__(self, stats=None, proc=read_proc_status, maxrss=read_maxrss):
        self.stats = stats
        self.proc = proc
        self.maxrss = maxrss
        self._found: dict[str, int] | None = None   # None: not read yet
        self._idle: dict[str, int] = {}
        self._limit: dict[str, int] = {}
        self._cache: dict[str, int] = {}
        self._rss: dict[str, int] = {}
        #: fresh programs launched and not yet waited for: (key, peak, span)
        self.pending: list[tuple] = []
        #: something was compiled or built since resident_idle was read
        self.dirty = False
        self._phase_peak = 0
        self._scraped: tuple[float, dict[str, int]] | None = None
        # the scheduler's thread and a request's may both reach an edge
        self._lock = threading.RLock()
        self.bind()

    def bind(self) -> None:
        """Point the two lazily read host families at this account."""
        obs_metrics.HOST_RSS_BYTES.fn = self._rss_family
        obs_metrics.HOST_RSS_PEAK_BYTES.fn = \
            lambda: self._scrape().get("rss_peak")

    # -- the two readers ------------------------------------------------
    def _counted(self, source: str, read):
        """``read()`` as one of the account's own reads: counted and timed."""
        t0 = time.perf_counter()
        out = read()
        obs_metrics.MEMORY_ACCOUNT_READS.inc(source)
        obs_metrics.MEMORY_ACCOUNT_READ_SECONDS.inc(
            source, n=time.perf_counter() - t0)
        return out

    def _device(self) -> dict[str, dict]:
        """One ``memory_stats()`` a local device."""
        return {} if self.stats is None else self._counted("device", self.stats)

    def _read_host(self) -> dict[str, int]:
        out = parse_proc_status(self.proc())
        if "rss" in out and "rss_peak" not in out:
            out["rss_peak"] = max(self.maxrss(), out["rss"])
        return out

    def _host(self) -> dict[str, int]:
        return self._counted("host", self._read_host)

    def _scrape(self) -> dict[str, int]:
        """The host's figures for a scrape: ONE read of ``/proc`` serves both
        lazily read families of a ``/metrics`` read (under gVisor a read of
        the status file is milliseconds), so a reading younger than 50 ms is
        used again.  The scraper's reads are not the account's: not counted."""
        now = time.monotonic()
        if self._scraped is None or now - self._scraped[0] > 0.05:
            self._scraped = (now, self._read_host())
        return self._scraped[1]

    def _rss_family(self) -> dict[str, int]:
        now = self._scrape().get("rss")
        return dict(self._rss) if now is None else {**self._rss, "now": now}

    @property
    def active(self) -> bool:
        """The backend reports allocator statistics (and ``found`` was read)."""
        return bool(self._found)

    # -- the device, by owner -------------------------------------------
    def found(self) -> None:
        """Once a process, before its first placement uploads anything: what
        the process already held on each device."""
        with self._lock:
            if self._found is not None:
                return
            st = self._device()
            self._found = _in_use(st)
            self._limit = {d: int(s["bytes_limit"]) for d, s in st.items()
                           if "bytes_limit" in s}
            self._phase_peak = _fullest(st, "peak_bytes_in_use")
            # whatever ran in the process before the engine set this much
            # of the peak (the harness's in-process reference)
            self._raised("found", 0, self._phase_peak)
            self._publish(self._found)

    def phase(self, key: str) -> None:
        """A load phase closed (``load_place``, ``cache_build``): by how much
        it raised the peak, and what stays is to be read again."""
        if not self.active:
            return
        with self._lock:
            st = self._device()
            peak = _fullest(st, "peak_bytes_in_use")
            self._raised(key, self._phase_peak, peak)
            self._phase_peak = peak
            self.dirty = True
            self._publish(_in_use(st))

    def cache_built(self, by_device: dict[str, int]) -> None:
        """An engine built its cache or pool: ``by_device`` bytes of it lie
        on each device (from the arrays ``kv_cache_bytes{kind}`` is set
        from).  :meth:`cache_dropped` takes them off again."""
        with self._lock:
            for d, n in by_device.items():
                self._cache[d] = self._cache.get(d, 0) + int(n)
            self.phase("cache_build")

    def cache_dropped(self, by_device: dict[str, int]) -> None:
        with self._lock:
            for d, n in by_device.items():
                self._cache[d] = self._cache.get(d, 0) - int(n)
            self.dirty = True

    def launching(self, key: str, span_args) -> None:
        """Entry of a fresh program's first call (inside ``engine.compile``):
        the span gains ``hbm_in_use`` and ``hbm_peak_before`` here, and
        ``hbm_peak_after`` where the call is first waited for."""
        if not self.active:
            return
        with self._lock:
            st = self._device()
            peak = _fullest(st, "peak_bytes_in_use")
            span_args.update(hbm_in_use=_fullest(st, "bytes_in_use"),
                             hbm_peak_before=peak)
            self.pending.append((key, peak, span_args))
            self.dirty = True

    def landed(self, st: dict | None = None) -> None:
        """The first wait after fresh programs were launched has returned:
        each raised the peak from where its own launch found it to where the
        next one's did (the last: to where it stands now).  Two fresh
        programs in flight together share the rise in launch order."""
        if not self.pending:
            return
        with self._lock:
            st = self._device() if st is None else st
            pending, self.pending = self.pending, []
            after = [p[1] for p in pending[1:]] \
                + [_fullest(st, "peak_bytes_in_use")]
            for (key, before, span_args), peak in zip(pending, after):
                span_args.late(hbm_peak_after=peak)
                self._raised(key, before, peak)
            self._phase_peak = max(self._phase_peak, after[-1])

    def idle(self) -> None:
        """Nothing is in flight.  Reads ``bytes_in_use`` only if a program was
        compiled or a cache built since the last reading: nothing else
        changes what stays resident."""
        if not self.dirty or not self.active:
            return
        with self._lock:
            st = self._device()
            self.landed(st)
            self.dirty = False
            self._idle = _in_use(st)
            self._publish(self._idle)

    def _raised(self, key: str, before: int, after: int) -> None:
        if after > before:
            obs_metrics.HBM_PEAK_RAISED_BYTES.set(key, after - before)
            obs_metrics.HBM_PEAK_SET_BY_BYTES.reset()  # one sample: the last
            obs_metrics.HBM_PEAK_SET_BY_BYTES.set(key, after)

    def owners(self, in_use: dict[str, int]) -> dict[str, int]:
        """The account of the fullest device of ``in_use``, the reading just
        made (``{}`` for an empty one)."""
        if not in_use:
            return {}
        dev = max(sorted(in_use), key=in_use.get)
        out = {"found": self._found.get(dev, 0),
               "params": int(obs_metrics.PARAM_BYTES_RESIDENT.get(dev)),
               "cache": self._cache.get(dev, 0)}
        if dev in self._idle:
            out["resident_idle"] = self._idle[dev]
            out["programs"] = self._idle[dev] - sum(out[k] for k in (
                "found", "params", "cache"))
        if dev in self._limit:
            out["limit"] = self._limit[dev]
        return out

    def _publish(self, in_use: dict[str, int]) -> None:
        for owner, nbytes in self.owners(in_use).items():
            obs_metrics.HBM_ACCOUNT_BYTES.set(owner, nbytes)

    # -- the host, by phase ---------------------------------------------
    def rss(self, phase: str) -> int | None:
        """Set ``host_rss_bytes{phase}`` from one read of ``/proc``; returns
        the bytes (a span's ``rss`` argument), ``None`` without ``/proc``."""
        rss = self._host().get("rss")
        if rss is not None:
            self._rss[phase] = rss
        return rss

    # -- a failing allocation -------------------------------------------
    def exhausted(self, exc: BaseException, key: str | None = None) -> None:
        """One ``hbm_exhausted`` line if ``exc`` is XLA's RESOURCE_EXHAUSTED:
        who held what when the allocation failed.  The caller re-raises."""
        if "RESOURCE_EXHAUSTED" not in str(exc):
            return
        st, host = self._device(), self._host()
        owners = dict.fromkeys(("found", "params", "cache", "resident_idle",
                                "programs", "limit"))
        owners.update(self.owners(_in_use(st)))
        free = [int(s["largest_free_block_bytes"]) for s in st.values()
                if "largest_free_block_bytes" in s]
        _log.error("hbm_exhausted", extra={
            **owners, "key": key,
            "in_use": _fullest(st, "bytes_in_use") if st else None,
            "peak": _fullest(st, "peak_bytes_in_use") if st else None,
            "largest_free_block": max(free) if free else None,
            "host_rss": host.get("rss"), "host_rss_peak": host.get("rss_peak"),
            "error": str(exc).splitlines()[0][:300]})

    @contextlib.contextmanager
    def exhaustion(self, key: str | None = None):
        """Around a placement, a cache build or a launch: :meth:`exhausted`
        on the way out of an error, which goes on unchanged."""
        try:
            yield
        except Exception as e:
            self.exhausted(e, key)
            raise


#: THE process's account (``found`` is a fact of the process, not of an engine)
ACCOUNT = MemoryAccount()
