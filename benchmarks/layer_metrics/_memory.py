"""What the readers of the program's memory account share.

The program keeps one account of the fullest local device
(``hbm_account_bytes{owner}``: ``found`` what the process held on the chip
before the engine placed anything, ``params``, ``cache``, ``resident_idle``
the bytes in use when nothing was in flight, ``programs`` the remainder,
``limit`` the allocator's own) and of its host (``host_rss_peak_bytes``, the
process's ``VmHWM``).  The readers take ``after``, the window's last instant:
the account is read at edges of the start-up only, so the window does not move
it, and the peak is the whole run's.

A reader is ``None`` only on a program without the gauge (the parent of the PR
that added it; a backend without allocator statistics): a gauge that is there
and reads 0 is 0.0."""

from __future__ import annotations


def owner_bytes(ctx: dict, owner: str) -> float | None:
    """``hbm_account_bytes{owner}``, or ``None`` where the program has no
    account or the account has no such owner yet."""
    account = ctx["after"].get("hbm_account_bytes")
    if not isinstance(account, dict) or account.get(owner) is None:
        return None
    return float(account[owner])


def owner_gb(ctx: dict, owner: str) -> float | None:
    nbytes = owner_bytes(ctx, owner)
    return None if nbytes is None else nbytes / 1e9


def peak_bytes(ctx: dict) -> float | None:
    """``hbm_bytes_peak`` of the fullest device."""
    peaks = ctx["after"].get("hbm_bytes_peak")
    if not isinstance(peaks, dict) or not peaks:
        return None
    return max(float(v) for v in peaks.values())
