"""What ``jax.profiler.ProfileData`` does not hand out: the *event metadata*
of an ``.xplane.pb``, read with the standard library alone.

On the TPU an ``XLA Ops`` event's name is the bare HLO instruction text and
``ProfileData``'s ``ev.stats`` gives only the device offsets, so the names a
program put on its ops (``jax.named_scope``) are invisible to
``harness/xplane.py``.  They are in the file all the same: each op's
``XEventMetadata`` carries ``tf_op`` (the JAX op-name path, e.g.
``jit(step)/while/body/kv_write/scatter``), ``hlo_category``, ``source``
(``file:line``), ``program_id``, ``bytes_accessed`` and ``flops``.
Compiler-made ops (layout copies, ``copy-start``/``copy-done``) carry no
``tf_op``.  The ``XLA Modules`` line names each program run
(``jit_<fn>(<fingerprint>)``); a host event given keyword arguments
(``jax.profiler.TraceAnnotation(name, **kw)``) keeps them as event stats.

The wire walk below knows these fields of tsl's ``xplane.proto`` and skips
the rest: XSpace planes=1; XPlane name=2, lines=3, event_metadata=4,
stat_metadata=5 (both maps: key=1, value=2); XLine name=2, timestamp_ns=3,
events=4; XEvent metadata_id=1, offset_ps=2, duration_ps=3, stats=4;
XEventMetadata id=1, name=2, display_name=4, stats=5; XStatMetadata id=1,
name=2; XStat metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
ref=7.  Checked by hand on the recorded v5e fixtures (``tests/test_xmeta.py``).
"""

from __future__ import annotations

import re
import struct

from harness import xplane

OP_LINE, MODULE_LINE = xplane.OP_LINE, "XLA Modules"
OP_STATS = ("tf_op", "hlo_category", "source", "program_id",
            "bytes_accessed", "flops")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def _varint(b, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if not c & 0x80:
            return r, i
        s += 7


def fields(b):
    """``(field number, wire type, value)`` of one message: a varint as an
    int, fixed widths and length-delimited values as memoryviews."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wire == 1:
            v = b[i:i + 8]
            i += 8
        elif wire == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(b, stat_names: dict[int, str]) -> tuple[str, object]:
    """One XStat as ``(name, value)``."""
    name, value = "", None
    for f, wire, v in fields(b):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f == 5:
            value = _text(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(b) -> tuple[int, object]:
    key, val = 0, b[0:0]
    for f, _, v in fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _event_metadata(b, stat_names: dict[int, str]) -> dict:
    out = {"name": "", "display": ""}
    for f, _, v in fields(b):
        if f == 2:
            out["name"] = _text(v)
        elif f == 4:
            out["display"] = _text(v)
        elif f == 5:
            k, val = _stat(v, stat_names)
            if k in OP_STATS:
                out[k] = val
    return out


def _events(line, t0_ns: float, want=None, stat_names=None) -> list[tuple]:
    """``(metadata_id, start_ns, dur_ns[, stats])`` of a line's events; with
    ``want`` only the events whose metadata id is in it, with their stats."""
    rows = []
    for f, _, ev in fields(line):
        if f != 4:
            continue
        mid = off = dur = 0
        stats = []
        for g, _, v in fields(ev):
            if g == 1:
                mid = v
                if want is not None and mid not in want:
                    break
            elif g == 2:
                off = v
            elif g == 3:
                dur = v
            elif g == 4 and want is not None:
                stats.append(v)
        else:
            # whole nanoseconds, as ProfileData gives them, so that sums
            # here equal harness/xplane.py's
            start, dur = float(t0_ns + off // 1000), float(dur // 1000)
            if want is None:
                rows.append((mid, start, dur))
            else:
                rows.append((mid, start, dur,
                             dict(_stat(s, stat_names) for s in stats)))
    return rows


def load(path: str, keep_host=None) -> dict:
    """``{"devices": {plane: {"meta": {id: {...}}, "ops": [(id, start_ns,
    dur_ns)], "modules": [(name, start_ns, dur_ns)]}}, "host": [(line, name,
    start_ns, dur_ns, stats)]}``.  ``meta[id]`` holds ``name`` (the HLO
    text), ``display`` and whichever of :data:`OP_STATS` the op has.  Host
    events are kept where ``keep_host(name)`` is true (all of them without
    it); only kept events have their stats decoded."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, host = {}, []
    for f, _, plane in fields(space):
        if f != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for g, _, v in fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                emeta.append(v)
            elif g == 5:
                smeta.append(v)
        is_dev = name.startswith("/device:") and "TPU" in name
        if not (is_dev or name == "/host:CPU"):
            continue
        stat_names = {}
        for m in smeta:
            key, val = _map_entry(m)
            stat_names[key] = next(
                (_text(v) for f2, _, v in fields(val) if f2 == 2), "")
        metas = dict(_map_entry(m) for m in emeta)
        if not is_dev:
            names = {mid: next((_text(v) for f2, _, v in fields(b)
                                if f2 == 2), "") for mid, b in metas.items()}
            want = {mid for mid, n in names.items()
                    if keep_host is None or keep_host(n)}
        for line in lines:
            lname, t0 = "", 0
            for g, _, v in fields(line):
                if g == 2:
                    lname = _text(v)
                elif g == 3:
                    t0 = v
            if is_dev and lname == OP_LINE:
                dev = devices.setdefault(name, {"meta": {}, "ops": [],
                                                "modules": []})
                dev["ops"].extend(_events(line, t0))
                for mid in {r[0] for r in dev["ops"]} - set(dev["meta"]):
                    dev["meta"][mid] = _event_metadata(metas[mid], stat_names)
            elif is_dev and lname == MODULE_LINE:
                dev = devices.setdefault(name, {"meta": {}, "ops": [],
                                                "modules": []})
                for mid, s, d in _events(line, t0):
                    mname = next((_text(v) for f2, _, v in fields(metas[mid])
                                  if f2 == 2), "")
                    dev["modules"].append((mname, s, d))
            elif not is_dev and want:
                host.extend((lname, names[mid], s, d, st) for mid, s, d, st
                            in _events(line, t0, want, stat_names) if d > 0)
    return {"devices": devices, "host": host}


def module_name(text: str) -> tuple[str, int | None]:
    """``("jit_step", 123)`` of ``"jit_step(123)"``: a program and the
    ``program_id`` its ops carry."""
    m = _MODULE.match(text)
    return (m.group(1), int(m.group(2))) if m else (text, None)


def scope_of(tf_op: str | None, scopes) -> str:
    """The last component of an op-name path that is a scope; ``unscoped``
    where there is none (or no path: a compiler-made op)."""
    found = "unscoped"
    for part in (tf_op or "").rstrip(":").split("/"):
        if part in scopes:
            found = part
    return found


def own_times(dev: dict) -> list[tuple[int, float]]:
    """``(metadata id, own ns)`` per executed op of one device plane: its
    duration minus the ops nested inside it (``xplane.self_times``)."""
    return xplane.self_times(dev["ops"])
