"""Named spans of a run's device trace, for the per-layer metrics that read
what the program itself names: device time by ``jax.named_scope`` path, and
the program's host ``TraceAnnotation`` spans (``engine/*``, ``server/*``,
``trainer/*``) by thread, inside the ``bench/window`` span.

``tracered.py`` reduces the same file to busy time and top operations
through ``jax.profiler.ProfileData``; that reader gives an event's name and
times, and not the statistics of its metadata, which is where the TPU
profiler keeps an operation's scope path (``tf_op``: the HLO ``op_name``,
e.g. ``jit(step)/jit(main)/while/body/attn_core/...``). So this module
decodes the ``.xplane.pb`` wire format itself (``xplane.proto``: a dozen
fields), descending only into what it needs: the device planes' ``XLA
Ops`` and ``XLA Modules`` lines, and of the host plane's events only the
ones whose name has a wanted prefix.

A reader built on this returns ``None`` where the trace, the plane, the
scope or the span is absent: the parent of the PR that added a name has no
such name, and a CPU rehearsal has no device plane.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct

from benchmark.lib import harness, tracered

WINDOW_SPAN = "bench/window"
HOST_PREFIXES = ("engine/", "server/", "trainer/")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# statistics of an operation's metadata that can carry its scope path
SCOPE_STATS = ("tf_op", "name_scope", "long_name")

_CACHE: dict = {}


def find_xplane(work_dir: str | None = None) -> str | None:
    """The newest ``*.xplane.pb`` a traced run left under
    ``<work>/<cell>/trace/``; None when no run traced."""
    root = work_dir or harness.WORK_DIR
    found = glob.glob(os.path.join(root, "*", "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str | None = None) -> dict | None:
    """The trace at ``path`` (default: ``find_xplane()``), decoded once a
    process: ``{"window": (lo_ns, hi_ns) | None, "device": {plane: {"ops":
    [(name, scope path, start_ns, dur_ns)], "modules": [(name, start_ns,
    dur_ns)]}}, "host": {thread: [(name, start_ns, dur_ns)]}}``."""
    path = path or find_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        with open(path, "rb") as f:
            _CACHE[key] = decode(f.read())
    return _CACHE[key]


# -- protobuf wire format ------------------------------------------------------


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) range of ``buf`` for anything with a length or a width."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
            yield key >> 3, val
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield key >> 3, (pos, pos + n)
            pos += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            yield key >> 3, (pos, pos + n)
            pos += n
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf: bytes, rng) -> str:
    return buf[rng[0]:rng[1]].decode("utf-8", "replace")


def _map_entry(buf, rng) -> tuple[int, tuple | None]:
    key, val = 0, None
    for f, v in _fields(buf, *rng):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _stat(buf, rng, stat_names) -> tuple[str, object]:
    """(name, value) of one XStat; a ``ref_value`` is the name of the
    statistic it points at."""
    name, value = "", None
    for f, v in _fields(buf, *rng):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", buf[v[0]:v[1]])[0]
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf, rng, want_line, want_event) -> dict | None:
    """One XPlane: ``{"name", "lines": {line name: [(event name, scope
    path, start_ns, dur_ns)]}}`` for the lines ``want_line(name)`` and, of
    them, the events ``want_event(name)``."""
    name, lines, metas, stats = "", [], [], []
    for f, v in _fields(buf, *rng):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            stats.append(v)
    if not want_line(name, None):
        return None
    stat_names = {}
    for rng_ in stats:
        key, val = _map_entry(buf, rng_)
        if val is not None:
            for f, v in _fields(buf, *val):
                if f == 2:
                    stat_names[key] = _text(buf, v)
    meta = {}        # id -> (name, scope path) of the wanted events
    for rng_ in metas:
        key, val = _map_entry(buf, rng_)
        if val is None:
            continue
        ev_name, ev_stats = "", []
        for f, v in _fields(buf, *val):
            if f == 2:
                ev_name = _text(buf, v)
            elif f == 5:
                ev_stats.append(v)
        if not want_event(ev_name):
            continue
        found = dict(_stat(buf, s, stat_names) for s in ev_stats)
        scope = next((str(found[k]) for k in SCOPE_STATS if found.get(k)), "")
        # ``tf_op`` reads ``<op_name>:<op type>``: keep the name's path
        meta[key] = (ev_name, scope.rsplit(":", 1)[0])
    out = {}
    for rng_ in lines:
        line_name, line_id, t0_ns, events = "", 0, 0, []
        for f, v in _fields(buf, *rng_):
            if f == 1:
                line_id = v
            elif f == 2:
                line_name = _text(buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if not want_line(name, line_name):
            continue
        rows = []
        for a, b in events:
            mid = off_ps = dur_ps = 0
            for f, v in _fields(buf, a, b):
                if f == 1:
                    mid = v
                    if mid not in meta:
                        break
                elif f == 2:
                    off_ps = v
                elif f == 3:
                    dur_ps = v
            else:
                ev_name, scope = meta[mid]
                rows.append((ev_name, scope, t0_ns + off_ps / 1e3,
                             dur_ps / 1e3))
        # threads of one name (``python3``) are lines of their own
        key = line_name if line_name not in out else f"{line_name}#{line_id}"
        out[key] = rows
    return {"name": name, "lines": out}


def decode(buf: bytes) -> dict:
    planes = [v for f, v in _fields(buf, 0, len(buf)) if f == 1]
    device, host, window = {}, {}, None

    def device_line(plane, line):
        return plane.startswith("/device:TPU:") and (
            line is None or line in (OPS_LINE, MODULES_LINE))

    def host_line(plane, line):
        return plane.startswith("/host:CPU")

    def host_event(name):
        return name == WINDOW_SPAN or name.startswith(HOST_PREFIXES)

    for rng in planes:
        p = _plane(buf, rng, device_line, lambda name: True)
        if p is not None:
            device[p["name"]] = {
                "ops": p["lines"].get(OPS_LINE, []),
                "modules": [(n, s, d) for n, _sc, s, d in
                            p["lines"].get(MODULES_LINE, [])]}
            continue
        p = _plane(buf, rng, host_line, host_event)
        if p is not None:
            for thread, rows in p["lines"].items():
                for n, _sc, s, d in rows:
                    if n == WINDOW_SPAN:
                        window = (s, s + d)
                    else:
                        host.setdefault(thread, []).append((n, s, d))
    return {"window": window, "device": device, "host": host}


# -- what the readers ask ------------------------------------------------------


def _in_scope(path: str, scope: str) -> bool:
    """``scope`` is a component of the path; under a gradient the
    component reads ``jvp(<scope>)`` or ``transpose(jvp(<scope>))``."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}\)*(?:/|$)", path) \
        is not None


def _is_container(op_name: str) -> bool:
    return tracered.op_key(op_name).startswith(tracered._CONTAINERS)


def _clip(s, d, window):
    if window is None:
        return s, s + d
    return max(s, window[0]), min(s + d, window[1])


def whole_programs(trace: dict | None, program_prefix: str) -> list:
    """(start_ns, end_ns), in order, of the programs on the first device
    plane whose ``XLA Modules`` name starts with ``program_prefix`` and
    that lie wholly inside the window: one that an edge of the window (or
    of the profiler's session, which ends just outside it) cuts is a
    program with part of its time."""
    if not trace or not trace["device"]:
        return []
    plane = trace["device"][sorted(trace["device"])[0]]
    win = trace["window"]
    return sorted((s, s + d) for n, s, d in plane["modules"]
                  if n.startswith(program_prefix)
                  and (win is None or (s >= win[0] and s + d <= win[1])))


def scope_seconds(trace: dict | None, scope: str,
                  program_prefix: str) -> tuple[float, int] | None:
    """(device seconds of the operations under ``scope`` inside programs
    whose ``XLA Modules`` name starts with ``program_prefix``, number of
    those programs) on the first device plane, inside the window; only
    programs that lie wholly inside it count. None when there is no such
    program or no operation carries the scope."""
    progs = whole_programs(trace, program_prefix)
    if not progs:
        return None
    plane = trace["device"][sorted(trace["device"])[0]]
    starts = [a for a, _b in progs]
    total, hits = 0.0, 0
    for name, path, s, d in plane["ops"]:
        # a loop's or a call's own event spans its body's operations
        if not _in_scope(path, scope) or _is_container(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < progs[i][1]:
            total += d
            hits += 1
    if not hits:
        return None
    return total / 1e9, len(progs)


def host_spans(trace: dict | None, prefix: str) -> dict:
    """``{thread: [(name, start_ns, end_ns)]}`` of the program's host
    annotations whose name starts with ``prefix``, clipped to the window;
    threads without one are left out."""
    out = {}
    if not trace:
        return out
    for thread, rows in trace["host"].items():
        kept = []
        for n, s, d in rows:
            if n.startswith(prefix):
                a, b = _clip(s, d, trace["window"])
                if b > a:
                    kept.append((n, a, b))
        if kept:
            out[thread] = sorted(kept, key=lambda r: r[1])
    return out


def program_names(trace: dict | None) -> set:
    """Names on the first device plane's ``XLA Modules`` line, without the
    fingerprint in brackets."""
    if not trace or not trace["device"]:
        return set()
    plane = trace["device"][sorted(trace["device"])[0]]
    return {n.split("(")[0] for n, _s, _d in plane["modules"]}
