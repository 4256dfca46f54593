"""One account of the device's time in a traced window, from the device's
own lines and the program's own names.

Over the STRETCH from the start of the first whole ``jit_step`` program on
the first device plane to the end of the last one (``whole``: of
``xspans.whole_programs``' events, which lie inside the window, those that
are no stump of a program: where the device's own session starts or stops
inside the window, the program it cuts is an event inside the window too,
with the part of its time the session saw; ``CUT_UNDER`` of the median
tells it), the union of the ``XLA Ops`` time (a loop's or a call's own event left out: it spans its body's
operations) is partitioned three ways:

- by program: ``jit_step``, every other ``XLA Modules`` name, no module;
- inside ``jit_step`` by the INNERMOST leaf scope on the operation's path
  that the program declares (``polyrl_tpu/models/scopes.py``), else
  ``none``;
- and the rest of the stretch: idle inside a program's event, idle between
  programs.

So that, by construction, a step: the programs' own events + busy between
them (``step_outside_ms``) + idle between them = the stretch
(``step_period_ms``), and the scopes, ``none`` and the gaps inside
programs sum to the programs' events; those are ``decode_step_ms`` times
its steps wherever no stump stands at the stretch's ends (the note says
how many it left out: ``decode_step_ms`` counts their steps and their
time).

The engine's completion stamps lie on the same clock: each landing leaves
one instant ``engine/landed`` annotation whose own statistics are the
cumulative ``decode_steps_done``, ``device_busy_s`` and
``device_busy_at_s`` as of that landing (``obs/engine_profile.py``).
``xspans.decode`` keeps an event's name and times and is left as it is;
``stamps`` below decodes the statistics of those events alone.

Everything returns None (or nothing) without a trace, a window, a whole
``jit_step`` program, or, where it needs one, a name the program under
test does not have: the parent of the PR that added it.
"""

from __future__ import annotations

import bisect
import collections
import os
import re

from benchmark.lib import notes, tracered, xspans

PROGRAM = "jit_step"
LANDED = "engine/landed"
NONE, NO_MODULE = "none", "no module"
# a ``jit_step`` event at an end of the window that lasts under this share
# of the median event is a program the device's session cut
CUT_UNDER = 0.9

_CACHE: dict = {}


def leaf_scopes() -> tuple | None:
    """The program's declaration of its step's leaf scopes; None for a
    program from before it."""
    try:
        from polyrl_tpu.models.scopes import LEAF_SCOPES
    except ImportError:
        return None
    return LEAF_SCOPES


def innermost(path: str, leaves) -> str:
    """The last component of a scope path that is a declared leaf (under
    a gradient a component reads ``jvp(<scope>)``), else ``none``."""
    for part in reversed(path.split("/")):
        m = re.fullmatch(r"(?:\w+\()*([\w.]+)\)*", part)
        if m and m.group(1) in leaves:
            return m.group(1)
    return NONE


def _clipped(intervals, spans):
    """The parts of ``intervals`` inside the sorted, disjoint ``spans``."""
    starts = [a for a, _b in spans]
    out = []
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(spans) and spans[i][0] < e:
            a, b = max(s, spans[i][0]), min(e, spans[i][1])
            if b > a:
                out.append((a, b))
            i += 1
    return out


def whole_programs(trace: dict | None) -> tuple[list, int]:
    """(``xspans.whole_programs``' ``jit_step`` events less the stumps at
    either end, how many stumps those were)."""
    progs = xspans.whole_programs(trace, PROGRAM)
    if not progs:
        return [], 0
    spans = sorted(b - a for a, b in progs)
    least = CUT_UNDER * spans[len(spans) // 2]
    lo, hi = 0, len(progs)
    while lo < hi - 1 and progs[lo][1] - progs[lo][0] < least:
        lo += 1
    while hi > lo + 1 and progs[hi - 1][1] - progs[hi - 1][0] < least:
        hi -= 1
    return progs[lo:hi], len(progs) - (hi - lo)


def partition(trace: dict | None, steps_per_program: int,
              leaves=None) -> dict | None:
    """The account of the stretch (module docstring), in nanoseconds:
    ``steps``; ``stretch``, ``programs`` (the ``jit_step`` events; of
    them ``first`` and ``last``, the first's and the last's alone; ``cut``:
    the stumps left out at the stretch's ends),
    ``outside`` (busy outside them), ``idle`` (neither); ``by_module``
    {name: busy}; inside the programs ``by_scope`` {leaf or ``none``:
    busy} (``{}`` without ``leaves``), ``gaps`` (no operation) and
    ``none_ops`` {``tracered.op_key``: busy}."""
    progs, cut = whole_programs(trace)
    if not progs or trace["window"] is None:
        return None
    plane = trace["device"][sorted(trace["device"])[0]]
    lo, hi = progs[0][0], progs[-1][1]
    modules = sorted((s, s + d, n.split("(")[0])
                     for n, s, d in plane["modules"])
    m_starts = [m[0] for m in modules]
    p_starts = [a for a, _b in progs]
    ops = sorted((s, s + d, name, path) for name, path, s, d in plane["ops"]
                 if s + d > lo and s < hi and not xspans._is_container(name))
    by_module = collections.Counter()
    by_scope = collections.Counter()
    none_ops = collections.Counter()
    inside = 0.0
    at = lo      # an instant counts once, for the operation that began first
    for s, e, name, path in ops:
        a, b = max(s, at), min(e, hi)
        if b <= a:
            continue
        at = b
        i = bisect.bisect_right(p_starts, s) - 1
        if i >= 0 and s < progs[i][1]:
            by_module[PROGRAM] += b - a
            inside += b - a
            if leaves is not None:
                leaf = innermost(path, leaves)
                by_scope[leaf] += b - a
                if leaf == NONE:
                    none_ops[tracered.op_key(name)] += b - a
            continue
        j = bisect.bisect_right(m_starts, s) - 1
        owner = modules[j][2] if j >= 0 and s < modules[j][1] else NO_MODULE
        by_module[owner] += b - a
    busy = [(s, e) for s, e, _n, _p in ops]
    between = tracered.gaps(progs, lo, hi)
    programs = float(sum(b - a for a, b in progs))
    outside = tracered.union_length(_clipped(busy, between))
    return {
        "steps": len(progs) * steps_per_program, "span": (lo, hi),
        "programs_n": len(progs), "cut": cut,
        "stretch": float(hi - lo), "programs": programs,
        "first": float(progs[0][1] - progs[0][0]),
        "last": float(progs[-1][1] - progs[-1][0]),
        "outside": outside,
        "idle": float(sum(b - a for a, b in between)) - outside,
        "by_module": dict(by_module), "by_scope": dict(by_scope),
        "gaps": programs - inside, "none_ops": dict(none_ops),
    }


def of_run(obs: dict) -> dict | None:
    """``partition`` of this run's trace, once a decoded trace, with the
    whole table said once on standard error."""
    trace = xspans.load()
    if trace is None:
        return None
    if _CACHE.get("trace") is not trace:
        _CACHE["trace"] = trace
        _CACHE["account"] = acc = partition(
            trace, int(obs["mix"]["engine"]["steps_per_dispatch"]),
            leaf_scopes())
        for line in table(acc) if acc is not None else ():
            notes.say(obs, line)
    return _CACHE["account"]


def ms_a_step(acc: dict, ns: float) -> float:
    return ns / 1e6 / acc["steps"]


def table(acc: dict) -> list:
    """The account as lines: milliseconds a step, rows in falling
    order."""
    def ms(ns):
        return f"{ms_a_step(acc, ns):.3f}"

    def one(ns):   # of one program's steps
        return ms(ns * acc["programs_n"])

    def rows(d, top=None):
        found = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return ", ".join(f"{k} {ms(v)}" for k, v in found) or "nothing"

    lines = [
        f"account: {acc['steps']} steps in {acc['stretch'] / 1e9:.3f} s; "
        f"ms a step: period {ms(acc['stretch'])} = programs "
        f"{ms(acc['programs'])} + outside {ms(acc['outside'])} + idle "
        f"{ms(acc['idle'])}; the first program's step "
        f"{one(acc['first'])}, the last's {one(acc['last'])}"
        + (f"; {acc['cut']} more `{PROGRAM}` events at the window's ends "
           f"are stumps of programs that the device's session cut, left out "
           f"here (decode_step_ms counts them whole)" if acc["cut"] else ""),
        f"account: by module: {rows(acc['by_module'])}"]
    if acc["by_scope"]:
        lines.append(f"account: inside {PROGRAM} by scope: "
                     f"{rows(acc['by_scope'])}, no operation "
                     f"{ms(acc['gaps'])}")
        lines.append(f"account: the largest of {len(acc['none_ops'])} "
                     f"operations under no scope: "
                     f"{rows(acc['none_ops'], 10)}")
    return lines


# -- the engine's stamps ---------------------------------------------------------


def _host_plane(buf, rng):
    """(name, lines, event metadata, statistics' metadata) of one plane:
    ranges of ``buf``."""
    name, lines, metas, stats = "", [], [], []
    for f, v in xspans._fields(buf, *rng):
        if f == 2:
            name = xspans._text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            stats.append(v)
    return name, lines, metas, stats


def decode_stamps(buf: bytes) -> list:
    """[(start_ns, {statistic: value})], rising, of every ``engine/landed``
    event on a host plane of an ``.xplane.pb``: the profiler keeps an
    annotation's keyword arguments as the event's OWN statistics (XEvent
    field 4), which ``xspans.decode`` does not read."""
    out = []
    if LANDED.encode() not in buf:     # a program from before the stamps
        return out
    for f, rng in xspans._fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, metas, stats = _host_plane(buf, rng)
        if not name.startswith("/host:CPU"):
            continue
        wanted = set()
        for m in metas:
            key, val = xspans._map_entry(buf, m)
            if val is not None and any(
                    g == 2 and xspans._text(buf, v) == LANDED
                    for g, v in xspans._fields(buf, *val)):
                wanted.add(key)
        if not wanted:
            continue
        stat_names = {}
        for s in stats:
            key, val = xspans._map_entry(buf, s)
            if val is not None:
                for g, v in xspans._fields(buf, *val):
                    if g == 2:
                        stat_names[key] = xspans._text(buf, v)
        for line in lines:
            t0_ns, events = 0, []
            for g, v in xspans._fields(buf, *line):
                if g == 3:
                    t0_ns = v
                elif g == 4:
                    events.append(v)
            for a, b in events:
                mid = off_ps = 0
                own = []
                for g, v in xspans._fields(buf, a, b):
                    if g == 1:
                        mid = v
                        if mid not in wanted:
                            break
                    elif g == 2:
                        off_ps = v
                    elif g == 4:
                        own.append(v)
                else:
                    out.append((t0_ns + off_ps / 1e3, dict(
                        xspans._stat(buf, s, stat_names) for s in own)))
    return sorted(out, key=lambda r: r[0])


def stamps(path: str | None = None) -> list:
    path = path or xspans.find_xplane()
    if path is None:
        return []
    key = ("stamps", path, os.path.getmtime(path))
    if key not in _CACHE:
        with open(path, "rb") as f:
            _CACHE[key] = decode_stamps(f.read())
    return _CACHE[key]


def busy_between_stamps(acc: dict | None, found: list) -> dict | None:
    """From the first and the last stamp inside the account's stretch:
    the engine's ``device_busy_s`` and ``decode_steps_done`` between those
    two landings, and the trace's own clock between them. None with fewer
    than two stamps there (a program from before them) or no step
    between."""
    if acc is None:
        return None
    lo, hi = acc["span"]
    inside = [(t, st) for t, st in found if lo <= t <= hi
              and "decode_steps_done" in st and "device_busy_s" in st]
    if len(inside) < 2:
        return None
    (t0, a), (t1, b) = inside[0], inside[-1]
    steps = b["decode_steps_done"] - a["decode_steps_done"]
    if steps <= 0:
        return None
    return {"steps": int(steps), "landings": len(inside),
            "busy_s": float(b["device_busy_s"] - a["device_busy_s"]),
            "trace_s": (t1 - t0) / 1e9}
