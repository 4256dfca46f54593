"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program. Busy time is the union of the operations' intervals;
an idle gap is an interval of the window in which no operation ran. Each
gap is labelled by what the host was doing in it: the innermost Python
function (outside the standard library's waiting primitives) or host
``TraceMe`` span, on any thread, that covers the gap's middle half.

The traced window is the span named ``bench/window`` that the harness
writes from its main thread; without it, first event to last.
"""

from __future__ import annotations

import collections
import re

WINDOW_SPAN = "bench/window"
_CONTAINERS = ("while", "conditional", "call")
MAX_LABELLED_GAPS = 2000   # the longest ones; the rest are summed
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# innermost frames that only say "waiting", not for what
_WAITING = ("$threading.py", "$queue.py", "$selectors.py", "$socket.py",
            "$<built-in>", "$time", "$<unknown>")


_SHAPE = re.compile(r"\b(?:bf16|f32|f16|s32|u32|s8|u8|pred)\[[\d,]*\]")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def op_key(name: str) -> str:
    """One name for every instance of the same operation: the trace names
    an operation by its whole HLO line (``%fusion.5560 = f32[526336]{...}
    fusion(f32[257,8,16,128]{...} %x, ...)``), a new one for each layer.
    Keeps the name without its number, the output shape and the first two
    operand shapes."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    base = re.sub(r"(\.\d+)+$", "", lhs.lstrip("%"))
    m = _OPCODE.search(" " + rhs)
    if m is None:
        return base
    outs = _SHAPE.findall(rhs[:m.start()])
    ins = _SHAPE.findall(rhs[m.end() - 1:])
    key = base + (" " + outs[0] if outs else "")
    if ins:
        key += " <- " + ",".join(ins[:2])
    return key


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """{"device": {plane: {line: [(name, start_ns, dur_ns)]}},
        "host": {line: [...]}} from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"device": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            out["device"][plane.name] = {
                ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                out["host"][ln.name] = _events(ln)
    return out


def union_length(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of [lo, hi] not covered by any of ``intervals``."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


class _HostIndex:
    """The host threads' events that can name a gap: everything but the
    standard library's waiting primitives and the harness's own window
    span, as arrays for a stabbing query."""

    def __init__(self, lines: dict):
        import numpy as np

        evs = [(n, s, d) for line in lines.values() for n, s, d in line
               if d > 0 and n != WINDOW_SPAN and not n.startswith(_WAITING)]
        self.names = [n for n, _, _ in evs]
        self.start = np.asarray([s for _, s, _ in evs], dtype=np.float64)
        self.end = self.start + np.asarray([d for _, _, d in evs],
                                           dtype=np.float64)

    def label(self, a: float, b: float) -> str:
        """The innermost (shortest) event that covers the middle half of
        the gap [a, b]: what the host was in for most of it."""
        import numpy as np

        q = (b - a) / 4.0
        hit = np.flatnonzero((self.start <= a + q) & (self.end >= b - q))
        if hit.size == 0:
            return "no host span"
        best = hit[np.argmin(self.end[hit] - self.start[hit])]
        return self.names[int(best)]


def reduce(trace: dict, top: int = 10) -> dict:
    """The numbers the harness reports from one trace."""
    window = None
    for evs in trace["host"].values():
        for name, s, d in evs:
            if name == WINDOW_SPAN:
                window = (s, s + d)
    all_dev = [e for lines in trace["device"].values()
               for evs in lines.values() for e in evs]
    if not all_dev:
        raise ValueError("the trace holds no device event")
    if window is None:
        window = (min(s for _, s, _ in all_dev),
                  max(s + d for _, s, d in all_dev))
    lo, hi = window
    window_s = (hi - lo) / 1e9

    host = _HostIndex(trace["host"])

    busy, op_s, modules = [], collections.Counter(), []
    gap_s = collections.Counter()
    for plane, lines in sorted(trace["device"].items()):
        ops = _clip(lines.get(OPS_LINE, []), lo, hi)
        if not ops:   # a plane of another kind (no operations): skip
            continue
        busy.append(union_length([(a, b) for _, a, b in ops]) / 1e9)
        for name, a, b in ops:
            key = op_key(name)
            # a loop's own event spans its body's operations
            if not key.startswith(_CONTAINERS):
                op_s[key] += (b - a) / 1e9
        for name, a, b in _clip(lines.get(MODULES_LINE, []), lo, hi):
            modules.append((plane, name, a / 1e9, (b - a) / 1e9))
        if len(busy) == 1:   # label the first chip's gaps
            found = sorted(gaps([(a, b) for _, a, b in ops], lo, hi),
                           key=lambda g: g[0] - g[1])
            for a, b in found[:MAX_LABELLED_GAPS]:
                gap_s[host.label(a, b)] += (b - a) / 1e9
            rest = sum(b - a for a, b in found[MAX_LABELLED_GAPS:])
            if rest:
                gap_s["shorter gaps, not labelled"] += rest / 1e9
    if not busy:
        raise ValueError("no operation ran on a device in the window")
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "device_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gap_s.most_common(top)],
        "modules": modules,
        "op_seconds": dict(op_s),
    }
