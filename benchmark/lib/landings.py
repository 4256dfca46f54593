"""When each decode program's result reached the host, on the device trace's
own clock and from the program's own spans alone: a ``jit_step`` program
ends on the first device at the end of its ``XLA Modules`` event, and it
has LANDED at the end of the fetcher's ``engine/fetch`` span that follows
(the blocking ``device_get`` that brought its tokens over: the first such
span to end at or after the program does). No Python-tracer frame is read.
(The host's clock and the device's can differ by a fraction of a
millisecond within one file: ``tests/test_xspans.py``.)"""

from __future__ import annotations

import bisect

from benchmark.lib import xspans

PROGRAM = "jit_step"
FETCH = "engine/fetch"


def fetch_ends(trace: dict) -> list:
    """Ends (ns) of every ``engine/fetch`` span, rising, unclipped."""
    return sorted(s + d for rows in trace["host"].values()
                  for n, s, d in rows if n == FETCH)


def landing(ends: list, program_end: float) -> float | None:
    """The landing of a program that ended at ``program_end``; None when
    the trace holds no fetch after it."""
    i = bisect.bisect_left(ends, program_end)
    return ends[i] if i < len(ends) else None


def programs(trace: dict) -> list:
    """(start_ns, end_ns) of every decode program on the first device
    plane, whole or cut by the window."""
    plane = trace["device"][sorted(trace["device"])[0]]
    return sorted((s, s + d) for n, s, d in plane["modules"]
                  if n.startswith(PROGRAM))


def device_busy(trace: dict) -> list:
    """(start_ns, end_ns) of every operation on the first device plane
    (of its programs, where the plane has no operations line)."""
    plane = trace["device"][sorted(trace["device"])[0]]
    return ([(s, s + d) for _n, _p, s, d in plane["ops"]]
            or [(s, s + d) for _n, s, d in plane["modules"]])
