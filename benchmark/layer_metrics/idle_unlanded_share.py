"""Share of the traced window in which the first device runs nothing AND
some decode program that has ended on it has not landed yet (its landing:
the end of the ``engine/fetch`` span that follows it, ``lib/landings.py``):
the part of ``device_idle.rollout`` that is "finished and not taken in", as
against "nothing dispatched" (100 - ``engine_device_busy``: the engine's
completion stamps call the device busy until the landing). A program
without a landing in the trace counts as unlanded to the window's end. Says
on standard error the three in SECONDS, since the trace covers the
window's first seconds and the stamps all of it: the device idle in the
traced window, of that with a finished program not landed, and the seconds
the engine's stamps count with nothing outstanding between the window's
samples (delta ``device_busy_at_s`` less delta ``device_busy_s``); and for
the idle time with nothing unlanded the share of the traced window each
``engine/*`` span was open in it on some thread. None without a trace, a
window or a decode program. Layer: device. Moves: rollout_tok_s."""

from benchmark.lib import landings, notes, tracered, xspans


def _overlap(spans, gaps) -> float:
    return sum(tracered.union_length(
        [(max(s, a), min(e, b)) for s, e in spans if s < b and e > a])
        for a, b in gaps)


def read(obs):
    trace = xspans.load()
    if not trace or trace["window"] is None or not trace["device"]:
        return None
    lo, hi = trace["window"]
    ends = landings.fetch_ends(trace)
    unlanded = []
    for _start, end in landings.programs(trace):
        if lo <= end < hi:
            at = landings.landing(ends, end)
            unlanded.append((end, hi if at is None else min(at, hi)))
    if not unlanded:
        return None
    idle = tracered.gaps(landings.device_busy(trace), lo, hi)
    share = 100.0 / (hi - lo)
    both = _overlap(unlanded, idle)
    rest = [g for a, b in idle for g in tracered.gaps(unlanded, a, b)]
    by_name: dict = {}
    for rows in xspans.host_spans(trace, "engine/").values():
        for name, s, e in rows:
            by_name.setdefault(name, []).append((s, e))
    held = sorted(((_overlap(v, rest) * share, k)
                   for k, v in by_name.items()), reverse=True)
    xs = [s for s in obs.get("server_info", [])
          if "device_busy_s" in s and "device_busy_at_s" in s]
    dry = (xs[-1]["device_busy_at_s"] - xs[0]["device_busy_at_s"]
           - xs[-1]["device_busy_s"] + xs[0]["device_busy_s"]) if xs else 0.0
    notes.say(
        obs, f"idle_unlanded_share: the device idle "
        f"{sum(b - a for a, b in idle) / 1e9:.3f} s of the traced "
        f"{(hi - lo) / 1e9:.3f}, {both / 1e9:.3f} s with a finished program "
        f"not landed; the engine's stamps: {dry:.3f} s with nothing "
        f"outstanding in the window; in the rest: "
        + ", ".join(f"{k} {v:.2f}%" for v, k in held[:5] if v))
    return both * share
