"""Milliseconds from a decode program's end on the device to its landing on
the host: the median, over the ``jit_step`` programs wholly inside the
traced window, of (end of the ``engine/fetch`` span that lands it - the
program's end), both on the device trace's clock (``lib/landings.py``). The
transfer, the fetcher's wake-up and, in a batched get, the wait for the
newest entry. None without a trace, a decode program or a fetch span.
Layer: CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import landings, stats, xspans


def read(obs):
    trace = xspans.load()
    progs = xspans.whole_programs(trace, landings.PROGRAM)
    if not progs:
        return None
    ends = landings.fetch_ends(trace)
    lags = [at - end for _start, end in progs
            if (at := landings.landing(ends, end)) is not None]
    return stats.median(lags) / 1e6 if lags else None
