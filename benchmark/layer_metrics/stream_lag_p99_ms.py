"""The tail of ``stream_lag_ms``: milliseconds from the engine's put of a
burst's first line to the return of the server's flush of that burst, at
the highest percentile up to the 99th that has ten bursts of the window
beyond it. From what the buckets of ``stream_lag_hist`` (cumulative log2
buckets, ``GET /get_server_info``) gained between the window's first and
last sample: the middle of the bucket the rank falls in, 9% wide. Says the
percentile and the count on standard error. None for an engine without the
histogram or a window of ten bursts or fewer. Layer: manager and server.
Moves: rollout_tok_s."""

from benchmark.lib import loghist, notes


def read(obs):
    counts = loghist.gained(obs, "stream_lag_hist")
    found = loghist.tail(counts) if counts else None
    if found is None:
        return None
    pct, value, n = found
    notes.say(obs, f"stream_lag_p99_ms: percentile {pct:g} of {n} bursts")
    return 1e3 * value
