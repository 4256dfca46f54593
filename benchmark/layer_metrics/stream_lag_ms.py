"""Milliseconds from the engine's put of a burst's first line to the
return of the server's flush of that burst, a burst: delta ``stream_lag_s``
over delta ``stream_chunks`` of ``GET /get_server_info``, first to last
sample. The server's share of the path from the fetcher thread to the
client. Layer: manager and server. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "stream_lag_s", "stream_chunks")
    return None if r is None else 1e3 * r
