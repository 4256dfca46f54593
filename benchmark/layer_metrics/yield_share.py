"""Rows that gave up their slot and their KV pages because the pool had no
more, per hundred decode dispatches of the window: delta ``slot_yields``
over delta ``decode_dispatches`` of ``GET /get_server_info``, first to
last sample, as a percentage. A row that yields goes back to the head of
the engine's queue and is prefilled again when the pool has headroom, so
every yield costs its context's prefill. 0 where the rows' pages fit the
pool for the whole window. None for an engine without the counter. Layer:
KV manager. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "slot_yields", "decode_dispatches")
    return None if r is None else 100.0 * r
