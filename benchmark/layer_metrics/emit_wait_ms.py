"""Milliseconds a landed dispatch waits for the loop thread: from its landing
on the fetcher thread (``device_get`` returned) to the start of its
``emit``, i.e. what a finished result sits in ``_fetched_q``. Delta
``emit_wait_s`` over delta ``dispatches_emitted`` of ``GET
/get_server_info``, first to last sample; both move at the emission, on the
engine's clock. None for an engine without the counter. Layer: CBEngine
loop. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "emit_wait_s", "dispatches_emitted")
    return None if r is None else 1e3 * r
