"""Live rows a decode step: delta ``row_steps_done`` (steps x the live rows of
their dispatch) over delta ``decode_steps_done`` of ``GET
/get_server_info``, first to last sample; both move at the landing. The
occupancy where the work happens, counted and not sampled (``slot_occupancy``
is a 2 Hz sample of an average). None for an engine without the counter.
Layer: CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    return counters.delta_ratio(obs, "row_steps_done", "decode_steps_done")
