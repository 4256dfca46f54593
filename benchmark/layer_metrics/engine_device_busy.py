"""Share of the window with device work outstanding, by the engine's own
completion stamps: delta ``device_busy_s`` over delta ``device_busy_at_s``
(the engine's clock at the landing up to which the seconds are booked), of
``GET /get_server_info``, first to last sample. The program's answer to
``100 - device_idle.rollout``, for the whole window and with the profiler
off. Layer: device. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "device_busy_s", "device_busy_at_s")
    return None if r is None else 100.0 * r
