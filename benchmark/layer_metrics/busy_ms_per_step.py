"""Device-busy milliseconds a fused decode step, over the whole window and
outside any profiler session: delta ``device_busy_s`` over delta
``decode_steps_done`` of ``GET /get_server_info``, first to last sample.
Both counters move only when a result lands on the host, so the ratio has
no dispatch quantum. Against ``decode_step_ms`` (the traced first seconds)
it says what a step costs later in the window. Layer: device. Moves:
rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "device_busy_s", "decode_steps_done")
    return None if r is None else 1e3 * r
