"""Host milliseconds the engine loop spends a decode dispatch between its
waits: delta ``loop_host_s`` (loop wall less the ``idle`` and
``sample_fetch`` phases) over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample. What the host must do before
the device can have its next dispatch. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "loop_host_s", "decode_dispatches")
    return None if r is None else 1e3 * r
