"""Share of the HBM roofline one decode step of a SambaY model reaches:
the least bytes the step needs (``costs_sambay.decode_step_bytes``: every
matmul weight and the tied head once, the shared pool's K/V of the
contexts at the traced part's middle once a reading layer, the rings' keys
and the Mamba rows the program counted on the device, states and tails
read and written) over the chip's published bandwidth, divided by
``decode_step_ms``. None without the engine's ``window_rows_read`` and
``ssm_state_rows``, the family's keys or a trace. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_sambay, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_sambay.is_sambay(c):
        return None
    kv_mid = costs_sambay.kv_tokens_mid(obs)
    window = costs_sambay.counted_per_step(obs, "window_rows_read")
    rows = costs_sambay.counted_per_step(obs, "ssm_state_rows")
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if None in (kv_mid, window, rows, step_ms):
        return None
    least_s = costs_sambay.decode_step_bytes(c, kv_mid, window, rows) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
