"""Share of the HBM roofline one decode step of a model of full and
window GQA layers with routed experts reaches: the least bytes the step
needs (``costs_mixed.decode_step_bytes``: every weight but the embedding
and the experts once, the experts the program counted as hit once each,
the full layers' K/V of the contexts at the traced part's middle, the
rings' keys the program counted on the device) over the chip's published
bandwidth, divided by ``decode_step_ms``. None without the engine's
``window_rows_read`` and ``moe_experts_hit``, the family's keys or a
trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_mixed, costs_moe, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_mixed.is_mixed(c):
        return None
    kv_mid = costs_mixed.kv_tokens_mid(obs)
    window = costs_mixed.counted_per_step(obs, "window_rows_read")
    hit = costs_moe.experts_hit_per_step(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if None in (kv_mid, window, hit, step_ms):
        return None
    least_s = costs_mixed.decode_step_bytes(c, hit, kv_mid, window) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
