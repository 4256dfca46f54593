"""Share of the HBM roofline one decode step of a model with routed
experts reaches: the least bytes the step must read
(``costs_moe.decode_step_bytes``: attention, router and head weights, the
experts that at least one row chose, the keys and values of every
context, taken at the traced part's middle as ``decode_step_roofline``
takes them) over the chip's published bandwidth, divided by
``decode_step_ms``. None without the engine's ``moe_experts_hit``. Layer:
forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_moe, harness


def read(obs):
    reduced = obs.get("trace")
    if obs["peaks"] is None or not reduced or "kv_tokens_at_end" not in obs:
        return None
    hit = costs_moe.experts_hit_per_step(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if hit is None or step_ms is None:
        return None
    t0, t1 = obs["window"]
    traced = reduced["window_s"] / (t1 - t0)
    kv_mid = (obs["kv_tokens_at_end"]
              - obs["tokens_in_window"] * (1.0 - traced / 2.0))
    least_s = costs_moe.decode_step_bytes(
        obs["config"]["config"], hit, kv_mid) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
