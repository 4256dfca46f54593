"""Share of its roofline one decode step of a model that is latent
attention in every layer reaches: the least time the step needs
(``costs_latent.decode_step_least``: mixer, router, shared-expert, dense
and head weights once and the held experts that at least one row chose
once each, over the chip's published bandwidth; the attention over the
contexts at the traced part's middle at the slower of its two roofs,
latent bytes over bandwidth or FLOPs over the published peak), divided by
``decode_step_ms``. None without the engine's ``moe_experts_hit``, a
latent key or a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_latent, costs_moe, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_latent.is_latent(c):
        return None
    kv_mid = costs_latent.kv_tokens_mid(obs)
    if kv_mid is None:
        return None
    hit = costs_moe.experts_hit_per_step(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if hit is None or step_ms is None:
        return None
    least_s = costs_latent.decode_step_least(c, hit, kv_mid, obs["peaks"])
    return 100.0 * least_s / (step_ms / 1e3)
