"""Share of the HBM roofline the scope ``moe_experts`` reaches in a decode
step: the weights of the experts that at least one row chose, once each
(the program's ``moe_experts_hit`` a step, summed over the layers, times
``costs_moe.expert_bytes``), over the chip's published bandwidth, divided
by ``moe_experts_ms``. Bound by bytes: a few rows an expert make 2 to 16
FLOPs a weight byte. Experts no row chose are not counted, and neither
are the activations, so it reads low, never high. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_moe, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    hit = costs_moe.experts_hit_per_step(obs)
    experts_ms = harness.load_reader("moe_experts_ms")(obs)
    if hit is None or experts_ms is None:
        return None
    least_s = costs_moe.experts_bytes(obs["config"]["config"], hit) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (experts_ms / 1e3)
