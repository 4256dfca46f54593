"""Share of the HBM roofline the scope ``moe_experts`` reaches in a decode
step of a model whose experts are TWO matrices (``relu(x W_up)^2 W_down``):
the weights of the experts that at least one row chose, once each (the
program's ``moe_experts_hit`` a step, summed over the expert layers, times
``costs_nemotron_h.expert_params``' two projections; ``costs_moe`` counts
three, and its reader would read 3/2 of this), over the chip's published
bandwidth, divided by ``moe_experts_ms``. Bound by bytes: 3 rows an expert
make 3 FLOPs a weight byte. Experts no row chose are not counted, and
neither are the activations, so it reads low, never high. None without the
counter, the family's keys or a trace. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import costs_nemotron_h as costs
from benchmark.lib import harness


def read(obs):
    if obs["peaks"] is None:
        return None
    hit = costs.counted_per_step(obs, "moe_experts_hit")
    experts_ms = harness.load_reader("moe_experts_ms")(obs)
    if hit is None or experts_ms is None:
        return None
    least_s = costs.experts_bytes(obs["config"]["config"], hit) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (experts_ms / 1e3)
