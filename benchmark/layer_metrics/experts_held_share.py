"""Share of the router's choices that fell on experts this chip holds,
over the window's decode steps and sparse layers: delta ``moe_routed``
(choices of live rows on held experts) over delta ``moe_choices`` (all
their choices) of ``GET /get_server_info``, in percent. With 128 of 512
experts held and near-uniform routing about 25; 0 or 100 means that the
router is not as wide as published. None for an engine without the
counters. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "moe_routed", "moe_choices")
    return None if r is None else 100.0 * r
