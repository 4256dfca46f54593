"""How uneven the routing was: the busiest expert's rows over the mean
expert's, over the window's decode steps and layers: delta
``moe_load_max`` times the number of experts over delta ``moe_routed`` of
``GET /get_server_info`` (both summed by the engine over the same steps
and layers). 1 is perfectly even; ``num_experts / num_experts_per_tok``
is every row on the same experts. None for a dense model or an engine
without the counters. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "moe_load_max", "moe_routed")
    n = obs["config"]["config"].get("num_experts")
    return None if r is None or not n else r * int(n)
