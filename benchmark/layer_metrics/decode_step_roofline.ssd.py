"""Share of the HBM roofline one decode step of a model of Mamba-2,
attention and expert layers reaches: the least bytes the step needs
(``costs_nemotron_h.decode_step_bytes``: every mixer's, router's and shared
expert's matmul weight and the untied head once, two projections of each
expert hit and the Mamba-2 rows' states and tails read and written, both
as the program counted them on the device, neither of which grows with
the window; the keys and values the rows attend over at the TRACED part's
middle, ``keys_traced``) over the chip's published bandwidth, divided by
``decode_step_ms``. None without the engine's ``moe_experts_hit`` and
``ssd_state_rows``, the family's keys or a trace. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_nemotron_h as costs
from benchmark.lib import harness


def read(obs):
    if obs["peaks"] is None:
        return None
    hit = costs.counted_per_step(obs, "moe_experts_hit")
    keys = costs.keys_traced(obs)
    rows = costs.counted_per_step(obs, "ssd_state_rows")
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if None in (hit, keys, rows, step_ms):
        return None
    least_s = costs.decode_step_bytes(
        obs["config"]["config"], hit, keys, rows) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
