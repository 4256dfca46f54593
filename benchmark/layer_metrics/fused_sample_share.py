"""Share of the window's decode steps whose program drew its tokens inside
the output matmul (``decoder.head_and_sample``) and wrote no logits: delta
``fused_sample_steps`` over delta ``decode_steps_done`` of ``GET
/get_server_info``, first to last sample, as a percentage. Both move at a
landing, by the same dispatches. 100 where every step was plain temperature
sampling on one chip; 0 under top-p or top-k, a quantised head or a mesh.
None for an engine without the counter. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "fused_sample_steps", "decode_steps_done")
    return None if r is None else 100.0 * r
