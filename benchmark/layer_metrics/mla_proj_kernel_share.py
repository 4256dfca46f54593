"""Share of the window's decode steps whose program multiplied its MLA
layers' ``wkv_b`` where it lies in the stacked parameter
(``ops/mla_proj.py``: each half read once by the product that multiplies
it, nothing of a layer's weights written) and not through the einsum on a
copy of the layer laid out with the heads major: delta
``mla_proj_kernel_steps`` over delta ``decode_steps_done`` of ``GET
/get_server_info``, first to last sample, as a percentage. Both move at a
landing, by the same dispatches. 100 for head sizes and a rank of whole
lane tiles on a TPU; 0 elsewhere. None for an engine without the counter.
Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "mla_proj_kernel_steps",
                             "decode_steps_done")
    return None if r is None else 100.0 * r
