"""Share of the window's decode steps whose program's expert calls took a
tile's rows from the tokens by a prefetched table and summed each token's
products back themselves (``ops/grouped_matmul.py::expert_rows``), and not
through a tiled copy of the rows made and read back by XLA's operations
around the kernels: delta ``moe_gather_kernel_steps`` over delta
``decode_steps_done`` of ``GET /get_server_info``, first to last sample,
as a percentage. Both move at a landing, by the same dispatches. 100 where
a step's tokens and their float32 sums fit VMEM beside the slabs and an
expert has a few rows, on a TPU; 0 elsewhere. None for an engine without
the counter. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "moe_gather_kernel_steps",
                             "decode_steps_done")
    return None if r is None else 100.0 * r
