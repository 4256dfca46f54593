"""Share of the window's landed decode steps whose program chose its
sparse layers' blocks in the kernel that walks a row's own pooled pages
(``ops/sparse_select.py``), and not by XLA's gather of every row's pooled
keys at the table's full width: delta ``sparse_kernel_steps`` over delta
``decode_steps_done`` of the window's ``server_info`` samples, in percent.
Both move at a landing, by the same dispatches. 100 on a TPU at the
published sizes (a page's pooled keys one float32 tile, heads of 128); 0
says the steps took the jnp form. None without the counter (a program from
before it) or where no step landed. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    share = counters.delta_ratio(obs, "sparse_kernel_steps",
                                 "decode_steps_done")
    return None if share is None else 100.0 * share
