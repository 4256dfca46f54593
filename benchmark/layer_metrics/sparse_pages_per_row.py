"""Pages a live row attends over, a K/V head and a sparse layer, as the
program counted them on the device: delta ``sparse_pages_read`` over delta
``row_steps_done`` of the window's ``server_info`` samples, over the K/V
heads and the sparse layers. The published ``topk`` (64) while every row is
past ``dense_len``; more says rows attend densely, fewer that the choice
degenerates. None without the counter (a program from before it) or the
family's keys. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_sala


def read(obs):
    return costs_sala.pages_per_row(obs)
