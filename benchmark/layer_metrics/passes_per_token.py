"""Passes of the stack a live row ran a decode step, as the program
counted them on the device: delta ``ut_passes`` over delta
``row_steps_done`` of the window's ``server_info`` samples. The published
``total_ut_steps`` (4.00) while every token is served by the last pass;
anything less is work left out. None without the counter (a program from
before it). Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    return counters.delta_ratio(obs, "ut_passes", "row_steps_done")
