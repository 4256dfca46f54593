"""Share of the window's landed decode steps whose program updated its
Mamba-2 states in the one-pass kernel (``ops/ssd_state.py``): delta
``ssd_kernel_steps`` over delta ``decode_steps_done`` of the window's
``server_info`` samples, in percent. 100 on a TPU at the published sizes; 0
says the steps took the oracle's two passes. None without the counter (a
program from before it). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    share = counters.delta_ratio(obs, "ssd_kernel_steps", "decode_steps_done")
    return None if share is None else 100.0 * share
