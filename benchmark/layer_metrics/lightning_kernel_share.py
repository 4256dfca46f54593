"""Share of the window's landed decode steps whose program updated its
lightning states in the one-pass kernel (``ops/lightning_state.py``):
delta ``lightning_kernel_steps`` over delta ``decode_steps_done`` of the
window's ``server_info`` samples, in percent. 100 on a TPU at the
published head size; 0 says the steps took the oracle's two passes. None
without the counter (a program from before it). Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    share = counters.delta_ratio(obs, "lightning_kernel_steps",
                                 "decode_steps_done")
    return None if share is None else 100.0 * share
