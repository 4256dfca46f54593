"""Share of the window's decode steps whose program updated its KDA
states in the one-pass kernel (``ops/kda_state.py``: a row's state read
once and written once, in place) and not in the two XLA passes of
``hybrid.kda_recurrent_step``: delta ``kda_kernel_steps`` over delta
``decode_steps_done`` of ``GET /get_server_info``, first to last sample,
as a percentage. Both move at a landing, by the same dispatches. 100 for a
float32 state with head sizes of whole lane tiles on a TPU; 0 elsewhere.
None for an engine without the counter. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "kda_kernel_steps", "decode_steps_done")
    return None if r is None else 100.0 * r
