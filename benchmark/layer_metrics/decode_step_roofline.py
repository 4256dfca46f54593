"""Share of the HBM roofline one decode step reaches: the least bytes the
step must read (every matmul weight once, and the keys and values of every
sequence's context, ``benchmark/lib/costs.py``) over the chip's published
bandwidth, divided by the step's device time (``decode_step_ms``). Decode
is bound by bytes here: the same step's FLOPs over the peak rate are a
tenth of this. Only for traffic without shared prefixes, where the bytes
are unambiguous. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs, harness


def read(obs):
    if obs["peaks"] is None or "kv_tokens_at_end" not in obs:
        return None
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if step_ms is None:
        return None
    # contexts grow by one token a step, and the step was timed in the
    # traced part at the window's start: take that part's middle
    t0, t1 = obs["window"]
    traced = obs["trace"]["window_s"] / (t1 - t0)
    kv_mid = (obs["kv_tokens_at_end"]
              - obs["tokens_in_window"] * (1.0 - traced / 2.0))
    least_s = costs.decode_step_bytes(obs["config"]["config"], kv_mid) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
