"""Mean absolute difference, in nats, between the log-probabilities the
system gave its sampled tokens and the float32 reference's on the same
weights (the run's correctness sample, outside the window). Not a speed:
it is here so that the ledger shows when a change buys time with
precision, before the run's ``correct`` fails at the configuration's
limit (1.25 times what bf16 measures). Layer: forward pass and kernels."""


def read(obs):
    ref = obs["checks"].get("reference", {})
    return ref.get("logprob_mean_abs_diff") if ref.get("positions") else None
