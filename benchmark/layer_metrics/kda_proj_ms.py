"""Device milliseconds a fused decode step spends in the KDA layers
outside the state update: the traced operations under the scope
``kda_proj`` (the q, k, v, decay, beta and gate projections, the three
short convolutions, the norms, the output gate and projection) inside
whole ``jit_step`` programs, over the steps those programs fuse. None
where no operation carries the scope. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "kda_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
