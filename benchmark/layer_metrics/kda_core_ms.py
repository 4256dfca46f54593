"""Device milliseconds a fused decode step spends updating the recurrent
states, in all KDA layers: the traced operations under the scope
``kda_core`` (read each live row's state, decay it by channel, apply the
delta rule, read the output, write the state back) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries the scope. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "kda_core", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
