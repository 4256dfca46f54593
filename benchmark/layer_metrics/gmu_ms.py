"""Device milliseconds a fused decode step spends in the gated memory
units: the traced operations under the scope ``gmu`` (the gate's product,
the product with the scan output carried down the step, the output
product, in every such layer) inside whole ``jit_step`` programs, over the
steps those programs fuse. None where no operation carries the scope (a program from before
it, a model of another family). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "gmu", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
