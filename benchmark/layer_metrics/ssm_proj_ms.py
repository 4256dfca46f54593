"""Device milliseconds a fused decode step spends in the Mamba layers
outside their recurrence: the traced operations under the scope
``ssm_proj`` (the input, (dt | B | C), dt and output products, the
depthwise convolution over the slot's tail and the new token, the tail's
write, the gate) inside whole ``jit_step`` programs, over the steps those
programs fuse. None where no operation carries the scope (a program from before
it, a model of another family). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "ssm_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
