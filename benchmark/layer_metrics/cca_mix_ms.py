"""Device milliseconds a fused decode step spends mixing the CCA layers'
latents: the traced operations under the scope ``cca_mix`` (the value
shift, the depthwise and the head-wise convolution over the slot's tails
and the new token, the q-k mean, the per-head norm with its temperature,
the rope, and the tails' read and write) inside whole ``jit_step``
programs, over the steps those programs fuse. None where no operation
carries the scope (a program from before it, a model without CCA layers).
Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "cca_mix", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
