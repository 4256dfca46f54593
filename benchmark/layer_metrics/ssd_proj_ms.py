"""Device milliseconds a fused decode step spends on the Mamba-2 layers' products around their recurrence: the traced operations under the scope ``ssd_proj`` (the in-projection, the short convolution over the slot's tail with its shift, the decay and ``dt x``, the skip, the gate, the grouped RMSNorm and the out-projection, in every Mamba-2 layer) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries the scope (a program from before it, a model of another
family). Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "ssd_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
