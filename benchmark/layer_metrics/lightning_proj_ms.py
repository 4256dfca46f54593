"""Device milliseconds a fused decode step spends in the linear-attention layers' products: the traced operations under the scope ``lightning_proj`` (q | k | v and the gate's products, the q/k norms, the rope, the output's norm and gate and ``W_o``, in every lightning layer) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries the scope (a program from before it, a model of another
family). Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "lightning_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
