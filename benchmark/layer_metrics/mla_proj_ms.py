"""Device milliseconds a fused decode step spends in the MLA layers
outside the attention itself: the traced operations under the scope
``mla_proj`` (the query projection, through its normed latent where the
model has one, ``wkv_a`` and the latent's norm, the rope, ``wkv_b``'s key
half folded into the query and its value half applied after, the head
gate where there is one, ``wo``) inside whole ``jit_step`` programs, over
the steps those programs fuse. None where no operation carries the scope.
Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "mla_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
