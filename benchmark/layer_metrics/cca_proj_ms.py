"""Device milliseconds a fused decode step spends in the CCA layers'
projections: the traced operations under the scope ``cca_proj`` (``W_in``:
the query latent, the key latent and the two value halves in one matmul;
``Wo``) inside whole ``jit_step`` programs, over the steps those programs
fuse. None where no operation carries the scope. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "cca_proj", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
