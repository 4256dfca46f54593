"""Device milliseconds a fused decode step spends under the scope
``mla_core`` (the new token's latent row written to its page and the
absorbed attention over the paged latent pool, in all MLA layers) inside
whole ``jit_step`` programs, over the steps those programs fuse. None
where no operation carries the scope. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "mla_core", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
