"""Device milliseconds a fused decode step spends in the experts, in all
layers: the traced operations under the scope ``moe_experts`` (the gather
of each choice's row, the three grouped matmuls, the weighted sum back to
tokens) inside whole ``jit_step`` programs, over the steps those programs
fuse. None where no operation carries the scope. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "moe_experts", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
