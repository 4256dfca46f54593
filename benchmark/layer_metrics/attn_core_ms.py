"""Device milliseconds a fused decode step spends under the scope
``attn_core`` (the KV write, the paged attention, and every gather,
convert or reshape between them, in all layers): the traced operations
whose scope path holds ``attn_core``, inside ``jit_step`` programs, over
the steps those programs fuse. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "attn_core", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
