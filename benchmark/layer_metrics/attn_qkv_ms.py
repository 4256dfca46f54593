"""Device milliseconds a fused decode step spends on an attention layer's products before its
core, in every layer that has them: the operations under the scope
``attn_qkv`` (the norm where the layer loop does not own it, the q | k |
v product, bias, rope, a gate's logits, differential heads' paired
queries), inside whole ``jit_step`` programs, over
the steps those programs fuse. None where no operation carries the scope,
or without a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "attn_qkv", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
