"""Device milliseconds a fused decode step spends on the shared expert of its routed
layers: the operations under the scope ``moe_shared`` (gate, up, down,
and the sum into the routed experts' output), inside whole ``jit_step`` programs, over
the steps those programs fuse. None where no operation carries the scope,
or without a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "moe_shared", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
