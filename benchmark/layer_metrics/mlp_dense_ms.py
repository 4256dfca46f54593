"""Device milliseconds a fused decode step spends on the gate, up and down products of its
dense MLPs, and nothing else: the operations under the scope
``mlp_dense`` (inside ``mlp``), inside whole ``jit_step`` programs, over
the steps those programs fuse. None where no operation carries the scope,
or without a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "mlp_dense", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
