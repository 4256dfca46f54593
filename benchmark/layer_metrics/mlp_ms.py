"""Device milliseconds a fused decode step spends on the MLP sublayer whole, in every layer: the
operations whose scope path holds ``mlp`` (its norm and residual, a dense
MLP's products under ``mlp_dense``, a routed block's ``moe_route``,
``moe_experts`` and ``moe_shared``), inside whole ``jit_step`` programs, over
the steps those programs fuse. None where no operation carries the scope,
or without a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "mlp", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
