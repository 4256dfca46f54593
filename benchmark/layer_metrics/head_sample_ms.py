"""Device milliseconds a fused decode step spends from the last layer's
output to its token: the traced operations under the scopes ``head`` (the
final norm and the output matmul, or the kernel that also samples) and
``sample`` (the sampler, or what is left of it: key split, stop check,
``where``s) inside whole ``jit_step`` programs, over the steps those
programs fuse. None where no operation carries the scope ``head``. Layer:
forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    trace = xspans.load()
    head = xspans.scope_seconds(trace, "head", "jit_step")
    if head is None:
        return None
    sample = xspans.scope_seconds(trace, "sample", "jit_step")
    seconds = head[0] + (sample[0] if sample else 0.0)
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (head[1] * k)
