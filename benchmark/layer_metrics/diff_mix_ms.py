"""Device milliseconds a fused decode step spends turning the two
softmaxes of differential attention into heads: the traced operations
under the scope ``diff_mix`` (lambda, the difference, the 128-wide norm,
``1 - lambda_init``, in every attention layer) inside whole ``jit_step``
programs, over the steps those programs fuse. None where no operation carries the scope (a program from before
it, a model of another family). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "diff_mix", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
