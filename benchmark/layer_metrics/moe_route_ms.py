"""Device milliseconds a fused decode step spends routing, in all layers:
the traced operations under the scope ``moe_route`` (router matmul,
softmax, top-k, the sort of the choices by expert and the experts' row
counts) inside whole ``jit_step`` programs, over the steps those programs
fuse. None where no operation carries the scope (a dense model, a program
older than the scope). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "moe_route", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
