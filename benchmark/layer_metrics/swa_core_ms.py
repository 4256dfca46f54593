"""Device milliseconds a fused decode step spends attending over the
window layers' rings: the traced operations under the scope ``swa_core``
(the token's K/V written at its position modulo the window into the slot's
own pages, the paged attention over at most ``sliding_window`` keys, in
every window layer) inside whole ``jit_step`` programs, over the steps
those programs fuse. None where no operation carries the scope (a program from before
it, a model of another family). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "swa_core", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
