"""Device milliseconds a fused decode step spends updating the Mamba-2 layers' states: the traced operations under the scope ``ssd_core`` (the one-pass kernel of ``ops/ssd_state.py``: a live row's float32 state read, decayed, added to and written back, and its output's product, in every Mamba-2 layer) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries the scope (a program from before it, a model of another
family). Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "ssd_core", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
