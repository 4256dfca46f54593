"""Device milliseconds a fused decode step spends choosing each row's blocks in the sparse layers: the traced operations under the scope ``sparse_select`` (the pooled key a token completes read back from its pages and written to the store, the store's rows gathered through the page table, the scores of the row's queries against them, the blocks' scores, the choice by rank and the chosen pages' table, in every sparse layer) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries the scope (a program from before it, a model of another
family). Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    found = xspans.scope_seconds(xspans.load(), "sparse_select", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k)
