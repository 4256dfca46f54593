"""Device milliseconds ONE pass of a looped model's stack takes in a fused
decode step: the traced operations whose scope path holds ``ut_pass`` (the
plan's layers, once; its events repeat ``total_ut_steps`` times a step),
inside ``jit_step`` programs, over the steps those programs fuse and the
passes a step runs. None without the scope (a program from before it),
the family's keys or a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_looped, xspans


def read(obs):
    c = obs["config"]["config"]
    if not costs_looped.is_looped(c):
        return None
    found = xspans.scope_seconds(xspans.load(), "ut_pass", "jit_step")
    if found is None:
        return None
    seconds, programs = found
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * seconds / (programs * k * costs_looped.passes(c))
