"""Device time of one decode step: the durations of the engine's jitted
decode program (``jit_step`` on the trace's ``XLA Modules`` line) over the
steps they fuse (``steps_per_dispatch`` each), over the programs that lie
wholly inside the traced window, as ``xspans.scope_seconds`` counts them:
the profiler's session starts and stops inside a program, and the two it
cuts are on the trace with the part of their time it saw. Layer: forward
pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import xspans


def read(obs):
    progs = xspans.whole_programs(xspans.load(), "jit_step")
    if not progs:
        return None
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * sum(b - a for a, b in progs) / 1e9 / (len(progs) * k)
