"""Device-busy milliseconds a fused decode step OUTSIDE the decode
programs: the union of the operations that run between the ``jit_step``
events of the stretch (``lib/account.py``; its note names every other
``XLA Modules`` program with its milliseconds a step, and what runs under
no module), over the steps of the stretch's programs. Zero on a device
that runs nothing but the step. None without a trace, a window or a whole
decode program. Layer: device. Moves: rollout_tok_s."""

from benchmark.lib import account


def read(obs):
    acc = account.of_run(obs)
    return None if acc is None else account.ms_a_step(acc, acc["outside"])
