"""Device milliseconds a fused decode step spends in operations that lie
under NO leaf scope the program declares (``polyrl_tpu/models/scopes.py``):
inside whole ``jit_step`` programs, the operations whose path holds no
declared leaf (``lib/account.py``; its note lists the ten largest by
name). What XLA emits with no metadata stays here; everything the step's
own code emits has a scope. None without a trace, a whole decode program,
or the declaration (a program from before it). Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import account


def read(obs):
    acc = account.of_run(obs)
    if acc is None or not acc["by_scope"]:
        return None
    return account.ms_a_step(acc, acc["by_scope"].get(account.NONE, 0.0))
