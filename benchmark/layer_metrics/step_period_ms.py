"""What a fused decode step costs the DEVICE in the traced seconds, whatever
runs between programs: the stretch from the first whole ``jit_step``
program's start to the last one's end on the first device plane, over the
steps of its programs (``lib/account.py``, which says the whole account of
the stretch once a run on standard error). By construction
``decode_step_ms`` + ``step_outside_ms`` + the stretch's idle a step =
this, wherever every ``jit_step`` event inside the window is a whole
program: where the device's session cut one inside the window,
``decode_step_ms`` counts the stump as a program, this leaves it out, and
the account's note says so. None without a trace, a window or a whole decode program. Layer:
device. Moves: rollout_tok_s."""

from benchmark.lib import account


def read(obs):
    acc = account.of_run(obs)
    return None if acc is None else account.ms_a_step(acc, acc["stretch"])
