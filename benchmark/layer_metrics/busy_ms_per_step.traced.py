"""``busy_ms_per_step`` over the traced stretch alone: from the first and
the last ``engine/landed`` stamp inside it (the engine's cumulative
``device_busy_s`` and ``decode_steps_done`` as of each landing, on the
device trace's clock: ``lib/account.py``), delta busy seconds over delta
steps. Against ``step_period_ms`` it checks the engine's stamps (they part
by the landings' lag over a stretch of seconds); against
``busy_ms_per_step`` (the whole window) it says whether a step costs more
later in the window. Says on standard error the stamps' steps and seconds
beside the trace's own clock between the same two landings. None without
a trace, a whole decode program or two stamps (a program from before
them). Layer: device. Moves: rollout_tok_s."""

from benchmark.lib import account, notes


def read(obs):
    acc = account.of_run(obs)
    found = account.busy_between_stamps(acc, account.stamps())
    if found is None:
        return None
    notes.say(
        obs, f"busy_ms_per_step.traced: {found['landings']} landings in "
        f"the stretch; between the first and the last the engine counted "
        f"{found['steps']} steps in {found['busy_s']:.6f} s busy, the "
        f"trace's clock ran {found['trace_s']:.6f} s "
        f"({1e3 * found['trace_s'] / found['steps']:.3f} ms a step)")
    return 1e3 * found["busy_s"] / found["steps"]
