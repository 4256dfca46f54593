"""Device time of one decode step: the traced durations of the engine's
jitted decode program (``jit_step`` on the trace's ``XLA Modules`` line)
over the steps they fuse (``steps_per_dispatch`` each). Layer: forward
pass and kernels. Moves: rollout_tok_s."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    durs = [d for _plane, name, _s, d in trace["modules"]
            if name.startswith("jit_step")]
    if not durs:
        return None
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return 1e3 * sum(durs) / (len(durs) * k)
