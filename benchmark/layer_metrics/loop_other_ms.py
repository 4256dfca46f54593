"""Host milliseconds a decode dispatch the engine's loop thread spends on the
rest: admission (``collect_wave``), spill readmission (``restore``) and the
residual no phase claims (``other``: the loop's own control flow between
phases). Delta ``phase_collect_wave_s`` + ``phase_restore_s`` +
``phase_other_s`` over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample. A part of ``loop_host_ms``. Says
the three on standard error. None for an engine without the counters.
Layer: CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import notes, phases

PARTS = ("collect_wave", "restore", "other")


def read(obs):
    parts = [phases.ms_a_dispatch(obs, p) for p in PARTS]
    if None in parts:
        return None
    notes.say(obs, "loop_other_ms: " + ", ".join(
        f"{p} {v:.3f}" for p, v in zip(PARTS, parts)))
    return sum(parts)
