"""Milliseconds a decode dispatch the engine's loop thread WAITS: blocked on
the fetcher (``sample_fetch``) or with nothing to do (``idle``). Delta
``phase_sample_fetch_s`` + ``phase_idle_s`` over delta ``decode_dispatches``
of ``GET /get_server_info``, first to last sample. With ``loop_host_ms`` it
is the loop's whole wall a dispatch: the slack the host has behind a
program. Says the two apart and their share of ``loop_wall_s`` on standard
error: what ``loop_wait_share`` reads from the trace under the Python
tracer, here from counters that run in every run. None for an engine
without the counters. Layer: CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import counters, notes, phases

PARTS = ("sample_fetch", "idle")


def read(obs):
    parts = [phases.ms_a_dispatch(obs, p) for p in PARTS]
    wall = counters.delta_ratio(obs, "loop_wall_s", "decode_dispatches")
    if None in parts or not wall:
        return None
    notes.say(obs, "loop_wait_ms: " + ", ".join(
        f"{p} {v:.3f}" for p, v in zip(PARTS, parts))
        + f", {sum(parts) / (10.0 * wall):.1f}% of the loop's wall")
    return sum(parts)
