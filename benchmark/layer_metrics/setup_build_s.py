"""Seconds the engine spent building its programs before the window: the
cumulative ``build_s`` (each miss of the engine's program tables, to the
first return of its call: trace, lower, compile or cache read, enqueue) of
``GET /get_server_info`` at the window's FIRST sample, so since the process
started (``programs_built.rollout`` 0 says none of it is the window's). The
part of ``setup_s`` that is compiling and cache reads, as against prefill
and warm-up. None for an engine without the counter. Layer: CBEngine loop.
Moves: setup_s."""


def read(obs):
    xs = [s["build_s"] for s in obs.get("server_info", []) if "build_s" in s]
    return xs[0] if xs else None
