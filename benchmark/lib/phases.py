"""The engine loop's host time a decode dispatch, by phase: the loop
thread's cumulative self seconds (``phase_<phase>_s`` of ``GET
/get_server_info``; ``polyrl_tpu/obs/engine_profile.py``) over
``decode_dispatches``, first to last sample of the window. The eight phases
that are no wait partition ``loop_host_ms``; the four ``loop_*_ms`` readers
group them."""

from __future__ import annotations

from benchmark.lib import counters


def ms_a_dispatch(obs: dict, *phases: str) -> float | None:
    """Milliseconds a decode dispatch the loop thread spent in ``phases``
    together; None for an engine without the keys."""
    parts = [counters.delta_ratio(obs, f"phase_{p}_s", "decode_dispatches")
             for p in phases]
    return None if None in parts else 1e3 * sum(parts)
