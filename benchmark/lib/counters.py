"""Rates from the program's cumulative counters: ``GET /get_server_info``
carries them flat and monotone (``polyrl_tpu/obs/engine_profile.py``,
``rollout/server.py``), the harness samples it through the window, and a
rate is the first sample's distance to the last: no profiler session, the
whole window, and the sampling's own timing cancels out."""

from __future__ import annotations


def delta_ratio(obs: dict, num: str, den: str) -> float | None:
    """(last - first of ``num``) / (last - first of ``den``) over the
    window's ``server_info`` samples that carry both; None when fewer than
    two do (an older engine) or ``den`` did not move."""
    xs = [s for s in obs.get("server_info", []) if num in s and den in s]
    if len(xs) < 2:
        return None
    d_den = xs[-1][den] - xs[0][den]
    if d_den <= 0:
        return None
    return (xs[-1][num] - xs[0][num]) / d_den
