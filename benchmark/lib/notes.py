"""What a reader says beside its number, on standard error (a count of
samples, the client's rate beside the engine's): of a run on the chip
only. A rehearsal's numbers are a CPU's and are printed nowhere; ``run.py``
hands its readers no ``peaks`` then."""

from __future__ import annotations

from benchmark.lib import harness


def say(obs: dict, msg: str) -> None:
    if obs.get("peaks") is not None:
        harness.say(msg)
