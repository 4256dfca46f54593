"""Tokens a second at the engine's own landings: delta ``row_steps_done``
over delta ``device_busy_at_s`` (the engine's clock at the landing up to
which the rows are booked) of ``GET /get_server_info``, first to last
sample. No dispatch quantum, no client, no profiler session: while no row
ends it is what the client's ``rollout_tok_s`` has to read, and the two
part when the client stands behind the engine. Says both on standard error,
and the client's rate between the same two landings (the harness's client
shares the engine's process and so its ``time.monotonic``): where that one
agrees and the window's does not, the two differ by the stretch they cover
(the samples leave out up to half a second at either end), not by what they
count. None for an engine without the counter. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import counters, notes, stats


def _client_rate(obs, t0: float, t1: float) -> float | None:
    """The sum of the requests' rates between their own arrivals at
    ``t0`` and ``t1`` (``stats.edge_rate``); None where no stream of the
    run reaches around both."""
    total = 0.0
    for r in obs.get("requests", []):
        try:
            total += stats.edge_rate(r.arrivals, t0, t1)[0]
        except ValueError:
            continue
    return total or None


def read(obs):
    rate = counters.delta_ratio(obs, "row_steps_done", "device_busy_at_s")
    client = obs.get("end_to_end", {}).get("rollout_tok_s")
    if rate is not None and client:
        at = [s["device_busy_at_s"] for s in obs["server_info"]
              if "row_steps_done" in s and "device_busy_at_s" in s]
        same = _client_rate(obs, at[0], at[-1])
        notes.say(
            obs, f"engine_tok_s {rate:.2f} at the engine's landings over "
            f"{at[-1] - at[0]:.2f} s, rollout_tok_s {client:.2f} at the "
            f"client ({100.0 * (rate / client - 1.0):+.3f}%)"
            + ("" if same is None else
               f"; the client between the same two landings {same:.2f} "
               f"({100.0 * (rate / same - 1.0):+.3f}%)"))
    return rate
