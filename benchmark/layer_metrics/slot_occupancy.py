"""Mean share of the engine's slots that were active, over the window:
``occupancy`` of ``GET /get_server_info`` (the flight deck's running mean
of active slots over slots at each decode dispatch), sampled twice a
second. Layer: CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import stats


def read(obs):
    xs = [s["occupancy"] for s in obs.get("server_info", [])
          if "occupancy" in s]
    return 100.0 * stats.mean(xs) if xs else None
