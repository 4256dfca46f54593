"""The longest time between two consecutive landings while work stayed
outstanding throughout, in milliseconds: the upper edge of the highest
bucket of ``landing_gap_hist`` (cumulative log2 buckets, ``GET
/get_server_info``) that gained a count between the window's first and last
sample. About a program's length for a sound run; thousands for one in
which every thread stood still (over 2 s the engine counts it in ``stalls``
and logs one record). Says the gaps and the stalls on standard error. None
for an engine without the histogram or a window without a gap. Layer:
CBEngine loop. Moves: rollout_tok_s."""

from benchmark.lib import loghist, notes


def read(obs):
    counts = loghist.gained(obs, "landing_gap_hist")
    if not counts:
        return None
    xs = [s["stalls"] for s in obs["server_info"] if "stalls" in s]
    notes.say(obs, f"landing_gap_max_ms: {sum(counts.values())} gaps, "
              f"stalls {xs[-1] - xs[0]} in the window, {xs[-1]} since the "
              f"start")
    return 1e3 * loghist.upper_edge(max(counts))
