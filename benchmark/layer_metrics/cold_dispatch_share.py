"""Share of the window's decode dispatches that the engine issued with
NOTHING outstanding (the device had run dry before them): delta
``decode_dispatches_cold`` over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample, as a percentage. Both move at
the dispatch. About 0 for an engine that keeps its run-ahead pipeline fed;
about 100 for one whose blocked admission drains the pipeline before every
dispatch. None for an engine without the counter. Layer: CBEngine loop.
Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    r = counters.delta_ratio(obs, "decode_dispatches_cold",
                             "decode_dispatches")
    return None if r is None else 100.0 * r
