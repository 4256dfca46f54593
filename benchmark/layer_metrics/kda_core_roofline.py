"""Share of the HBM roofline the scope ``kda_core`` reaches in a decode
step: the state rows really updated (the program's ``kda_state_rows`` a
step: live rows summed over the KDA layers), each read once and written
once in float32 (``costs_hybrid.kda_core_bytes``: 4.2 MB a row a layer at
Ling's sizes), over the chip's published bandwidth, divided by
``kda_core_ms``. Bound by bytes: 4 FLOPs a state byte. Rows without a
request are not counted, so it reads low, never high. Layer: forward pass
and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_hybrid, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    rows = costs_hybrid.state_rows_per_step(obs)
    core_ms = harness.load_reader("kda_core_ms")(obs)
    if rows is None or core_ms is None:
        return None
    least_s = costs_hybrid.kda_core_bytes(obs["config"]["config"], rows) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
