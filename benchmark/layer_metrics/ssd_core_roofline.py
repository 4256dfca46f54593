"""Share of the HBM roofline the scope ``ssd_core`` reaches in a decode
step: a live row's float32 state read and written once a Mamba-2 layer
(``costs_nemotron_h.ssd_core_bytes`` of the rows the program counted on the
device, ``ssd_state_rows``: 2 x 2 MiB a row and layer) over the chip's
published bandwidth (the FLOPs, 4 a state element, are far under the
ridge), divided by ``ssd_core_ms``. None without the counter, the family's
keys or a trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_nemotron_h as costs
from benchmark.lib import harness


def read(obs):
    if obs["peaks"] is None:
        return None
    rows = costs.counted_per_step(obs, "ssd_state_rows")
    core_ms = harness.load_reader("ssd_core_ms")(obs)
    if rows is None or core_ms is None:
        return None
    c = obs["config"]["config"]
    least_s = costs.least_seconds(obs["peaks"], costs.ssd_core_bytes(c, rows),
                                  costs.ssd_core_flops(c, rows))
    return 100.0 * least_s / (core_ms / 1e3)
