"""Share of the HBM roofline the scope ``swa_core`` reaches in a decode
step of a model whose window layers are rope'd GQA over rings: the keys
and values of the rings that the step read
(``costs_mixed.swa_core_bytes`` of the keys the program counted on the
device, ``window_rows_read``: at most ``sliding_window`` a row a window
layer, 4,096 B each) over the chip's published bandwidth, divided by
``swa_core_ms``. Bound by bytes, as ``attn_core`` is. None without the
counter, the family's keys or a trace. Layer: forward pass and kernels.
Moves: rollout_tok_s."""

from benchmark.lib import costs_mixed, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    rows = costs_mixed.counted_per_step(obs, "window_rows_read")
    core_ms = harness.load_reader("swa_core_ms")(obs)
    if rows is None or core_ms is None:
        return None
    least_s = costs_mixed.swa_core_bytes(obs["config"]["config"], rows) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
