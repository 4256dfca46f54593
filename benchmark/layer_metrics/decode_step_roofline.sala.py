"""Share of the HBM roofline one decode step of a model with block-sparse
and linear-attention layers reaches: the least bytes the step needs
(``costs_sala.decode_step_bytes``: every matmul weight and the untied head
once, the pages the rows chose, the pooled keys they were scored against
and the lightning rows, each as the program counted it on the device,
states read and written) over the chip's published bandwidth, divided by
``decode_step_ms``. None without the engine's ``sparse_pages_read``,
``sparse_pooled_scored`` and ``lightning_state_rows``, the family's keys or
a trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_sala, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    pages = costs_sala.counted_per_step(obs, "sparse_pages_read")
    scored = costs_sala.counted_per_step(obs, "sparse_pooled_scored")
    rows = costs_sala.counted_per_step(obs, "lightning_state_rows")
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if None in (pages, scored, rows, step_ms):
        return None
    least_s = costs_sala.decode_step_bytes(
        obs["config"]["config"], pages, scored, rows) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
