"""Share of its roofline the scope ``attn_core`` reaches in a decode step
of a model with block-sparse attention layers: the keys and values of the
pages the step's rows CHOSE (``costs_sala.attn_core_bytes`` of the pages
the program counted on the device, ``sparse_pages_read``: a page of 64
tokens of one K/V head, 32,768 B; ``topk`` a row, head and layer past
``dense_len``), the larger of those bytes over the chip's published
bandwidth and the scores' and values' FLOPs over its published peak,
divided by ``attn_core_ms``. The token's own K/V write is left out, so the
share reads low by that, never high. None without the counter, the
family's keys or a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_sala, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    pages = costs_sala.counted_per_step(obs, "sparse_pages_read")
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if pages is None or core_ms is None:
        return None
    c = obs["config"]["config"]
    least_s = costs_sala.least_seconds(
        obs["peaks"], costs_sala.attn_core_bytes(c, pages),
        costs_sala.attn_core_flops(c, pages))
    return 100.0 * least_s / (core_ms / 1e3)
