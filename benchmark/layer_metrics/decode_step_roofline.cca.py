"""Share of the HBM roofline one decode step of a model of CCA layers
reaches: the least bytes the step needs (``costs_cca.decode_step_bytes``:
mixer, router and head weights once, the experts that at least one row
chose once each, the K/V pair of the contexts at the traced part's middle,
the live rows' tails read and written) over the chip's published
bandwidth, divided by ``decode_step_ms``. None without the engine's
``moe_experts_hit`` and ``cca_tail_rows``, a CCA key or a trace. Layer:
forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_cca, costs_hybrid, costs_moe, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_cca.is_cca(c):
        return None
    kv_mid = costs_hybrid.kv_tokens_mid(obs)
    if kv_mid is None:
        return None
    hit = costs_moe.experts_hit_per_step(obs)
    rows = costs_cca.tail_rows_per_step(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if hit is None or rows is None or step_ms is None:
        return None
    least_s = costs_cca.decode_step_bytes(c, hit, rows, kv_mid) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
