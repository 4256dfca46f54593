"""Share of the HBM roofline one decode step of the KDA / MLA / routed
hybrid reaches: the least bytes the step must move
(``costs_hybrid.decode_step_bytes``: mixer, router, shared-expert, dense
and head weights, the held experts that at least one row chose, every
live row's recurrent state read and written, the latent rows of every
context at the traced part's middle) over the chip's published bandwidth,
divided by ``decode_step_ms``. None without the engine's
``moe_experts_hit`` and ``kda_state_rows``. Layer: forward pass and
kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_hybrid, costs_moe, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_hybrid.is_hybrid(c):
        return None
    kv_mid = costs_hybrid.kv_tokens_mid(obs)
    rows = costs_hybrid.state_rows_per_step(obs)
    if kv_mid is None or rows is None:
        return None
    hit = costs_moe.experts_hit_per_step(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if hit is None or step_ms is None:
        return None
    least_s = costs_hybrid.decode_step_bytes(c, hit, rows, kv_mid) \
        / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
