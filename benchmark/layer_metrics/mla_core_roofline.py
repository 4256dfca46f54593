"""Share of the HBM roofline the scope ``mla_core`` reaches in a decode
step: the latent rows of every context (taken at the traced part's middle,
as ``decode_step_roofline`` takes the keys and values), once, in each MLA
layer (``costs_hybrid.mla_core_bytes``: 1,152 B a token at Ling's sizes),
over the chip's published bandwidth, divided by ``mla_core_ms``. The
pool's rows are padded to whole lanes and the new row's write is left
out, so it reads low, never high. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_hybrid, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_hybrid.is_hybrid(c):
        return None
    kv_mid = costs_hybrid.kv_tokens_mid(obs)
    core_ms = harness.load_reader("mla_core_ms")(obs)
    if kv_mid is None or core_ms is None:
        return None
    least_s = costs_hybrid.mla_core_bytes(c, kv_mid) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
