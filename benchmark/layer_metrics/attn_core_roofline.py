"""Share of the HBM roofline the scope ``attn_core`` reaches in a decode
step: the keys and values of every context once
(``costs_kernels.attn_core_decode_bytes``, the contexts taken at the traced
part's middle as ``decode_step_roofline`` takes them) over the chip's
published bandwidth, divided by ``attn_core_ms``. Bound by bytes: a decode
query does two FLOPs a KV element. Only for traffic without shared
prefixes. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_kernels, harness


def read(obs):
    reduced = obs.get("trace")
    if obs["peaks"] is None or not reduced or "kv_tokens_at_end" not in obs:
        return None
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if core_ms is None:
        return None
    t0, t1 = obs["window"]
    traced = reduced["window_s"] / (t1 - t0)
    kv_mid = (obs["kv_tokens_at_end"]
              - obs["tokens_in_window"] * (1.0 - traced / 2.0))
    least_s = costs_kernels.attn_core_decode_bytes(
        obs["config"]["config"], kv_mid) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
