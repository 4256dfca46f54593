"""Share of the HBM roofline the scope ``attn_core`` reaches in a decode
step of a looped model: every pass's keys and values of every context once
a layer (``costs_looped.attn_core_bytes``: 8,192 B a token a layer a pass
at Ouro-2.6B's 16 K/V heads of 128; the contexts at the traced part's
middle by the client's count, the passes as the program counted them) over
the chip's published bandwidth, divided by ``attn_core_ms`` (the scope's
own events: the K/V write and the paged attention of all layers and
passes). None without the engine's ``ut_passes``, the family's keys or a
trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_looped, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_looped.is_looped(c):
        return None
    rows = costs_looped.pass_rows_mid(obs)
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if rows is None or core_ms is None:
        return None
    least_s = costs_looped.attn_core_bytes(c, rows) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
