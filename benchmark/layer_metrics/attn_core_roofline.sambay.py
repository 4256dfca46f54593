"""Share of the HBM roofline the scope ``attn_core`` reaches in a decode
step of a model with ONE paged K/V layer that several layers read: the
shared pool's keys and values of every context once for each layer that
attends over it (``costs_sambay.attn_core_bytes``: 5,120 B a token, eight
readers at Phi-4-mini-flash's depth; the contexts at the traced part's
middle by the client's count) over the chip's published bandwidth, divided
by ``attn_core_ms``. Whether the program's own count of keys
(``shared_kv_rows_read``) agrees with the client's to 2% goes into
``checks`` (``shared_kv_rows``). None without the family's keys or a
trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_sambay, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_sambay.is_sambay(c):
        return None
    kv_mid = costs_sambay.kv_tokens_mid(obs)
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if kv_mid is None or core_ms is None:
        return None
    agree = costs_sambay.rows_agree(obs)
    if agree is not None:
        obs["checks"]["shared_kv_rows"] = agree
    least_s = costs_sambay.attn_core_bytes(c, kv_mid) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
