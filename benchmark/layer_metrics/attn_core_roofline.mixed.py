"""Share of the HBM roofline the scope ``attn_core`` reaches in a decode
step of a model whose FULL attention layers alone keep pages: the keys and
values of every context once a full layer
(``costs_mixed.attn_core_bytes``: 4,096 B a token a layer, three full
layers at Laguna-XS.2's cut; the contexts at the traced part's middle by
the client's count) over the chip's published bandwidth, divided by
``attn_core_ms``. Whether the program's own count of keys
(``paged_rows_read``) agrees with the client's to 2% goes into ``checks``
(``paged_rows``). None without the family's keys or a trace. Layer:
forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_mixed, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_mixed.is_mixed(c):
        return None
    kv_mid = costs_mixed.kv_tokens_mid(obs)
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if kv_mid is None or core_ms is None:
        return None
    agree = costs_mixed.rows_agree(obs)
    if agree is not None:
        obs["checks"]["paged_rows"] = agree
    least_s = costs_mixed.attn_core_bytes(c, kv_mid) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (core_ms / 1e3)
