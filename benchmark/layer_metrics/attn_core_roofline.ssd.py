"""Share of its roofline the scope ``attn_core`` reaches in a decode step
of a model with a few attention layers among layers of other kinds: the
keys and values of every key the step's live rows attend over, summed over
the attention layers (``costs_nemotron_h.keys_traced``: the client's
tokens of context at the TRACED part's middle an attention layer, as
``attn_core_roofline.mixed`` takes them; ``attn_core_bytes``: 1,024 B a key
and layer), the larger of those bytes over the chip's published bandwidth
and the scores' and values' FLOPs over its published peak, divided by
``attn_core_ms``, which is the traced part's too. Whether the program's
own count of keys over the whole window (``paged_rows_read``) agrees with
the client's at the window's middle to 2% goes into ``checks``
(``paged_rows``). The token's own K/V write is left out. None without the
family's keys or a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_nemotron_h as costs
from benchmark.lib import harness


def read(obs):
    if obs["peaks"] is None:
        return None
    keys = costs.keys_traced(obs)
    core_ms = harness.load_reader("attn_core_ms")(obs)
    if keys is None or core_ms is None:
        return None
    agree = costs.rows_agree(obs)
    if agree is not None:
        obs["checks"]["paged_rows"] = agree
    c = obs["config"]["config"]
    least_s = costs.least_seconds(obs["peaks"], costs.attn_core_bytes(c, keys),
                                  costs.attn_core_flops(c, keys))
    return 100.0 * least_s / (core_ms / 1e3)
