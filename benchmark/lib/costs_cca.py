"""Parameters and bytes of ZAYA1's decoder (compressed convolutional
attention and a top-1 routed MLP behind a router MLP in every layer, a
tied head), computed from a configuration's sizes: the published keys of
``benchmark/configs/zaya1-8b.json``. Beside ``costs.py`` (dense GQA),
``costs_moe.py``, ``costs_hybrid.py`` and ``costs_latent.py`` and kept
here for the same reason: the sizes and the arithmetic are the benchmark's
own, so a change to the program cannot move a roofline share
(``benchmark/tests/test_cca_metrics.py`` holds them to the tree the
program builds).

Bytes are the least a decode step needs: every weight outside the experts
once, the experts that at least one row chose once each, the K/V pair of
every context once (``costs.kv_bytes_per_token``: the cache IS a GQA
cache, 2 heads of 128 a layer), the live rows' tails read and written,
the tied head once. Bound by bytes throughout: 129 rows make at most 129
FLOPs a weight byte of a matmul every row shares, 8 rows an expert make 8,
a decode query makes 4 a K/V byte at 8 heads over 2, where v5e's ridge is
240. With no CCA key in the configuration (a CPU rehearsal runs
``configs/rehearsal.json``'s tiny dense model under this cell's plane and
readers) the page arithmetic is GQA's and the readers find nothing to
read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_moe


def is_cca(c: dict) -> bool:
    return bool(c.get("cca_time0"))


def mixed_channels(c: dict) -> int:
    """The channels the two convolutions mix: the query and key latents."""
    return ((c["num_attention_heads"] + c["num_key_value_heads"])
            * costs.head_dim(c))


def value_half(c: dict) -> int:
    return c["num_key_value_heads"] * costs.head_dim(c) // 2


def cca_params(c: dict) -> int:
    """One CCA mixer: ``W_in`` (query latent, key latent, two value
    halves), the depthwise taps, the head-wise ``d x d`` taps, one
    temperature a K/V head, ``Wo``."""
    d, hd = c["hidden_size"], costs.head_dim(c)
    mixed = mixed_channels(c)
    heads = c["num_attention_heads"] + c["num_key_value_heads"]
    return (d * (mixed + 2 * value_half(c)) + c["cca_time0"] * mixed
            + c["cca_time1"] * heads * hd * hd + c["num_key_value_heads"]
            + c["num_attention_heads"] * hd * d)


def router_params(c: dict) -> int:
    """The router MLP: the down projection to the latent, its norm, the
    carry's factor, two square matrices, the output over the experts and
    the balancing bias."""
    d, r, e = c["hidden_size"], c["router_hidden_size"], c["num_experts"]
    return d * r + r + 1 + 2 * r * r + r * e + e


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: dict) -> int:
    """One layer: the mixer, the router, every expert, two norms and the
    four residual vectors of each sublayer."""
    return (cca_params(c) + router_params(c)
            + c["num_experts"] * expert_params(c) + 10 * c["hidden_size"])


def vocab_params(c: dict) -> int:
    """Embedding and head: one array where they are tied."""
    tied = c.get("tie_word_embeddings", False)
    return (1 if tied else 2) * c["vocab_size"] * c["hidden_size"]


def weight_params(c: dict) -> int:
    return (c["num_hidden_layers"] * layer_params(c) + vocab_params(c)
            + c["hidden_size"])


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: a K/V pair in every layer."""
    return costs.kv_bytes_per_token(c, dtype_bytes)


def slot_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """What a slot keeps beside the pages, all layers: the last
    ``cca_time0 - 1`` rows of the latents, the last ``cca_time1 - 1`` of
    the first convolution's output, the last token's second value half."""
    if not is_cca(c):
        return 0
    per_layer = ((c["cca_time0"] - 1 + c["cca_time1"] - 1)
                 * mixed_channels(c) + value_half(c))
    return c["num_hidden_layers"] * per_layer * dtype_bytes


def dense_params(c: dict) -> int:
    """Parameters every decode step multiplies with whatever the routing:
    mixers, routers and the output head (the embedding is gathered)."""
    return (c["num_hidden_layers"] * (cca_params(c) + router_params(c))
            + c["vocab_size"] * c["hidden_size"])


def tails_bytes(c: dict, rows_x_layers: float, dtype_bytes: int = 2) -> float:
    """The live rows' tails, read and written once a layer."""
    return 2.0 * rows_x_layers * slot_bytes(c, dtype_bytes) \
        / c["num_hidden_layers"]


def decode_step_bytes(c: dict, experts_hit: float, rows_x_layers: float,
                      kv_tokens_read: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the dense weights once, the
    experts that a row chose once each (summed over the layers), the K/V
    pair of every context, the live rows' tails read and written."""
    return (dense_params(c) * dtype_bytes
            + costs_moe.experts_bytes(c, experts_hit, dtype_bytes)
            + kv_tokens_read * paged_bytes_per_token(c, dtype_bytes)
            + tails_bytes(c, rows_x_layers, dtype_bytes))


def tail_rows_per_step(obs: dict) -> float | None:
    """Live rows summed over the CCA layers, a decode step: delta
    ``cca_tail_rows`` over delta ``decode_steps_done`` of the window's
    ``server_info`` samples. None without the counter (a program from
    before it, a model without CCA layers)."""
    from benchmark.lib import counters

    rows = counters.delta_ratio(obs, "cca_tail_rows", "decode_steps_done")
    c = obs["config"]["config"]
    if rows is None or not is_cca(c):
        return None
    most = (obs["mix"]["engine"]["max_slots"] + 1) * c["num_hidden_layers"]
    if rows > most:
        raise ValueError(f"cca_tail_rows counts {rows:.1f} rows a step; "
                         f"the engine has {most}")
    return rows
