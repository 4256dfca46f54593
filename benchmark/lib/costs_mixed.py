"""Parameters and bytes of Laguna's decoder (``laguna``: rope'd softmax
GQA in every layer, full layers whose K/V lie in pages and window layers
whose last ``sliding_window`` keys lie in a ring of the slot's, a head
count a kind, a gate a head, a dense MLP first and then routed experts
beside a shared one, an untied head), computed from a configuration's
sizes: the published keys of ``benchmark/configs/laguna-xs.2.json``.
Beside ``costs.py`` (dense GQA), ``costs_moe.py``, ``costs_hybrid.py``,
``costs_latent.py``, ``costs_cca.py`` and ``costs_sambay.py`` and kept here
for the same reason: the sizes and the arithmetic are the benchmark's own,
so a change to the program cannot move a roofline share
(``benchmark/tests/test_mixed_metrics.py`` holds them to the tree the
program builds).

Bytes are the least a decode step needs: every matmul weight outside the
experts once, an expert that a row chose once (``moe_experts_hit``), the
full layers' keys and values of every context once a full layer, a window
layer's ring up to the window, the head once (the embedding is gathered: a
row a token). Bound by bytes throughout: 65 rows make at most 65 FLOPs a
weight byte, a decode query row makes 2 FLOPs a K/V element and a K/V head
is shared by 6 or 8 rows, where v5e's ridge is 240. With no family key in
the configuration (a CPU rehearsal runs ``configs/rehearsal.json``'s tiny
dense model under this cell's plane and readers) the page arithmetic is
GQA's and the readers find nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_hybrid, costs_moe

KIND_OF = {"full_attention": "full", "sliding_attention": "window"}


def is_mixed(c: dict) -> bool:
    return bool(c.get("layer_types"))


def kinds(c: dict) -> list[str]:
    """``full`` or ``window`` a layer that is run."""
    return [KIND_OF[t] for t in c["layer_types"]]


def count(c: dict, kind: str) -> int:
    return kinds(c).count(kind)


def sparse_layers(c: dict) -> int:
    return list(c["mlp_layer_types"]).count("sparse")


def mixer_params(c: dict, heads: int) -> int:
    """One attention mixer of ``heads`` query heads: q, k, v, the gate a
    head, o."""
    d, hd, hkv = c["hidden_size"], costs.head_dim(c), c["num_key_value_heads"]
    gate = d * heads if c.get("gating") else 0
    return d * heads * hd + 2 * d * hkv * hd + gate + heads * hd * d


def mixers_params(c: dict) -> int:
    return sum(mixer_params(c, h) for h in c["num_attention_heads_per_layer"])


def dense_mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c.get("shared_expert_intermediate_size", 0)


def router_width(c: dict) -> int:
    """The router keeps its published width whatever is held here."""
    return int((c.get("published") or {}).get("num_experts")
               or c["num_experts"])


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c)


def outside_experts_params(c: dict) -> int:
    """The layers' parameters that every decode step multiplies with
    whatever the routing: the mixers, the dense MLPs, each sparse layer's
    router and shared expert. Norms (two vectors a layer) are left out."""
    n_sparse = sparse_layers(c)
    return (mixers_params(c)
            + (c["num_hidden_layers"] - n_sparse) * dense_mlp_params(c)
            + n_sparse * (router_params(c) + shared_params(c)))


def weight_params(c: dict, experts: int | None = None) -> int:
    """The whole tree but its norms: the layers with ``experts`` routed
    experts a sparse layer (those held here by default), the embedding and
    the untied head."""
    held = c["num_experts"] if experts is None else experts
    tied = c.get("tie_word_embeddings", False)
    return (outside_experts_params(c)
            + sparse_layers(c) * held * expert_params(c)
            + (1 if tied else 2) * c["vocab_size"] * c["hidden_size"])


def norm_params(c: dict) -> int:
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def published(c: dict) -> dict:
    """The configuration with the keys its ``published`` group restores:
    the uncut model."""
    return {**c, **(c.get("published") or {})}


def kv_bytes_a_layer(c: dict, dtype_bytes: int = 2) -> int:
    """One layer's K and V of one token."""
    return 2 * c["num_key_value_heads"] * costs.head_dim(c) * dtype_bytes


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: the FULL layers' K and V."""
    if not is_mixed(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    return count(c, "full") * kv_bytes_a_layer(c, dtype_bytes)


def ring_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """The window layers' rings of one slot: ``sliding_window`` keys and
    values a layer, whatever the sequence's length."""
    if not is_mixed(c):
        return 0
    return (count(c, "window") * c["sliding_window"]
            * kv_bytes_a_layer(c, dtype_bytes))


def attn_core_bytes(c: dict, kv_tokens_read: float,
                    dtype_bytes: int = 2) -> float:
    """The keys and values of every context, once a full layer."""
    return kv_tokens_read * paged_bytes_per_token(c, dtype_bytes)


def swa_core_bytes(c: dict, window_rows: float, dtype_bytes: int = 2) -> float:
    """``window_rows``: keys of the rings read, summed over the window
    layers."""
    return window_rows * kv_bytes_a_layer(c, dtype_bytes)


def dense_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Every weight but the embedding table and the routed experts."""
    return (outside_experts_params(c)
            + c["vocab_size"] * c["hidden_size"]) * dtype_bytes


def decode_step_bytes(c: dict, experts_hit: float, kv_tokens_read: float,
                      window_rows: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights but the embedding
    and the experts once, the experts hit (summed over the layers) once
    each, the full layers' pages of every context, the rings' keys."""
    return (dense_bytes(c, dtype_bytes)
            + costs_moe.experts_bytes(c, experts_hit, dtype_bytes)
            + attn_core_bytes(c, kv_tokens_read, dtype_bytes)
            + swa_core_bytes(c, window_rows, dtype_bytes))


# tokens of context the traced part's middle step attends to, from the
# client's count, as every ``decode_step_roofline`` takes them
kv_tokens_mid = costs_hybrid.kv_tokens_mid


def counted_per_step(obs: dict, key: str) -> float | None:
    """What the program counted on the device a decode step: delta ``key``
    (``paged_rows_read``, ``window_rows_read``) over delta
    ``decode_steps_done`` of the window's ``server_info`` samples. None
    without the counter (a program from before it) or the family's keys."""
    from benchmark.lib import counters

    if not is_mixed(obs["config"]["config"]):
        return None
    return counters.delta_ratio(obs, key, "decode_steps_done")


# how far the program's count of rows may lie from the client's
ROWS_AGREE = 0.02


def rows_agree(obs: dict) -> dict | None:
    """The program's keys of the pages a step and a full layer (over the
    whole window: its middle) beside the client's tokens of context at the
    window's middle, and whether they agree to ``ROWS_AGREE``: the
    client's count decides the shares, and a run whose two counts part
    says so in ``checks``."""
    mine = counted_per_step(obs, "paged_rows_read")
    if mine is None or "kv_tokens_at_end" not in obs:
        return None
    mine /= count(obs["config"]["config"], "full")
    client = obs["kv_tokens_at_end"] - obs["tokens_in_window"] / 2.0
    return {"program_rows_a_step": mine, "client_tokens_mid_window": client,
            "agree": bool(abs(mine - client) <= ROWS_AGREE * client)}
