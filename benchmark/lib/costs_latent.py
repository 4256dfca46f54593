"""Parameters, bytes and FLOPs of DeepSeek-V3's decoder (latent attention
with a query latent in EVERY layer, a routed MLP behind leading dense
layers), computed from a configuration's sizes: the published keys of
``benchmark/configs/dots.vlm1.json``. Beside ``costs.py`` (dense GQA),
``costs_moe.py`` and ``costs_hybrid.py`` and kept here for the same
reason: the sizes and the arithmetic are the benchmark's own, so a change
to the program cannot move a roofline share.

Bytes and FLOPs are the least a decode step needs: the logical values,
not the chip's tiles (the program pads a latent row of 576 values to 640
lanes and multiplies all 640 twice, which a share computed here reads as
work it did not need: low, never high). At 128 heads the absorbed
attention makes ``2 * H * (2 * rank + rope)`` = 278,528 FLOPs for a row of
1,152 B, 242 a byte, where v5e's ridge is 240: which of the two roofs
binds is computed, not assumed (``mla_core_least``). With no latent key in
the configuration (a CPU rehearsal runs ``configs/rehearsal.json``'s tiny
dense model under this cell's plane and readers) the page arithmetic is
GQA's and the readers find nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_hybrid, costs_moe


def is_latent(c: dict) -> bool:
    """Latent attention in every layer: a latent rank and no period of
    layer kinds."""
    return bool(c.get("kv_lora_rank")) and not c.get("layer_group_size")


def plan(c: dict) -> list[str]:
    """The MLP of each layer that is run: the first
    ``first_k_dense_replace`` KEPT layers dense."""
    dense = int(c.get("first_k_dense_replace") or 0)
    return ["dense" if at < dense else "moe"
            for at in range(int(c["num_hidden_layers"]))]


def mla_params(c: dict) -> int:
    """One MLA mixer: the query latent's two matrices and its norm,
    ``wkv_a``, the latent's norm, ``wkv_b``, ``wo``."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    q = c["q_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * q + q + q * h * (nope + rope) + d * (r + rope) + r
            + r * h * (nope + v) + h * v * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_width(c: dict) -> int:
    """The router keeps its published width whatever is held here."""
    return int((c.get("published") or {}).get("n_routed_experts")
               or c["n_routed_experts"])


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c)


def shared_params(c: dict) -> int:
    return (3 * c["hidden_size"] * c["moe_intermediate_size"]
            * int(c.get("n_shared_experts") or 0))


def layer_params(c: dict, mlp: str) -> int:
    """One layer as this chip holds it (``n_routed_experts`` experts
    here), with its two norms."""
    d = c["hidden_size"]
    n = mla_params(c) + 2 * d
    if mlp == "dense":
        return n + 3 * d * c["intermediate_size"]
    return (n + c["n_routed_experts"] * expert_params(c) + shared_params(c)
            + router_params(c) + router_width(c))


def vocab_params(c: dict) -> int:
    """Embedding and untied head over the rows held here."""
    tied = c.get("tie_word_embeddings", False)
    return (1 if tied else 2) * c["vocab_size"] * c["hidden_size"]


def weight_params(c: dict) -> int:
    return (sum(layer_params(c, mlp) for mlp in plan(c)) + vocab_params(c)
            + c["hidden_size"])


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: one latent row (``kv_lora_rank`` +
    ``qk_rope_head_dim`` values) in each layer; a GQA model's K and V
    where the configuration has no latent key."""
    if not is_latent(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * dtype_bytes)


def mla_core_bytes(c: dict, kv_tokens_read: float,
                   dtype_bytes: int = 2) -> float:
    """Least traffic of the absorbed attention: the latent rows of every
    context, once, in each layer; the new row's write and the queries are
    a context's 1/length of it and left out."""
    return kv_tokens_read * paged_bytes_per_token(c, dtype_bytes)


def mla_core_flops(c: dict, kv_tokens_read: float) -> float:
    """Least FLOPs of the absorbed attention: every head's scores over a
    row's ``rank + rope`` values and its values over ``rank``, two FLOPs a
    product, in each layer."""
    per_row = 2 * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])
    return kv_tokens_read * per_row * c["num_hidden_layers"]


def mla_core_least(c: dict, kv_tokens_read: float, peaks: dict
                   ) -> tuple[float, str]:
    """(least seconds of the scope ``mla_core``, which roof gives them:
    ``bytes`` or ``flops``)."""
    by_bytes = mla_core_bytes(c, kv_tokens_read) / peaks["bytes"]
    by_flops = mla_core_flops(c, kv_tokens_read) / peaks["flops"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops,
                                                             "flops")


def dense_params(c: dict) -> int:
    """Parameters every decode step multiplies with whatever the routing:
    mixers, routers, shared experts, dense MLPs and the output head (the
    embedding is gathered, not multiplied)."""
    d = c["hidden_size"]
    total = c["vocab_size"] * d
    for mlp in plan(c):
        total += mla_params(c)
        total += (3 * d * c["intermediate_size"] if mlp == "dense"
                  else router_params(c) + shared_params(c))
    return total


def decode_step_least(c: dict, experts_hit: float, kv_tokens_read: float,
                      peaks: dict, dtype_bytes: int = 2) -> float:
    """Least seconds of one decode step: the dense weights once and the
    held experts that a row chose once each (summed over the layers) at
    the HBM's rate, and the attention at whichever of its two roofs is
    the slower."""
    weights = (dense_params(c) * dtype_bytes
               + costs_moe.experts_bytes(c, experts_hit, dtype_bytes))
    return (weights / peaks["bytes"]
            + mla_core_least(c, kv_tokens_read, peaks)[0])


# tokens of context the traced part's middle step attends to, from the
# client's count, as every ``decode_step_roofline`` takes them
kv_tokens_mid = costs_hybrid.kv_tokens_mid


def rows_read_per_step(obs: dict) -> float | None:
    """Latent rows a decode step attended in ONE layer, as the program
    counted them on the device: delta ``mla_rows_read`` over delta
    ``decode_steps_done`` of the window's ``server_info`` samples, over
    the layers. None without the counter."""
    from benchmark.lib import counters

    rows = counters.delta_ratio(obs, "mla_rows_read", "decode_steps_done")
    c = obs["config"]["config"]
    if rows is None or not is_latent(c):
        return None
    return rows / c["num_hidden_layers"]


# how far the program's count of rows may lie from the client's
ROWS_AGREE = 0.02


def rows_agree(obs: dict) -> dict | None:
    """The program's rows a step (over the whole window: its middle)
    beside the client's tokens of context at the window's middle, and
    whether they agree to ``ROWS_AGREE``: the client's count decides the
    shares, and a run whose two counts part says so in ``checks``."""
    mine = rows_read_per_step(obs)
    if mine is None or "kv_tokens_at_end" not in obs:
        return None
    client = obs["kv_tokens_at_end"] - obs["tokens_in_window"] / 2.0
    return {"program_rows_a_step": mine, "client_tokens_mid_window": client,
            "agree": bool(abs(mine - client) <= ROWS_AGREE * client)}
