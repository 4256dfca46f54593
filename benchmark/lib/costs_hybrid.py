"""Parameters and bytes of the Ling-3.0 hybrid decoder (KDA layers, every
``layer_group_size``-th an MLA layer, a routed MLP behind leading dense
layers), computed from a configuration's sizes: the published keys of
``benchmark/configs/ling-3.0-flash.json``. Beside ``costs.py`` (dense
GQA) and ``costs_moe.py`` and kept here for the same reason: the sizes
and the arithmetic are the benchmark's own, so a change to the program
cannot move a roofline share.

Bytes are the least a decode step must move: the logical values, not the
chip's tiles (the program pads a latent row of 576 values to 640 lanes,
which a share computed here reads as bytes it did not need: low, never
high). With no latent or state key in the configuration (a CPU rehearsal
runs ``configs/rehearsal.json``'s tiny dense model under this cell's
plane and readers) the page arithmetic is GQA's and the readers find
nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_moe

STATE_BYTES = 4      # the recurrent state is float32


def is_hybrid(c: dict) -> bool:
    return bool(c.get("layer_group_size"))


def plan(c: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of each layer that is run: kinds from the published
    index (``kept_layers``), the first ``first_k_dense_replace`` kept
    layers dense."""
    n = int(c["num_hidden_layers"])
    kept = [int(i) for i in c.get("kept_layers") or range(n)]
    size = int(c["layer_group_size"])
    dense = int(c.get("first_k_dense_replace") or 0)
    return [("mla" if (i + 1) % size == 0 else "kda",
             "dense" if at < dense else "moe") for at, i in enumerate(kept)]


def count(c: dict, kind: str) -> int:
    return sum(kind in layer for layer in plan(c))


def kda_params(c: dict) -> int:
    """One KDA mixer: q, k, v, o, the decay projection and the output gate
    at full rank (``no_kda_lora``), beta, the three convolutions and the
    per-head and per-channel vectors."""
    d, h, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    k = c["short_conv_kernel_size"]
    return 6 * d * h * hd + d * h + 3 * k * h * hd + h + h * hd + hd


def mla_params(c: dict) -> int:
    """One MLA mixer: ``wq``, ``wkv_a``, ``wkv_b``, ``wo``, the head gate
    and the latent's norm."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v)
            + h * v * d + d * h + r)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    """The router keeps its published width whatever is held here."""
    return c["hidden_size"] * router_width(c)


def router_width(c: dict) -> int:
    return int((c.get("published") or {}).get("num_experts")
               or c["num_experts"])


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def layer_params(c: dict, mixer: str, mlp: str) -> int:
    """One layer as this chip holds it (``num_experts`` experts here),
    with its two norms."""
    d = c["hidden_size"]
    n = (kda_params(c) if mixer == "kda" else mla_params(c)) + 2 * d
    if mlp == "dense":
        return n + 3 * d * c["intermediate_size"]
    return (n + c["num_experts"] * expert_params(c) + shared_params(c)
            + router_params(c) + router_width(c))


def vocab_params(c: dict) -> int:
    """Embedding and untied head over the rows held here."""
    tied = c.get("tie_word_embeddings", False)
    return (1 if tied else 2) * c["vocab_size"] * c["hidden_size"]


def weight_params(c: dict) -> int:
    return (sum(layer_params(c, *layer) for layer in plan(c))
            + vocab_params(c) + c["hidden_size"])


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: one latent row (``kv_lora_rank`` +
    ``qk_rope_head_dim`` values) in each MLA layer; a GQA model's K and V
    where the configuration has no latent key."""
    if not is_hybrid(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    return (count(c, "mla") * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * dtype_bytes)


def state_bytes(c: dict) -> int:
    """One row's recurrent state in one KDA layer: H x D x D float32."""
    return (c["num_attention_heads"] * c["head_dim"] * c["head_dim"]
            * STATE_BYTES)


def slot_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """What a slot keeps outside pages, all KDA layers: the state and the
    three convolutions' tails."""
    conv = ((c["short_conv_kernel_size"] - 1) * 3
            * c["num_attention_heads"] * c["head_dim"] * dtype_bytes)
    return count(c, "kda") * (state_bytes(c) + conv)


def kda_core_bytes(c: dict, rows_x_layers: float) -> float:
    """Least traffic of the state update: each live row's state read once
    and written once, in each KDA layer (``rows_x_layers``: live rows
    summed over the KDA layers)."""
    return rows_x_layers * 2 * state_bytes(c)


def mla_core_bytes(c: dict, kv_tokens_read: float,
                   dtype_bytes: int = 2) -> float:
    """Least traffic of the absorbed attention: the latent rows of every
    context, once, in each MLA layer; the new row's write and the queries
    are a context's 1/length of it and left out."""
    return kv_tokens_read * paged_bytes_per_token(c, dtype_bytes)


def dense_params(c: dict) -> int:
    """Parameters every decode step multiplies with whatever the routing:
    mixers, routers, shared experts, dense MLPs and the output head (the
    embedding is gathered, not multiplied)."""
    d = c["hidden_size"]
    total = c["vocab_size"] * d
    for mixer, mlp in plan(c):
        total += kda_params(c) if mixer == "kda" else mla_params(c)
        total += (3 * d * c["intermediate_size"] if mlp == "dense"
                  else router_params(c) + shared_params(c))
    return total


def decode_step_bytes(c: dict, experts_hit: float, rows_x_layers: float,
                      kv_tokens_read: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the dense weights once, the
    held experts that a row chose once each (summed over the layers), the
    live rows' states read and written, the latent rows of every
    context."""
    return (dense_params(c) * dtype_bytes
            + costs_moe.experts_bytes(c, experts_hit, dtype_bytes)
            + kda_core_bytes(c, rows_x_layers)
            + mla_core_bytes(c, kv_tokens_read, dtype_bytes))


def state_rows_per_step(obs: dict) -> float | None:
    """Live rows summed over the KDA layers, a decode step: delta
    ``kda_state_rows`` over delta ``decode_steps_done`` of the window's
    ``server_info`` samples. None without the counter."""
    from benchmark.lib import counters

    rows = counters.delta_ratio(obs, "kda_state_rows", "decode_steps_done")
    c = obs["config"]["config"]
    if rows is not None and is_hybrid(c):
        most = (obs["mix"]["engine"]["max_slots"] + 1) * count(c, "kda")
        if rows > most:
            raise ValueError(f"kda_state_rows counts {rows:.1f} rows a "
                             f"step; the engine has {most}")
    return rows


def kv_tokens_mid(obs: dict) -> float | None:
    """Tokens of context the traced part's middle step attends to, as
    ``decode_step_roofline`` takes them."""
    reduced = obs.get("trace")
    if not reduced or "kv_tokens_at_end" not in obs:
        return None
    t0, t1 = obs["window"]
    traced = reduced["window_s"] / (t1 - t0)
    return (obs["kv_tokens_at_end"]
            - obs["tokens_in_window"] * (1.0 - traced / 2.0))
