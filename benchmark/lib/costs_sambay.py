"""Parameters and bytes of the SambaY decoder (``phi4flash``: Mamba-1
scans, window attention, ONE full-attention layer whose K/V the cross
layers read, gated memory units, differential attention, a tied head),
computed from a configuration's sizes: the published keys of
``benchmark/configs/phi-4-mini-flash-reasoning.json`` and the family's
Mamba sizes (``SSM``, which no published key carries). Beside ``costs.py``
(dense GQA), ``costs_moe.py``, ``costs_hybrid.py``, ``costs_latent.py`` and
``costs_cca.py`` and kept here for the same reason: the sizes and the
arithmetic are the benchmark's own, so a change to the program cannot move
a roofline share (``benchmark/tests/test_sambay_metrics.py`` holds them to
the tree the program builds).

Bytes are the least a decode step needs: every matmul weight once, the one
shared K/V pool once for each layer that attends over it (the full layer
and the cross layers: they have different queries, so each reads every
key), a window layer's ring up to the window, a Mamba layer's state read
and written, the tied head once. Bound by bytes throughout: 129 rows make
at most 129 FLOPs a weight byte, a decode query row makes 2 FLOPs a K/V
element and a K/V pair is shared by 4 rows of 128 (the paired layout's
zero halves double the score FLOPs and move no byte), a state element
takes 5 FLOPs for its 8 bytes, where v5e's ridge is 240. With no family
key in the configuration (a CPU rehearsal runs ``configs/rehearsal.json``'s
tiny dense model under this cell's plane and readers) the page arithmetic
is GQA's and the readers find nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_hybrid

# the family's Mamba-1 sizes (benchmark/configs/phi-4-mini-flash-
# reasoning.json, ``assumed``): inner width over hidden, state size,
# convolution taps, hidden over the rank of dt
SSM = {"expand": 2, "state": 16, "conv": 4, "rank_divisor": 16}
STATE_BYTES = 4     # a scan's state is float32


def is_sambay(c: dict) -> bool:
    return bool(c.get("mb_per_layer"))


def kinds(c: dict) -> list[str]:
    """The mixer of each layer: ``ssm``, ``swa``, ``full``, ``gmu``,
    ``cross`` (the scan at half depth is an ``ssm`` like the others)."""
    n, every = c["num_hidden_layers"], c["mb_per_layer"]
    h = n // 2
    out = []
    for i in range(n):
        scan = i % every == 0
        if i < h:
            out.append("ssm" if scan else "swa")
        elif i < h + 2:
            out.append("ssm" if i == h else "full")
        else:
            out.append("gmu" if scan else "cross")
    return out


def count(c: dict, kind: str) -> int:
    return kinds(c).count(kind)


def inner(c: dict) -> int:
    return SSM["expand"] * c["hidden_size"]


def ssm_params(c: dict) -> int:
    """One Mamba mixer: the input product (xi | z), the depthwise taps and
    their bias, the product to (dt | B | C), dt's product and bias, A, the
    skip, the output product."""
    d, i, n = c["hidden_size"], inner(c), SSM["state"]
    rank = d // SSM["rank_divisor"]
    return (d * 2 * i + SSM["conv"] * i + i + i * (rank + 2 * n)
            + rank * i + i + n * i + i + i * d)


def lambda_params(c: dict) -> int:
    """Four lambda vectors of a head's size and the 2D norm."""
    return 6 * costs.head_dim(c)


def attn_params(c: dict) -> int:
    """A window or full layer: ``W_qkv`` and ``W_o``, each with a bias."""
    d, hd = c["hidden_size"], costs.head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    wide = (hq + 2 * hkv) * hd
    return d * wide + wide + hq * hd * d + d + lambda_params(c)


def cross_params(c: dict) -> int:
    d = c["hidden_size"]
    q = c["num_attention_heads"] * costs.head_dim(c)
    return d * q + q + q * d + d + lambda_params(c)


def gmu_params(c: dict) -> int:
    return 2 * c["hidden_size"] * inner(c)


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


MIXER_PARAMS = {"ssm": ssm_params, "swa": attn_params, "full": attn_params,
                "gmu": gmu_params, "cross": cross_params}


def layers_params(c: dict) -> int:
    """Every layer: its mixer, its MLP and two LayerNorms (weight and
    bias)."""
    return sum(MIXER_PARAMS[k](c) + mlp_params(c) + 4 * c["hidden_size"]
               for k in kinds(c))


def weight_params(c: dict) -> int:
    """The whole tree: the layers, the tied embedding and head, the final
    LayerNorm."""
    tied = c.get("tie_word_embeddings", False)
    return (layers_params(c)
            + (1 if tied else 2) * c["vocab_size"] * c["hidden_size"]
            + 2 * c["hidden_size"])


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: ONE layer's K and V (the full-attention
    layer's), whatever the depth."""
    if not is_sambay(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    return 2 * c["num_key_value_heads"] * costs.head_dim(c) * dtype_bytes


def ring_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """The window layers' rings of one slot: ``sliding_window`` keys and
    values a layer, whatever the sequence's length."""
    return (count(c, "swa") * c["sliding_window"]
            * paged_bytes_per_token(c, dtype_bytes))


def state_bytes_a_layer(c: dict) -> int:
    return SSM["state"] * inner(c) * STATE_BYTES


def tail_bytes_a_layer(c: dict, dtype_bytes: int = 2) -> int:
    return (SSM["conv"] - 1) * inner(c) * dtype_bytes


def state_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """The Mamba layers' float32 states and convolution tails of one
    slot."""
    return count(c, "ssm") * (state_bytes_a_layer(c)
                              + tail_bytes_a_layer(c, dtype_bytes))


def slot_bytes(c: dict, dtype_bytes: int = 2) -> int:
    if not is_sambay(c):
        return 0
    return ring_bytes(c, dtype_bytes) + state_bytes(c, dtype_bytes)


def shared_readers(c: dict) -> int:
    """Layers that attend over the shared pool: the full layer and the
    cross layers."""
    return count(c, "full") + count(c, "cross")


def dense_params(c: dict) -> int:
    """Parameters every decode step multiplies with: the layers and the
    output head (the embedding is gathered)."""
    return layers_params(c) + c["vocab_size"] * c["hidden_size"]


def attn_core_bytes(c: dict, kv_tokens_read: float,
                    dtype_bytes: int = 2) -> float:
    """The shared pool's keys and values of every context, once a reading
    layer."""
    return (shared_readers(c) * kv_tokens_read
            * paged_bytes_per_token(c, dtype_bytes))


def swa_core_bytes(c: dict, window_rows: float, dtype_bytes: int = 2) -> float:
    """``window_rows``: keys of the rings read, summed over the window
    layers."""
    return window_rows * paged_bytes_per_token(c, dtype_bytes)


def ssm_core_bytes(c: dict, rows_x_layers: float) -> float:
    """A live row's state read and written once a Mamba layer."""
    return 2.0 * rows_x_layers * state_bytes_a_layer(c)


def decode_step_bytes(c: dict, kv_tokens_read: float, window_rows: float,
                      rows_x_layers: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights once, the shared
    pool once a reading layer, the rings, the states and tails read and
    written."""
    return (dense_params(c) * dtype_bytes
            + attn_core_bytes(c, kv_tokens_read, dtype_bytes)
            + swa_core_bytes(c, window_rows, dtype_bytes)
            + ssm_core_bytes(c, rows_x_layers)
            + 2.0 * rows_x_layers * tail_bytes_a_layer(c, dtype_bytes))


# tokens of context the traced part's middle step attends to, from the
# client's count, as every ``decode_step_roofline`` takes them
kv_tokens_mid = costs_hybrid.kv_tokens_mid


def counted_per_step(obs: dict, key: str) -> float | None:
    """What the program counted on the device a decode step: delta ``key``
    (``ssm_state_rows``, ``shared_kv_rows_read``, ``window_rows_read``)
    over delta ``decode_steps_done`` of the window's ``server_info``
    samples. None without the counter (a program from before it) or the
    family's keys."""
    from benchmark.lib import counters

    if not is_sambay(obs["config"]["config"]):
        return None
    return counters.delta_ratio(obs, key, "decode_steps_done")


# how far the program's count of rows may lie from the client's
ROWS_AGREE = 0.02


def rows_agree(obs: dict) -> dict | None:
    """The program's keys of the shared pool a step and a reading layer
    (over the whole window: its middle) beside the client's tokens of
    context at the window's middle, and whether they agree to
    ``ROWS_AGREE``: the client's count decides the shares, and a run whose
    two counts part says so in ``checks``."""
    mine = counted_per_step(obs, "shared_kv_rows_read")
    if mine is None or "kv_tokens_at_end" not in obs:
        return None
    mine /= shared_readers(obs["config"]["config"])
    client = obs["kv_tokens_at_end"] - obs["tokens_in_window"] / 2.0
    return {"program_rows_a_step": mine, "client_tokens_mid_window": client,
            "agree": bool(abs(mine - client) <= ROWS_AGREE * client)}
