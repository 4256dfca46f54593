"""Parameters, bytes and FLOPs of MiniCPM-SALA's decoder (``minicpm_sala``:
block-sparse softmax attention layers, ``minicpm4``, whose pages carry a
pooled-key store, among linear-attention layers, ``lightning-attn``, with a
float32 state in the slot; a SwiGLU MLP in every layer; an untied head),
computed from a configuration's sizes: the published keys of
``benchmark/configs/minicpm-sala.json`` and its ``sparse_config`` group.
Beside ``costs.py`` (dense GQA) and the other families' files and kept here
for the same reason: the sizes and the arithmetic are the benchmark's own,
so a change to the program cannot move a roofline share
(``benchmark/tests/test_sala_metrics.py`` holds them to the tree the
program builds). They count from the configuration's keys and the
program's COUNTERS (pages chosen, pooled keys scored, state rows), never
from what a kernel fetches.

Bytes are the least a decode step needs: every matmul weight once, the
untied head once, the chosen pages' keys and values (a page of ``block``
tokens, both of a K/V head's arrays: 32,768 B a head at the published
sizes), the pooled keys a row's queries are scored against (float32: 512 B
a head), a lightning layer's state read and written. A share is the larger
of bytes over the chip's bandwidth and FLOPs over its peak: 96 rows make
at most 96 FLOPs a weight byte and a state element takes 3 FLOPs for its 8
bytes (bytes decide, v5e's ridge is 240), and a chosen page's 16 query rows
a K/V head make 4 x 16 FLOPs for the 4 bytes of a key and a value element
(16 a byte: bytes decide there too). With no family key in the
configuration (a CPU rehearsal runs ``configs/rehearsal.json``'s tiny dense
model under this cell's plane and readers) the page arithmetic is GQA's and
the readers find nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_hybrid

STATE_BYTES = 4     # a lightning state is float32
POOLED_BYTES = 4    # and so is a pooled key (the program's store)
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def is_sala(c: dict) -> bool:
    return bool(c.get("mixer_types"))


def kinds(c: dict) -> list[str]:
    """The mixer of each layer that runs here: ``sparse`` or
    ``lightning``."""
    got = [KINDS[k] for k in c["mixer_types"]]
    if len(got) != c["num_hidden_layers"]:
        raise ValueError(f"{len(got)} mixer_types for "
                         f"{c['num_hidden_layers']} layers")
    return got


def count(c: dict, kind: str) -> int:
    return kinds(c).count(kind)


def sparse_params(c: dict) -> int:
    """One ``minicpm4`` mixer: q, k, v, the gate (the output's width), o,
    and the q/k norms' vectors."""
    d, hd = c["hidden_size"], costs.head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * (hq + 2 * hkv) * hd + 2 * d * hq * hd + 2 * hd


def lightning_params(c: dict) -> int:
    """One ``lightning-attn`` mixer: q, k, v, the gate, o, the q/k/o
    norms' vectors and the slopes."""
    d, h, hd = c["hidden_size"], c["lightning_nh"], c["lightning_head_dim"]
    return 5 * d * h * hd + 3 * hd + h


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


MIXER_PARAMS = {"sparse": sparse_params, "lightning": lightning_params}


def layers_params(c: dict) -> int:
    """Every layer: its mixer, its MLP and two RMSNorms."""
    return sum(MIXER_PARAMS[k](c) + mlp_params(c) + 2 * c["hidden_size"]
               for k in kinds(c))


def weight_params(c: dict) -> int:
    """The whole tree: the layers, the embedding and the untied head, the
    final norm."""
    tied = c.get("tie_word_embeddings", False)
    return (layers_params(c)
            + (1 if tied else 2) * c["vocab_size"] * c["hidden_size"]
            + c["hidden_size"])


def dense_params(c: dict) -> int:
    """Parameters every decode step multiplies with: the layers and the
    output head (the embedding is gathered)."""
    return layers_params(c) + c["vocab_size"] * c["hidden_size"]


def kv_bytes_a_layer(c: dict, dtype_bytes: int = 2) -> int:
    """A token's K and V in one sparse layer."""
    return 2 * c["num_key_value_heads"] * costs.head_dim(c) * dtype_bytes


def pooled_key_bytes(c: dict) -> int:
    """One pooled key of every K/V head."""
    return c["num_key_value_heads"] * costs.head_dim(c) * POOLED_BYTES


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: a K/V pair a sparse layer and its share
    of the pooled keys (one every ``kernel_stride`` tokens)."""
    if not is_sala(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    stride = c["sparse_config"]["kernel_stride"]
    return count(c, "sparse") * (kv_bytes_a_layer(c, dtype_bytes)
                                 + pooled_key_bytes(c) // stride)


def state_bytes_a_layer(c: dict) -> int:
    return c["lightning_nh"] * c["lightning_head_dim"] ** 2 * STATE_BYTES


def table_bytes_a_layer(c: dict) -> int:
    """What a sparse layer's last decode step attended, kept in the slot:
    a K/V head's table of pages (``topk``, or the blocks of ``dense_len``
    keys) and the keys they hold, int32."""
    sp = c["sparse_config"]
    width = max(sp["topk"], -(-sp["dense_len"] // sp["block_size"]))
    return c["num_key_value_heads"] * (width + 1) * 4


def slot_bytes(c: dict) -> int:
    """One slot: the lightning layers' float32 states and the sparse
    layers' tables."""
    if not is_sala(c):
        return 0
    return (count(c, "lightning") * state_bytes_a_layer(c)
            + count(c, "sparse") * table_bytes_a_layer(c))


def page_bytes_a_head(c: dict, dtype_bytes: int = 2) -> int:
    """One chosen page of one K/V head: ``block`` keys and values."""
    return (2 * c["sparse_config"]["block_size"] * costs.head_dim(c)
            * dtype_bytes)


def attn_core_bytes(c: dict, pages_read: float, dtype_bytes: int = 2) -> float:
    """``pages_read``: chosen pages summed over live rows, K/V heads and
    sparse layers (``sparse_pages_read`` a step)."""
    return pages_read * page_bytes_a_head(c, dtype_bytes)


def attn_core_flops(c: dict, pages_read: float) -> float:
    """Scores and values of a K/V head's query rows over a chosen page."""
    group = c["num_attention_heads"] // c["num_key_value_heads"]
    return (pages_read * c["sparse_config"]["block_size"] * group
            * 4.0 * costs.head_dim(c))


def select_bytes(c: dict, pooled_scored: float) -> float:
    """``pooled_scored``: pooled keys scored, summed over live rows past
    ``dense_len`` and sparse layers (``sparse_pooled_scored`` a step)."""
    return pooled_scored * pooled_key_bytes(c)


def select_flops(c: dict, pooled_scored: float) -> float:
    return (pooled_scored * c["num_attention_heads"] * 2.0
            * costs.head_dim(c))


def lightning_core_bytes(c: dict, rows_x_layers: float) -> float:
    """A live row's state read and written once a lightning layer."""
    return 2.0 * rows_x_layers * state_bytes_a_layer(c)


def lightning_core_flops(c: dict, rows_x_layers: float) -> float:
    """Decay, the outer product's add and the output's product: 4 a state
    element."""
    return (4.0 * rows_x_layers * c["lightning_nh"]
            * c["lightning_head_dim"] ** 2)


def decode_step_bytes(c: dict, pages_read: float, pooled_scored: float,
                      rows_x_layers: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights and the head
    once, the chosen pages, the pooled keys scored, the states read and
    written."""
    return (dense_params(c) * dtype_bytes
            + attn_core_bytes(c, pages_read, dtype_bytes)
            + select_bytes(c, pooled_scored)
            + lightning_core_bytes(c, rows_x_layers))


def least_seconds(peaks: dict, n_bytes: float, flops: float) -> float:
    """The larger of bytes over the bandwidth and FLOPs over the peak."""
    return max(n_bytes / peaks["bytes"], flops / peaks["flops"])


# tokens of context the traced part's middle step attends to, from the
# client's count, as every ``decode_step_roofline`` takes them
kv_tokens_mid = costs_hybrid.kv_tokens_mid


def counted_per_step(obs: dict, key: str) -> float | None:
    """What the program counted on the device a decode step: delta ``key``
    (``sparse_pages_read``, ``sparse_pooled_scored``, ``sparse_dense_rows``,
    ``lightning_state_rows``) over delta ``decode_steps_done`` of the
    window's ``server_info`` samples. None without the counter (a program
    from before it) or the family's keys."""
    from benchmark.lib import counters

    if not is_sala(obs["config"]["config"]):
        return None
    return counters.delta_ratio(obs, key, "decode_steps_done")


def pages_per_row(obs: dict) -> float | None:
    """Chosen pages a live row, a K/V head and a sparse layer: delta
    ``sparse_pages_read`` over delta ``row_steps_done``, over heads and
    layers. ``topk`` while every row is past ``dense_len``."""
    from benchmark.lib import counters

    c = obs["config"]["config"]
    if not is_sala(c):
        return None
    got = counters.delta_ratio(obs, "sparse_pages_read", "row_steps_done")
    if got is None:
        return None
    return got / (c["num_key_value_heads"] * count(c, "sparse"))


def deployment(c: dict, slots: int, pool_bytes: int, page: int = 64) -> dict:
    """The configuration file's ``deployment`` arithmetic, recounted."""
    per_page = paged_bytes_per_token(c) * page
    pages = pool_bytes // per_page + 1
    return {"weight_params": weight_params(c),
            "weight_bytes": 2 * weight_params(c),
            "paged_bytes_per_token": paged_bytes_per_token(c),
            "slot_bytes": slot_bytes(c),
            "state_bytes": (slots + 1) * slot_bytes(c),
            "pages": pages, "pool_bytes": pages * per_page,
            "pool_tokens": (pages - 1) * page}
