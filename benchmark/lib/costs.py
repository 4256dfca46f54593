"""Bytes the algorithm needs, computed from a configuration's sizes (the
dict of a ``benchmark/configs/*.json`` file's ``config`` with the cell's
depth applied). Dense GQA decoders only; a new architecture adds its own
functions beside these, in a new file. Kept here so that a change to the
program cannot move a roofline share.
"""

from __future__ import annotations


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def matmul_params(c: dict) -> int:
    """Parameters a token multiplies with: the layers and the output head
    (tied or not, the head matmul runs); the embedding gather is no matmul."""
    d, hd = c["hidden_size"], head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + mlp) + c["vocab_size"] * d


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * head_dim(c) * dtype_bytes)


def decode_step_bytes(c: dict, kv_tokens_read: float,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: every matmul weight once plus
    the keys and values the step's sequences attend to."""
    return (matmul_params(c) * dtype_bytes
            + kv_tokens_read * kv_bytes_per_token(c, dtype_bytes))
