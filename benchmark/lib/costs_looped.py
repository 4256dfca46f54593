"""Parameters and bytes of a looped decoder (``ouro``: ONE stack of layers
of plain multi-head attention and a dense SwiGLU MLP, four norms a layer,
run ``total_ut_steps`` times a token with the final norm between passes,
each pass keeping keys and values of its own; an exit gate; an untied
head), computed from a configuration's sizes: the published keys of
``benchmark/configs/ouro-2.6b.json``. Beside ``costs.py`` (dense GQA) and
the other families' files and kept here for the same reason: the sizes and
the arithmetic are the benchmark's own, so a change to the program cannot
move a roofline share (``benchmark/tests/test_looped_metrics.py`` holds
them to the tree the program builds).

Bytes are the least a decode step needs: the stack's matmul weights once a
PASS (a pass's input is the pass before's output, so no pass can share a
read with another), the head once (the embedding is gathered: a row a
token), and every key and value that a pass's layer attends over once (a
pass reads its own). Bound by bytes throughout: 6 rows make 6 FLOPs a
weight byte and a query row 2 FLOPs a K/V element, one query head a K/V
head, where v5e's ridge is 240. With no family key in the configuration (a
CPU rehearsal runs ``configs/rehearsal.json``'s tiny dense model under
this cell's plane and readers) the page arithmetic is GQA's and the
readers find nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs


def is_looped(c: dict) -> bool:
    return "total_ut_steps" in c


def passes(c: dict) -> int:
    return int(c["total_ut_steps"])


def layer_params(c: dict) -> int:
    """One layer's matrices: q, k, v, o and the MLP's three."""
    d, hd = c["hidden_size"], costs.head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return (d * hq * hd + 2 * d * hkv * hd + hq * hd * d
            + 3 * d * c["intermediate_size"])


def stack_params(c: dict) -> int:
    return c["num_hidden_layers"] * layer_params(c)


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def norm_params(c: dict) -> int:
    """Four norms a layer and the final one."""
    return (4 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def gate_params(c: dict) -> int:
    """The exit gate: a weight a hidden column and a bias."""
    return c["hidden_size"] + 1


def weight_params(c: dict) -> int:
    """The whole tree: ONE stack whatever the passes, embedding and the
    untied head, the norms, the gate."""
    tied = c.get("tie_word_embeddings", False)
    return (stack_params(c) + (1 if tied else 2) * head_params(c)
            + norm_params(c) + gate_params(c))


def kv_bytes_a_layer(c: dict, itemsize: int = 2) -> int:
    """One layer's K and V of one token in one pass."""
    return 2 * c["num_key_value_heads"] * costs.head_dim(c) * itemsize


def paged_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What a token keeps in pages: every layer's K and V of EVERY pass."""
    if not is_looped(c):
        return costs.kv_bytes_per_token(c, itemsize)
    return passes(c) * c["num_hidden_layers"] * kv_bytes_a_layer(c, itemsize)


def attn_core_bytes(c: dict, pass_rows: float, itemsize: int = 2) -> float:
    """``pass_rows``: keys a step's rows attend over, summed over the
    passes (``kv_pass_rows_read`` a step): each read once a layer."""
    return pass_rows * c["num_hidden_layers"] * kv_bytes_a_layer(c, itemsize)


def decode_step_bytes(c: dict, pass_rows: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: the stack once a pass, the
    head once, the passes' keys and values."""
    return ((passes(c) * stack_params(c) + head_params(c)) * itemsize
            + attn_core_bytes(c, pass_rows, itemsize))


def counted_per_step(obs: dict, key: str) -> float | None:
    """What the program counted on the device a decode step: delta ``key``
    (``kv_pass_rows_read``, ``ut_passes``) over delta ``decode_steps_done``
    of the window's ``server_info`` samples. None without the counter (a
    program from before it) or the family's keys."""
    from benchmark.lib import counters

    if not is_looped(obs["config"]["config"]):
        return None
    return counters.delta_ratio(obs, key, "decode_steps_done")


# how far the program's count of rows may lie from the client's
ROWS_AGREE = 0.02


def rows_agree(obs: dict) -> dict | None:
    """The program's keys a step and a pass (over the whole window: its
    middle) beside the client's tokens of context at the window's middle,
    and whether they agree to ``ROWS_AGREE``: a run whose two counts part
    says so in ``checks``."""
    mine = counted_per_step(obs, "kv_pass_rows_read")
    if mine is None or "kv_tokens_at_end" not in obs:
        return None
    mine /= passes(obs["config"]["config"])
    client = obs["kv_tokens_at_end"] - obs["tokens_in_window"] / 2.0
    return {"program_rows_a_step": mine, "client_tokens_mid_window": client,
            "agree": bool(abs(mine - client) <= ROWS_AGREE * client)}


def pass_rows_mid(obs: dict) -> float | None:
    """Keys a step's rows attend over, summed over the passes, at the
    traced part's middle: the client's tokens of context there
    (``costs_hybrid.kv_tokens_mid``, as every ``decode_step_roofline``
    takes them) times the passes the program counted a row and step
    (``ut_passes`` over ``row_steps_done``: a pass left out is bytes left
    out, not a better share)."""
    from benchmark.lib import costs_hybrid, counters

    if not is_looped(obs["config"]["config"]):
        return None
    kv_mid = costs_hybrid.kv_tokens_mid(obs)
    ran = counters.delta_ratio(obs, "ut_passes", "row_steps_done")
    if kv_mid is None or ran is None:
        return None
    return kv_mid * ran
