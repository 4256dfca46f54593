"""Bytes a decode step of a GQA decoder with routed experts must read,
computed from a configuration's sizes (the published keys of a
``benchmark/configs/*.json`` file) and from how many experts the step's
rows chose. Beside ``costs.py`` (dense decoders) and kept here for the
same reason: the sizes and the arithmetic are the benchmark's own.

An expert that no row chose need not be read, so the count takes the
experts hit, not all of them: the shares read low where the program
reads more. That number is the one input the program under test supplies
itself (``moe_experts_hit``, counted on the device by the block that is
measured): a program that over-counted it would read a higher share.
Nothing here checks it against the reference's own routing yet (PERF.md
section 7, for the next ``benchmark`` issue); what is checked is the
bound that holds regardless, ``num_experts`` a layer
(``experts_hit_per_step``).
"""

from __future__ import annotations

from benchmark.lib import costs


def expert_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One expert's three projections."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * dtype_bytes


def experts_bytes(c: dict, experts_hit: float, dtype_bytes: int = 2) -> float:
    """The experts a step read at least: ``experts_hit`` is summed over
    the layers."""
    return experts_hit * expert_bytes(c, dtype_bytes)


def dense_params(c: dict) -> int:
    """Parameters every step multiplies with whatever the routing: the
    attention projections and the router of every layer, and the output
    head. Norm weights (a few KB a layer) are left out."""
    d, hd = c["hidden_size"], costs.head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return (c["num_hidden_layers"] * (attn + d * c["num_experts"])
            + c["vocab_size"] * d)


def decode_step_bytes(c: dict, experts_hit: float, kv_tokens_read: float,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the dense weights once, the
    experts hit (summed over the layers) once each, and the keys and
    values of every context."""
    return (dense_params(c) * dtype_bytes
            + experts_bytes(c, experts_hit, dtype_bytes)
            + kv_tokens_read * costs.kv_bytes_per_token(c, dtype_bytes))


def experts_hit_per_step(obs: dict) -> float | None:
    """Experts with at least one row, summed over the layers, a decode
    step: delta ``moe_experts_hit`` over delta ``decode_steps_done`` of
    the window's ``server_info`` samples. None for a dense model or an
    engine without the counter."""
    from benchmark.lib import counters

    hit = counters.delta_ratio(obs, "moe_experts_hit", "decode_steps_done")
    c = obs["config"]["config"]
    if hit is not None and hit > c["num_experts"] * c["num_hidden_layers"]:
        raise ValueError(f"moe_experts_hit counts {hit:.1f} experts a step; "
                         "the model has fewer")
    return hit
