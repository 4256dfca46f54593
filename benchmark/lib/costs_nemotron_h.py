"""Parameters and bytes of Nemotron-H's decoder (``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B): layers of ONE sublayer each by the
published ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer with a float32
state in the slot, ``*`` grouped-query attention without positions over
pages, ``E`` routed experts of TWO matrices (``relu(x W_up)^2 W_down``)
beside an ungated shared expert; an untied head. Computed from a
configuration's sizes: the published keys of
``benchmark/configs/nemotron-3-nano-30b-a3b.json``. Beside ``costs.py``
(dense GQA) and the other families' files and kept here for the same
reason: the sizes and the arithmetic are the benchmark's own, so a change
to the program cannot move a roofline share
(``benchmark/tests/test_nemotron_h_metrics.py`` holds them to the tree the
program builds). They count from the configuration's keys, the program's
COUNTERS (state rows and experts hit, whose counts a step do not grow with
the contexts) and the CLIENT's count of the contexts at the traced
seconds' middle (``keys_traced``: the keys grow a token a step, so the
whole window's counter is not the trace's), never from what a kernel
fetches.

Bytes are the least a decode step needs: every matmul weight of the
mixers, routers and shared experts once, the untied head once, TWO
projections of each expert at least one row chose (``costs_moe`` counts
three: its reader would read 3/2 of the truth here), the keys and values of
every context in the six attention layers, and a Mamba-2 layer's state read
and written with its convolution tail. Every share is bound by bytes: 64
rows make at most 64 FLOPs a weight byte, a state element takes 4 FLOPs for
its 8 bytes, and a key's 16 query rows a K/V head make 16 FLOPs a byte
(v5e's ridge is 240). With no family key in the configuration (a CPU
rehearsal runs ``configs/rehearsal.json``'s tiny dense model under this
cell's plane and readers) the page arithmetic is GQA's and the readers find
nothing to read.
"""

from __future__ import annotations

from benchmark.lib import costs, costs_hybrid

STATE_BYTES = 4     # a Mamba-2 state is float32
KINDS = {"M": "mamba2", "*": "gqa", "E": "moe"}


def is_nemotron_h(c: dict) -> bool:
    return bool(c.get("hybrid_override_pattern"))


def kinds(c: dict) -> list[str]:
    """The ONE sublayer of each layer: ``mamba2``, ``gqa`` or ``moe``."""
    got = [KINDS[ch] for ch in c["hybrid_override_pattern"]]
    if len(got) != c["num_hidden_layers"]:
        raise ValueError(f"a pattern of {len(got)} layers for "
                         f"{c['num_hidden_layers']}")
    return got


def count(c: dict, kind: str) -> int:
    return kinds(c).count(kind)


def mamba_inner(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: dict) -> int:
    """x | B | C: the convolution's channels."""
    return mamba_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_matmul_params(c: dict) -> int:
    """One Mamba-2 mixer's two matrices: in (z | x | B | C | dt), out."""
    d, inner = c["hidden_size"], mamba_inner(c)
    return (d * (inner + conv_channels(c) + c["mamba_num_heads"])
            + inner * d)


def mamba_params(c: dict) -> int:
    """One Mamba-2 mixer: its matrices, the convolution's taps and bias,
    ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's vector."""
    return (mamba_matmul_params(c)
            + (c["conv_kernel"] + 1) * conv_channels(c)
            + 3 * c["mamba_num_heads"] + mamba_inner(c))


def attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], costs.head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def expert_params(c: dict) -> int:
    """One routed expert: TWO projections."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return 2 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def router_width(c: dict) -> int:
    """The experts a router scores: the published count, whatever is held."""
    return int((c.get("published") or {}).get("n_routed_experts",
                                               c["n_routed_experts"]))


def router_params(c: dict) -> int:
    """The router's matrix and its correction bias."""
    return (c["hidden_size"] + 1) * router_width(c)


def moe_params(c: dict) -> int:
    """One expert layer as held here: the experts held, the shared expert,
    the router."""
    return (c["n_routed_experts"] * expert_params(c) + shared_params(c)
            + router_params(c))


LAYER_PARAMS = {"mamba2": mamba_params, "gqa": attn_params, "moe": moe_params}


def layers_params(c: dict) -> int:
    """Every layer: its one sublayer and its one RMSNorm."""
    return sum(LAYER_PARAMS[k](c) + c["hidden_size"] for k in kinds(c))


def weight_params(c: dict) -> int:
    """The whole tree: the layers, the embedding and the untied head, the
    final norm."""
    return (layers_params(c) + 2 * c["vocab_size"] * c["hidden_size"]
            + c["hidden_size"])


def published_params(c: dict) -> int:
    """The same with the published counts of experts and of vocabulary
    rows in place of this chip's share."""
    return weight_params({**c, **(c.get("published") or {})})


def dense_params(c: dict) -> int:
    """Matmul parameters every decode step multiplies with whatever the
    routing: the mixers, the routers, the shared experts and the output
    head (the embedding is gathered; vectors are left out)."""
    return (count(c, "mamba2") * mamba_matmul_params(c)
            + count(c, "gqa") * attn_params(c)
            + count(c, "moe") * (shared_params(c)
                                 + c["hidden_size"] * router_width(c))
            + c["vocab_size"] * c["hidden_size"])


def kv_bytes_a_layer(c: dict, dtype_bytes: int = 2) -> int:
    """A token's K and V in one attention layer."""
    return 2 * c["num_key_value_heads"] * costs.head_dim(c) * dtype_bytes


def paged_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a token keeps in pages: a K/V pair an attention layer."""
    if not is_nemotron_h(c):
        return costs.kv_bytes_per_token(c, dtype_bytes)
    return count(c, "gqa") * kv_bytes_a_layer(c, dtype_bytes)


def state_bytes_a_layer(c: dict) -> int:
    """One row's Mamba-2 state: ``[H, P, N]`` float32."""
    return mamba_inner(c) * c["ssm_state_size"] * STATE_BYTES


def tail_bytes_a_layer(c: dict, dtype_bytes: int = 2) -> int:
    """One row's convolution tail: the last K-1 rows of x | B | C."""
    return (c["conv_kernel"] - 1) * conv_channels(c) * dtype_bytes


def slot_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One slot: every Mamba-2 layer's state and tail."""
    if not is_nemotron_h(c):
        return 0
    return count(c, "mamba2") * (state_bytes_a_layer(c)
                                 + tail_bytes_a_layer(c, dtype_bytes))


def ssd_core_bytes(c: dict, rows_x_layers: float) -> float:
    """A live row's state read and written once a Mamba-2 layer."""
    return 2.0 * rows_x_layers * state_bytes_a_layer(c)


def ssd_core_flops(c: dict, rows_x_layers: float) -> float:
    """Decay, the outer product's add and the output's product: 4 a state
    element."""
    return (4.0 * rows_x_layers * mamba_inner(c) * c["ssm_state_size"])


def experts_bytes(c: dict, experts_hit: float, dtype_bytes: int = 2) -> float:
    """The experts a step read at least: ``experts_hit`` is summed over
    the expert layers; TWO projections each."""
    return experts_hit * expert_params(c) * dtype_bytes


def attn_core_bytes(c: dict, keys_read: float, dtype_bytes: int = 2) -> float:
    """``keys_read``: the keys a step's live rows attend over, summed over
    the attention layers (``keys_traced``)."""
    return keys_read * kv_bytes_a_layer(c, dtype_bytes)


def attn_core_flops(c: dict, keys_read: float) -> float:
    """Scores and values of every query head over a key."""
    return keys_read * c["num_attention_heads"] * 4.0 * costs.head_dim(c)


def decode_step_bytes(c: dict, experts_hit: float, keys_read: float,
                      rows_x_layers: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one decode step: the dense weights and the
    head once, the experts hit once each, the keys and values of every
    context, the states read and written with their tails."""
    tails = 2.0 * rows_x_layers * tail_bytes_a_layer(c, dtype_bytes)
    return (dense_params(c) * dtype_bytes
            + experts_bytes(c, experts_hit, dtype_bytes)
            + attn_core_bytes(c, keys_read, dtype_bytes)
            + ssd_core_bytes(c, rows_x_layers) + tails)


def least_seconds(peaks: dict, n_bytes: float, flops: float) -> float:
    """The larger of bytes over the bandwidth and FLOPs over the peak."""
    return max(n_bytes / peaks["bytes"], flops / peaks["flops"])


def counted_per_step(obs: dict, key: str) -> float | None:
    """What the program counted on the device a decode step: delta ``key``
    (``ssd_state_rows``, ``paged_rows_read``, ``moe_experts_hit``) over
    delta ``decode_steps_done`` of the window's ``server_info`` samples.
    None without the counter (a program from before it) or the family's
    keys."""
    from benchmark.lib import counters

    if not is_nemotron_h(obs["config"]["config"]):
        return None
    got = counters.delta_ratio(obs, key, "decode_steps_done")
    c = obs["config"]["config"]
    if (key == "moe_experts_hit" and got is not None
            and got > c["n_routed_experts"] * count(c, "moe")):
        raise ValueError(f"moe_experts_hit counts {got:.1f} experts a step; "
                         "the chip holds fewer")
    return got


def keys_traced(obs: dict) -> float | None:
    """The keys one decode step of the TRACED part reads, summed over the
    attention layers: the client's tokens of context at the traced part's
    middle (``costs_hybrid.kv_tokens_mid``) an attention layer. The
    contexts grow a token a step, so the window's mean
    (``paged_rows_read`` over the whole window) is not the traced
    seconds': the shares that divide by a traced time take this one.
    None without a trace or the family's keys."""
    c = obs["config"]["config"]
    mid = costs_hybrid.kv_tokens_mid(obs) if is_nemotron_h(c) else None
    return None if mid is None else count(c, "gqa") * mid


# how far the program's count of keys may lie from the client's
ROWS_AGREE = 0.02


def rows_agree(obs: dict) -> dict | None:
    """The program's keys a step and attention layer
    (``paged_rows_read``, over the whole window: its middle) beside the
    client's tokens of context at the window's middle, and whether they
    agree to ``ROWS_AGREE``: the client's count decides the shares, and a
    run whose two counts part says so in ``checks``."""
    mine = counted_per_step(obs, "paged_rows_read")
    if mine is None or "kv_tokens_at_end" not in obs:
        return None
    mine /= count(obs["config"]["config"], "gqa")
    client = obs["kv_tokens_at_end"] - obs["tokens_in_window"] / 2.0
    return {"program_rows_a_step": mine, "client_tokens_mid_window": client,
            "agree": bool(abs(mine - client) <= ROWS_AGREE * client)}


def deployment(c: dict, slots: int, pool_bytes: int, page: int = 64) -> dict:
    """The configuration file's ``deployment`` arithmetic, recounted."""
    per_page = paged_bytes_per_token(c) * page
    pages = pool_bytes // per_page + 1
    return {"weight_params": weight_params(c),
            "weight_bytes": 2 * weight_params(c),
            "published_params": published_params(c),
            "paged_bytes_per_token": paged_bytes_per_token(c),
            "slot_bytes": slot_bytes(c),
            "state_bytes": (slots + 1) * slot_bytes(c),
            "pages": pages, "pool_bytes": pages * per_page,
            "pool_tokens": (pages - 1) * page}
