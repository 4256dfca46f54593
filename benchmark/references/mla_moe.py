"""Plain reference forward of DeepSeek-V3's decoder (dots.vlm1 / dots.llm1
share it key for key): float32 ``jax.numpy`` at the highest matmul
precision, one sequence at a time, no kernel, no cache, no absorbed form,
nothing of ``polyrl_tpu/models`` or ``polyrl_tpu/ops``.

Pre-norm residual blocks, ``h = x + mla(rms(x))``, ``out = h +
mlp(rms(h))``; latent attention in EVERY layer; the first
``first_k_dense_replace`` layers that are kept have the dense SwiGLU, the
rest the routed block.

MLA with a query latent, in the expanded form with a full causal softmax::

    cq = rms(x Wqa);  q = cq Wqb as [H, nope | rope]
    [c | kr] = x Wkva;  c = rms(c)
    rope (interleaved pairs, absolute positions, YaRN's frequencies) on q's
        rope part and on kr, one rope key for all heads
    [k_nope | v] = c Wkvb as [H, nope | v]
    logits = (q_nope . k_nope + q_rope . kr) * (nope + rope)^-0.5 * m^2
    out = concat_h(softmax(logits) v) Wo          no gate, no bias

YaRN (``rope_scaling`` of type ``yarn``, DeepSeek-V3's reading): frequency
``i`` of the ``rope / 2`` is ``theta^(-2i/rope)``, divided by ``factor``
from dimension ``ceil(d(beta_slow))`` up, kept below ``floor(d(beta_fast))``
and blended linearly between, ``d(n) = rope * ln(L / (2 pi n)) / (2 ln
theta)`` being the dimension at which the original length ``L`` makes
``n`` turns; cos and sin times ``y(mscale) / y(mscale_all_dim)``, the
logits times ``y(mscale_all_dim)^2``, ``y(a) = 0.1 a ln(factor) + 1``.

The routed block is ``hybrid_kda_mla_moe.py``'s, imported and not copied
(``noaux_tc``: sigmoid scores, group-limited choice on score + bias,
weights ``routed_scaling_factor * s / sum``; EVERY held expert applied to
EVERY position with the position's weight for it or zero; a choice that
falls on an expert held elsewhere adds nothing; the shared expert whole).

Sized for 17k tokens at 128 heads beside 9 GB of weights on a 16 GB chip:
one jitted program a KIND of layer, run layer by layer (a layer's weights
are cast to float32 as its turn comes); attention ``HEAD_GROUP`` heads at a
time (their queries, K and V for the whole sequence, then blocks of
``Q_BLOCK`` queries against all keys, then those heads' rows of ``Wo``), the dense MLP
``ROW_BLOCK`` positions at a time.

``trace`` returns, beside the log-probabilities, each sparse layer's
routed-block input at the scored positions, to which ``routed_block``
applies one layer's held experts. ``even_router_bias`` is part of how the
benchmark makes its weights. ``control``: ``"int8_experts"`` rounds every
routed expert's matrices to int8 with one scale an output channel;
``"low"`` is the whole forward in the nearest precision below the one the
configuration states: that, every other matmul weight in int8 as well, and
the latent rows a token keeps (``c`` and ``kr``) in int8 with one scale a
row.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 256
HEAD_GROUP = 16
ROW_BLOCK = 512
BUCKET = 512
EXPERTS = ("we_gate", "we_up", "we_down")
# matmul weights outside the routed experts, by their names in the tree
MATMULS = ("wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
           "w_down", "router", "ws_gate", "ws_up", "ws_down")


def _routed():
    """``hybrid_kda_mla_moe.py``: the routed block, the evening of a
    router's bias and the int8 rounding are one family's and live there."""
    name = "benchmark_references_hybrid_kda_mla_moe"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "hybrid_kda_mla_moe.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn_y(factor: float, a: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def rope_frequencies(c: dict) -> tuple[tuple, float, float]:
    """(the rope's ``rope / 2`` frequencies, what cos and sin are
    multiplied by, what the logits are multiplied by) from the published
    keys; plain Python floats."""
    r, theta = int(c["qk_rope_head_dim"]), float(c["rope_theta"])
    inv = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    rs = c.get("rope_scaling")
    if not rs:
        return tuple(inv), 1.0, 1.0
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {rs}")
    factor = float(rs["factor"])
    length = float(rs["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:
        return r * math.log(length / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(rs.get("beta_slow", 1)))), r - 1)
    out = []
    for i, f in enumerate(inv):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    all_dim = float(rs.get("mscale_all_dim", 0))
    amp = _yarn_y(factor, float(rs.get("mscale", 1))) / _yarn_y(factor,
                                                                 all_dim)
    return tuple(out), amp, (_yarn_y(factor, all_dim) ** 2 if all_dim
                             else 1.0)


def _rope_pairs(x, pos, inv, amp):
    """x [T, H, R]: pairs (x[2i], x[2i+1]) turned by pos * inv[i]."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :] * amp, jnp.sin(ang)[:, None, :] * amp
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _int8_rows(x):
    """[..., n] with one int8 scale a row."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


class Sizes(NamedTuple):
    plan: tuple            # "dense" | "moe" a layer that is run
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    q_rank: int
    inv: tuple
    amp: float
    scale: float
    eps: float
    held: tuple
    top_k: int
    n_group: int
    topk_group: int
    factor: float
    norm_topk: bool
    tied: bool


def plan(c: dict) -> tuple:
    """The MLP of each layer that is run: the first ``first_k_dense_replace``
    KEPT layers dense, the rest routed."""
    n = int(c["num_hidden_layers"])
    dense = int(c.get("first_k_dense_replace") or 0)
    return tuple("dense" if at < dense else "moe" for at in range(n))


def _sizes(c: dict) -> Sizes:
    n_all = int(c.get("n_routed_experts") or c["num_experts"])
    held = tuple(int(v) for v in c.get("experts_held") or (0, n_all))
    inv, amp, more = rope_frequencies(c)
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    return Sizes(
        plan(c), int(c["num_attention_heads"]), nope, rope,
        int(c["v_head_dim"]), int(c["kv_lora_rank"]),
        int(c.get("q_lora_rank") or 0), inv, amp,
        (nope + rope) ** -0.5 * more, float(c["rms_norm_eps"]), held,
        int(c["num_experts_per_tok"]), int(c["n_group"]),
        int(c["topk_group"]), float(c["routed_scaling_factor"]),
        bool(c.get("norm_topk_prob", True)),
        bool(c.get("tie_word_embeddings", False)))


def mla(h, lp, z: Sizes, low: bool = False):
    """The MLA mixer on one sequence ``h`` [T, d] float32; ``lp`` one
    layer's weights in float32."""
    t = h.shape[0]
    hh, nope, rope, vd, rank = z.heads, z.nope, z.rope, z.v, z.rank
    pos = jnp.arange(t)
    # the queries' input: their normed latent where there is one
    if z.q_rank:
        src, wq = _rms(h @ lp["wq_a"], lp["q_norm"], z.eps), lp["wq_b"]
    else:
        src, wq = h, lp["wq"]
    kv = h @ lp["wkv_a"]
    c = _rms(kv[:, :rank], lp["kv_norm"], z.eps)
    kr = _rope_pairs(kv[:, None, rank:], pos, z.inv, z.amp)[:, 0]   # [T, rope]
    if low:
        # what a token keeps, as an int8 cache would hold it
        c, kr = _int8_rows(c), _int8_rows(kr)
    g = min(HEAD_GROUP, hh)
    wq = wq.reshape(-1, hh // g, g * (nope + rope))
    wkv_b = lp["wkv_b"].reshape(rank, hh // g, g, nope + vd)
    wo = lp["wo"].reshape(hh // g, g * vd, -1)
    n_blk = -(-t // Q_BLOCK)
    t_pad = n_blk * Q_BLOCK
    kpos = jnp.arange(t)

    def heads(out, at):
        q = (src @ wq[:, at]).reshape(t, g, nope + rope)
        qn, qr = q[..., :nope], _rope_pairs(q[..., nope:], pos, z.inv, z.amp)
        kvb = jnp.einsum("tr,rgd->tgd", c, wkv_b[:, at])          # [T, g, ..]
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        qn, qr = (jnp.pad(a, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
            n_blk, Q_BLOCK, g, -1) for a in (qn, qr))

        def block(a):
            i, qn, qr = a
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = (jnp.einsum("qgd,kgd->gqk", qn, k_nope)
                 + jnp.einsum("qgd,kd->gqk", qr, kr)) * z.scale
            s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
            return jnp.einsum("gqk,kgd->qgd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (jnp.arange(n_blk), qn, qr)).reshape(
            t_pad, g * vd)[:t]
        return out + o @ wo[at], None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(h), jnp.arange(hh // g))
    return out


def _pick(stack: dict, i, low: bool, skip=()):
    """Layer ``i`` of a kind's stacked weights in float32 (the experts stay
    whole stacks: one is picked out and cast at a time); with ``low``
    every matmul weight rounded to int8."""
    r = _routed()
    out = {}
    for k, v in stack.items():
        if k in skip:
            out[k] = v
            continue
        w = jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False).astype(
            jnp.float32)
        out[k] = r._int8(w) if low and k in MATMULS else w
    return out


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _attn_layer(stack, norm, x, i, z: Sizes, low: bool):
    """x [B, T, d] -> x + mla(rms(x)), one sequence at a time."""
    lp = _pick(stack, i, low)
    h = _rms(x, norm.astype(jnp.float32), z.eps)
    return x + jax.lax.map(lambda hb: mla(hb, lp, z, low), h)


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _dense_layer(stack, norm, x, j, z: Sizes, low: bool):
    mp = _pick(stack, j, low)
    b, t, d = x.shape
    h = _rms(x, norm.astype(jnp.float32), z.eps).reshape(-1, d)
    n = h.shape[0]
    pad = -n % ROW_BLOCK
    rows = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, d)
    y = jax.lax.map(lambda r: (jax.nn.silu(r @ mp["w_gate"])
                               * (r @ mp["w_up"])) @ mp["w_down"], rows)
    return x + y.reshape(-1, d)[:n].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("z", "control", "tap", "even"))
def _moe_layer(stack, norm, x, j, rows, first, z: Sizes, control: str,
               tap: int = 0, even: bool = False):
    """The routed layer ``j`` (among the sparse ones) on x [B, T, d].
    ``tap``: also its routed-block input at ``tap`` positions from
    ``first`` on; ``even``: the router's bias is evened on the rows
    ``rows`` [B * T] bool first, and returned."""
    r = _routed()
    low = control == "low"
    mp = _pick(stack, j, low, skip=EXPERTS)
    b, t, d = x.shape
    h = _rms(x, norm.astype(jnp.float32), z.eps).reshape(-1, d)
    bias = mp["router_bias"]
    if even:
        bias = r.even_bias(h, mp["router"], rows, z.top_k, z.n_group,
                           z.topk_group)
        mp["router_bias"] = bias
    seen = (jax.lax.dynamic_slice_in_dim(h.reshape(b, t, d), first, tap, 1)
            if tap else None)
    y = r.routed_mlp(h, mp, z.held, z.top_k, z.n_group, z.topk_group,
                     z.factor, z.norm_topk,
                     low or control == "int8_experts", layer=j)
    return x + y.reshape(x.shape), seen, bias


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _head(params, x, z: Sizes, low: bool):
    """Final norm and the output matrix on ``x`` [N, d]: logits."""
    x = _rms(x, params["final_norm"].astype(jnp.float32), z.eps)
    head = (params["embed"].T if z.tied else params["lm_head"]).astype(
        jnp.float32)
    return x @ (_routed()._int8(head) if low else head)


def _decoder(params, tokens, z: Sizes, control="", tap=None, even=None):
    """Every layer over the sequences ``tokens`` [B, T], layer by layer:
    the hidden states [B, T, d] before the final norm, and what was seen
    on the way: ``moe_in`` (with ``tap`` = (first, count): each sparse
    layer's routed-block input at those positions, [B, count, d]),
    ``bias`` (with ``even`` = rows [B * T] bool: each sparse layer's
    router bias evened on those rows, found at that layer and used from
    there on)."""
    layers = params["layers"]
    low = control == "low"
    x = params["embed"][tokens].astype(jnp.float32)
    seen = {"dense": 0, "moe": 0}
    taps = {"moe_in": [], "bias": []}
    first, count = tap if tap is not None else (0, 0)
    rows = even if even is not None else jnp.zeros((1,), bool)
    for l, mlp in enumerate(z.plan):
        j = seen[mlp]
        seen[mlp] = j + 1
        x = _attn_layer(layers["mla"], layers["attn_norm"][l], x, l, z, low)
        if mlp == "dense":
            x = _dense_layer(layers["dense"], layers["mlp_norm"][l], x, j, z,
                             low)
            continue
        x, moe_in, bias = _moe_layer(
            layers["moe"], layers["mlp_norm"][l], x, j, rows,
            jnp.int32(first), z, control, tap=int(count),
            even=even is not None)
        if tap is not None:
            taps["moe_in"].append(moe_in)
        if even is not None:
            taps["bias"].append(bias)
    return x, taps


def _padded(tokens, bucket: int):
    import numpy as np

    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), n


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "") -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``) and ``moe_in`` (each sparse layer's routed-block input
    [n_score, d] at the positions that predict them), on the host. Only
    what those positions see is computed: the sequence is cut after the
    last scored token."""
    import numpy as np

    z = _sizes(c)
    tokens = list(tokens)[:n_prompt + n_score]
    padded, _n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        # position i predicts token i + 1
        x, taps = _decoder(params, padded[None], z, control,
                           tap=(n_prompt - 1, n_score))
        pred = x[0, n_prompt - 1:n_prompt - 1 + n_score]
        logp = jax.nn.log_softmax(_head(params, pred, z, control == "low"),
                                  axis=-1)
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "moe_in": [np.asarray(h[0]) for h in taps["moe_in"]]}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). With no latent
    rank in it it is ``dense_gqa``'s decoder: a CPU rehearsal walks every
    cell with a tiny dense model."""
    if not c.get("kv_lora_rank"):
        return _routed()._dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    z = _sizes(c)
    with jax.default_matmul_precision("highest"):
        x, _ = _decoder(params, jnp.asarray(tokens, jnp.int32)[None], z)
        return _head(params, x[0], z, False)


@functools.partial(jax.jit, static_argnames=("z", "layer", "control"))
def _routed_block(moe, h, z: Sizes, layer: int, control: str):
    mp = {k: (v if k in EXPERTS else v[layer].astype(jnp.float32))
          for k, v in moe.items() if not k.startswith("ws_")}
    return _routed().routed_mlp(
        h, mp, z.held, z.top_k, z.n_group, z.topk_group, z.factor,
        z.norm_topk, control == "int8_experts", layer=layer)


def routed_block(params, c: dict, layer: int, h, control: str = ""):
    """The routed experts held here of sparse layer ``layer`` (counted
    among the sparse layers) on ``h`` [N, d]: each position's weighted sum
    over those of its choices that are held, WITHOUT the shared expert:
    [N, d] float32 on the host."""
    import numpy as np

    with jax.default_matmul_precision("highest"):
        return np.asarray(_routed_block(
            params["layers"]["moe"], jnp.asarray(h, jnp.float32), _sizes(c),
            int(layer), control))


def even_router_bias(params, c: dict, ids, skip: int = 0):
    """``router_bias`` [sparse layers, E_all] float32 that evens every
    sparse layer's expert loads on the token sequences ``ids`` [B, T],
    positions from ``skip`` on (``hybrid_kda_mla_moe.even_bias``:
    DeepSeek-V3's rule without an auxiliary loss): what training does to
    this bias, done once for weights that were never trained. Layer by
    layer: a layer's bias is found from its own scores and used for what
    the later layers see. The bias that was drawn is not read."""
    ids = jnp.asarray(ids, jnp.int32)
    b, t = ids.shape
    rows = jnp.broadcast_to(jnp.arange(t) >= skip, (b, t)).reshape(-1)
    with jax.default_matmul_precision("highest"):
        _x, taps = _decoder(params, ids, _sizes(c), even=rows)
    return jnp.stack(taps["bias"])
