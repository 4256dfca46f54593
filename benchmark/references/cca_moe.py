"""Plain reference forward of ZAYA1's decoder (``model_type`` ``zaya``):
float32 ``jax.numpy`` at the highest matmul precision, one sequence at a
time, no kernel, no cache, no batching, nothing of ``polyrl_tpu/models`` or
``polyrl_tpu/ops``. It reads the tree the program builds (the names below
are that tree's).

Every layer is a compressed-convolutional-attention (CCA) sublayer
followed by a top-1 routed MLP. ``E`` hidden size, ``Hq`` query heads over
``Hkv`` K/V heads of size ``d``; the query latent is ``Hq * d`` wide, the
key latent ``Hkv * d``, a value half ``Hkv * d / 2``; ``x`` is the residual
stream ``[T, E]``. Sources: the published config for every size; for the
operators, Compressed Convolutional Attention (arXiv:2510.04476) and the
ZAYA1 report (arXiv:2511.17127) as far as the catalog's row summarises
them; what neither settles is listed in ``benchmark/configs/zaya1-8b.json``
under ``assumed``. The nine steps:

1. ``h = RMSNorm(x)``; ``[q~ | k~ | va | vb] = h W_in``: ``q~ [T, Hq d]``,
   ``k~ [T, Hkv d]``, ``va`` and ``vb`` ``[T, Hkv d / 2]`` each. No bias.
2. Value shift: the first half of the K/V heads of token ``t`` hold
   ``va[t]``, the second half ``vb[t-1]`` (zero at ``t = 0``): half the
   value heads see the previous token.
3. Convolutional mixing of ``c = [q~ ; k~]`` (``Hq + Hkv`` heads of ``d``
   channels): ``u[t] = sum_{j < cca_time0} conv0[j] * c[t-j]`` (causal,
   depthwise: one tap vector a channel); then ``w[t] = sum_{j < cca_time1}
   u[t-j] @ conv1[j, g]`` head by head (``conv1[j, g]`` is ``d x d``:
   causal over the sequence, mixing the channels of head ``g`` only).
4. q-k mean, from the PRE-convolution latents: ``mq = (q~ + repeat(k~)) /
   2`` with each K/V head repeated over its ``Hq / Hkv`` query heads, ``mk =
   (group_mean(q~) + k~) / 2`` with the query heads of a group averaged;
   ``q = w_q + mq``, ``k = w_k + mk``.
5. Per head: ``q <- sqrt(d) q / |q|``, ``k <- tau_g sqrt(d) k / |k|``
   (``tau_g``: one learned temperature a K/V head; the norm is ``sqrt(sum
   x^2 + 1e-6)``). Rope on the first ``partial_rotary_factor`` of each
   head's columns, after the norm: rotate-half within those columns,
   frequencies ``theta^(-2i/rot)``, absolute positions.
6. Causal grouped-query attention in the latent: ``softmax(q k^T / sqrt(d))
   v``; ``y = o Wo`` (``Wo``: ``Hq d -> E``). What a serving system caches
   in pages is the finished ``k`` (step 5) and ``v`` (step 2); what it
   keeps beside them is ``c[t-1]``, ``u[t-1]`` and ``vb[t-1]`` (``tails``).
7. Residual scaling, both sublayers: ``x <- (a_r * x + b_r) + (a_o *
   F(RMSNorm(x)) + b_o)``, four learned vectors of ``E`` a sublayer
   (``attn_res`` / ``mlp_res`` rows 0-3).
8. MoE sublayer: ``h2 = RMSNorm(x)``; router latent ``s_l = h2 Wd + gamma_l
   * s_{l-1}`` (``s_{-1} = 0``; ``s_{l-1}`` is the layer before's latent of
   the SAME token: the carry); ``p = softmax(gelu(gelu(RMSNorm(s_l) W1) W2)
   W3)`` over the experts (exact gelu); ``e = argmax(p + b)`` (the
   balancing bias ``b`` enters the choice only); ``out = p_e * Wdown_e(
   silu(Wgate_e h2) * (Wup_e h2))``. No shared expert, no groups.
9. Head: final RMSNorm, logits over the tied embedding.

Sized for a 9k-token request beside 6 GB of bfloat16 weights on a 16 GB
chip: one jitted program a kind of sublayer, run layer by layer (a layer's
weights are cast to float32 as its turn comes, an expert at a time);
attention in blocks of ``Q_BLOCK`` queries against blocks of ``K_BLOCK``
keys with a running softmax; the MLP ``ROW_BLOCK`` positions at a time.

``trace`` returns, beside the log-probabilities, each layer's routed-block
input and the router latent it was handed at the scored positions
(``moe_in``, ``carry_in``), to which ``routed_block`` applies one layer's
router and experts, and the tails after the sequence's last token.
``even_router_bias`` is part of how the benchmark makes its weights.
``control``: ``"int8_experts"`` rounds every routed expert's matrices to
int8 with one scale an output channel; ``"low"`` is the whole forward in
the nearest precision below the one the configuration states: that, every
other matmul weight in int8 as well, and the ``k`` and ``v`` a token keeps
in int8 with one scale a head's row.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 256
K_BLOCK = 1024
ROW_BLOCK = 1024
BUCKET = 512
L2_EPS = 1e-6
EXPERTS = ("we_gate", "we_up", "we_down")
# matmul weights outside the routed experts, by their names in the tree
MATMULS = ("w_in", "conv1", "wo", "router_down", "router_w1", "router_w2",
           "router")
# the evening of a router's bias: rounds, and the first round's step (in
# units of probability: the 16 probabilities lie around 1/16)
EVEN_ROUNDS = 256
EVEN_STEP = 0.01


def _dense_gqa():
    """``dense_gqa.py``, for a configuration without CCA keys (a
    ``--rehearse-cpu`` walk runs a tiny dense model under every plane)."""
    name = "benchmark_references_dense_gqa"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "dense_gqa.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


class Sizes(NamedTuple):
    layers: int
    hq: int
    hkv: int
    d: int
    time0: int
    time1: int
    rot: int
    theta: float
    eps: float
    experts: int
    top_k: int


def _sizes(c: dict) -> Sizes:
    rope = (c.get("rope_parameters") or {}).get("hybrid") or {}
    d = int(c["head_dim"])
    factor = float(rope.get("partial_rotary_factor",
                            c.get("partial_rotary_factor", 1.0)))
    return Sizes(int(c["num_hidden_layers"]), int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), d, int(c["cca_time0"]),
                 int(c["cca_time1"]), int(d * factor),
                 float(rope.get("rope_theta", c.get("rope_theta", 10000.0))),
                 float(c["rms_norm_eps"]), int(c["num_experts"]),
                 int(c["num_experts_per_tok"]))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _int8(w):
    """[..., in, out] as weight-only int8 holds it: one scale an output
    channel."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


def _int8_rows(x):
    """[..., n] with one int8 scale a row."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


def _rope(x, z: Sizes):
    """x [T, H, d]: rotate-half on the first ``rot`` columns of a head."""
    half = z.rot // 2
    inv = jnp.asarray([z.theta ** (-2.0 * i / z.rot) for i in range(half)],
                      jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:z.rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., z.rot:]], axis=-1)


def _shifted(x, j: int):
    """x[t - j] along the first axis, zero before the sequence."""
    return x if j == 0 else jnp.pad(x, ((j, 0),) + ((0, 0),) * (x.ndim - 1)
                                    )[:x.shape[0]]


def _attend(q, k, v, z: Sizes):
    """Causal grouped-query softmax attention, q [T, Hq, d] over k, v
    [T, Hkv, d]: blocks of queries against blocks of keys with a running
    softmax, so that no [T, T] score matrix stands at once."""
    t = q.shape[0]
    g = z.hq // z.hkv
    tq, tk = -(-t // Q_BLOCK) * Q_BLOCK, -(-t // K_BLOCK) * K_BLOCK
    qb = jnp.pad(q, ((0, tq - t), (0, 0), (0, 0))).reshape(
        tq // Q_BLOCK, Q_BLOCK, z.hkv, g, z.d)
    kb = jnp.pad(k, ((0, tk - t), (0, 0), (0, 0))).reshape(
        tk // K_BLOCK, K_BLOCK, z.hkv, z.d)
    vb = jnp.pad(v, ((0, tk - t), (0, 0), (0, 0))).reshape(
        tk // K_BLOCK, K_BLOCK, z.hkv, z.d)

    def queries(a):
        i, qi = a
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)

        def keys(carry, b):
            m, l, acc = carry
            j, kj, vj = b
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            s = jnp.einsum("qhgd,khd->hgqk", qi, kj) / jnp.sqrt(
                jnp.float32(z.d))
            ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < t)
            s = jnp.where(ok[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # a block no query of this row sees yet: everything stays zero
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                    alpha * acc + jnp.einsum("hgqk,khd->hgqd", p, vj)), None

        init = (jnp.full((z.hkv, g, Q_BLOCK, 1), -jnp.inf),
                jnp.zeros((z.hkv, g, Q_BLOCK, 1)),
                jnp.zeros((z.hkv, g, Q_BLOCK, z.d)))
        (_m, l, acc), _ = jax.lax.scan(
            keys, init, (jnp.arange(tk // K_BLOCK), kb, vb))
        return (acc / jnp.maximum(l, 1e-30)).transpose(2, 0, 1, 3)

    o = jax.lax.map(queries, (jnp.arange(tq // Q_BLOCK), qb))
    return o.reshape(tq, z.hq * z.d)[:t]


def cca(h, lp, z: Sizes, low: bool = False):
    """Steps 1 to 6 on one sequence's normed input ``h`` [T, E] float32
    with one layer's weights ``lp`` in float32: (``y`` [T, E], the
    sequences ``c``, ``u``, ``vb`` whose rows are a serving system's
    tails)."""
    t = h.shape[0]
    hq, hkv, d = z.hq, z.hkv, z.d
    mixed, half = (hq + hkv) * d, hkv * d // 2
    proj = h @ lp["w_in"]                                       # step 1
    c, va, vb = (proj[:, :mixed], proj[:, mixed:mixed + half],
                 proj[:, mixed + half:])
    v = jnp.concatenate([va, _shifted(vb, 1)], -1).reshape(t, hkv, d)   # 2
    u = sum(lp["conv0"][j] * _shifted(c, j) for j in range(z.time0))    # 3
    uh = u.reshape(t, hq + hkv, d)
    w = sum(jnp.einsum("tgd,gde->tge", _shifted(uh, j), lp["conv1"][j])
            for j in range(z.time1))
    ch = c.reshape(t, hq + hkv, d)                                      # 4
    qm = ch[:, :hq].reshape(t, hkv, hq // hkv, d)
    km = ch[:, hq:]
    q = w[:, :hq] + ((qm + km[:, :, None]) / 2).reshape(t, hq, d)
    k = w[:, hq:] + (jnp.mean(qm, axis=2) + km) / 2
    q = _unit(q) * jnp.sqrt(jnp.float32(d))                             # 5
    k = _unit(k) * jnp.sqrt(jnp.float32(d)) * lp["tau"][:, None]
    q, k = _rope(q, z), _rope(k, z)
    if low:
        # what a token keeps in pages, as an int8 cache would hold it
        k, v = _int8_rows(k), _int8_rows(v)
    return _attend(q, k, v, z) @ lp["wo"], (c, u, vb)                   # 6


def route(h2, carry, lp, z: Sizes):
    """Step 8's router on ``h2`` [T, E] with the layer before's latents
    ``carry`` [T, R]: (routing weights [T, experts] float32, zero off the
    chosen; this layer's latents; the probabilities)."""
    t = h2.shape[0]
    s = h2 @ lp["router_down"] + lp["router_gamma"] * carry
    x = _rms(s, lp["router_norm"], z.eps)
    x = jax.nn.gelu(x @ lp["router_w1"], approximate=False)
    x = jax.nn.gelu(x @ lp["router_w2"], approximate=False)
    p = jax.nn.softmax(x @ lp["router"], axis=-1)
    top_i = jax.lax.top_k(p + lp["router_bias"], z.top_k)[1]
    weight = jnp.zeros_like(p).at[jnp.arange(t)[:, None], top_i].set(
        jnp.take_along_axis(p, top_i, axis=-1))
    return weight, s, p


def even_bias(p, rows, top_k: int):
    """The balancing bias [experts] float32 that evens the experts' loads
    on the rows ``rows`` [T] bool of the probabilities ``p`` [T, experts],
    by DeepSeek-V3's rule without an auxiliary loss: after every round an
    expert with more than the mean load has its bias lowered by a step and
    one with less has it raised, the step annealed to zero over
    ``EVEN_ROUNDS`` rounds."""
    e = p.shape[1]
    count = rows.astype(jnp.float32)
    mean = jnp.sum(count) * top_k / e

    def one_round(i, bias):
        top_i = jax.lax.top_k(p + bias, top_k)[1]
        load = jnp.zeros((e,), jnp.float32).at[top_i].add(count[:, None])
        return bias - EVEN_STEP * (1.0 - i / EVEN_ROUNDS) * jnp.sign(
            load - mean)

    return jax.lax.fori_loop(0, EVEN_ROUNDS, one_round,
                             jnp.zeros((e,), jnp.float32))


def experts(h2, weight, moe: dict, layer, int8: bool):
    """``sum_e weight[:, e] * Wdown_e(silu(Wgate_e h2) * (Wup_e h2))`` with
    EVERY expert applied to EVERY position (its weight there, or zero);
    ``moe`` holds the whole stacks [L, experts, ..], of which one expert
    of ``layer`` is picked out and cast at a time."""
    n = weight.shape[1]

    def pick(stack, e):
        w = jax.lax.dynamic_index_in_dim(
            stack.reshape(-1, *stack.shape[2:]), layer * n + e, 0,
            keepdims=False).astype(jnp.float32)
        return _int8(w) if int8 else w

    def one_expert(acc, ex):
        e, w = ex
        y = (jax.nn.silu(h2 @ pick(moe["we_gate"], e))
             * (h2 @ pick(moe["we_up"], e))) @ pick(moe["we_down"], e)
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h2),
                          (jnp.arange(n), weight.T))
    return out


def _pick(stack: dict, i, low: bool, skip=()):
    """Layer ``i`` of stacked weights in float32; with ``low`` every matmul
    weight rounded to int8."""
    out = {}
    for k, v in stack.items():
        if k in skip:
            continue
        w = jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False).astype(
            jnp.float32)
        out[k] = _int8(w) if low and k in MATMULS else w
    return out


def _scaled(x, y, res):
    """Step 7: ``res`` [4, E] = (a_r, b_r, a_o, b_o)."""
    return (res[0] * x + res[1]) + (res[2] * y + res[3])


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _attn_layer(stack, norm, res, x, i, z: Sizes, low: bool):
    """x [T, E] -> (x after the CCA sublayer, the tails' sequences' last
    rows are taken by the caller from (c, u, vb))."""
    lp = _pick(stack, i, low)
    y, seqs = cca(_rms(x, norm.astype(jnp.float32), z.eps), lp, z, low)
    return _scaled(x, y, res.astype(jnp.float32)), seqs


@functools.partial(jax.jit, static_argnames=("z",))
def _route_probs(moe, norm, x, carry, z: Sizes, layer):
    """The router's probabilities [T, experts] float32 of layer ``layer``
    on x [T, E] with the carried latents [T, R] (what ``_moe_layer``
    chooses by, before any bias)."""
    lp = _pick(moe, layer, False, skip=EXPERTS)
    return route(_rms(x, norm.astype(jnp.float32), z.eps), carry, lp, z)[2]


@functools.partial(jax.jit, static_argnames=("z", "control"))
def _moe_layer(moe, norm, res, x, carry, z: Sizes, layer, control: str):
    """The MoE sublayer of layer ``layer`` on x [T, E] with the carried
    latents [T, R]: (x, this layer's latents, its normed input)."""
    low = control == "low"
    lp = _pick(moe, layer, low, skip=EXPERTS)
    h2 = _rms(x, norm.astype(jnp.float32), z.eps)
    weight, s, _p = route(h2, carry, lp, z)
    n = h2.shape[0]
    pad = -n % ROW_BLOCK
    blocks = (jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, a.shape[1])
              for a in (h2, weight))
    y = jax.lax.map(
        lambda a: experts(a[0], a[1], moe, layer,
                          low or control == "int8_experts"),
        tuple(blocks)).reshape(-1, h2.shape[1])[:n]
    return _scaled(x, y, res.astype(jnp.float32)), s, h2


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _head(params, x, z: Sizes, low: bool):
    """Step 9 on ``x`` [N, E]: logits over the tied embedding."""
    x = _rms(x, params["final_norm"].astype(jnp.float32), z.eps)
    head = params["embed"].T.astype(jnp.float32)
    return x @ (_int8(head) if low else head)


def _decoder(params, tokens, z: Sizes, control="", at=None, n_real=None):
    """Every layer over one sequence ``tokens`` [T], layer by layer: the
    hidden states [T, E] before the final norm, and what was seen on the
    way, a list a layer each: with ``at`` (a slice of positions)
    ``moe_in`` (the routed block's normed input there) and ``carry_in``
    (the latents the router was handed there); with ``n_real`` ``tails``
    (``tail_rows`` after that many tokens). Only the rows asked for are
    kept: a 9k-token sequence's twelve layers of latents would be a
    gigabyte."""
    layers = params["layers"]
    low = control == "low"
    x = params["embed"][tokens].astype(jnp.float32)
    carry = jnp.zeros((x.shape[0], layers["moe"]["router_down"].shape[-1]),
                      jnp.float32)
    seen = {"moe_in": [], "carry_in": [], "tails": []}
    for l in range(z.layers):
        x, seqs = _attn_layer(layers["cca"], layers["attn_norm"][l],
                              layers["attn_res"][l], x, l, z, low)
        if n_real is not None:
            seen["tails"].append(tail_rows(z, seqs, n_real))
        if at is not None:
            seen["carry_in"].append(carry[at])
        x, carry, h2 = _moe_layer(
            layers["moe"], layers["mlp_norm"][l], layers["mlp_res"][l], x,
            carry, z, jnp.int32(l), control)
        if at is not None:
            seen["moe_in"].append(h2[at])
    return x, seen


def _padded(tokens, bucket: int):
    import numpy as np

    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), n


def tail_rows(z: Sizes, seqs, n: int):
    """What a serving system's slot holds after ``n`` tokens, from a
    layer's (c, u, vb) sequences: the last ``time0 - 1`` rows of ``c``, the
    last ``time1 - 1`` of ``u``, the last of ``vb``, flattened side by
    side (zero before the sequence)."""
    c, u, vb = seqs
    rows = [_shifted(c, j)[n - 1] for j in reversed(range(z.time0 - 1))]
    rows += [_shifted(u, j)[n - 1] for j in reversed(range(z.time1 - 1))]
    return jnp.concatenate(rows + [vb[n - 1]])


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "") -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``); ``moe_in`` and ``carry_in`` (each layer's routed-block
    input [n_score, E] and the latents its router was handed [n_score, R]
    at the positions that predict them); ``states``: a layer each, the
    tails after ALL of ``tokens``, flattened (``tail_rows``); on the
    host."""
    import numpy as np

    z = _sizes(c)
    tokens = list(tokens)
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        at = slice(n_prompt - 1, n_prompt - 1 + n_score)   # i predicts i + 1
        x, seen = _decoder(params, padded, z, control, at=at, n_real=n)
        logp = jax.nn.log_softmax(_head(params, x[at], z, control == "low"),
                                  axis=-1)
        states = [np.asarray(rows) for rows in seen["tails"]]
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "moe_in": [np.asarray(h) for h in seen["moe_in"]],
            "carry_in": [np.asarray(s) for s in seen["carry_in"]],
            "states": states}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). Without CCA
    keys in it it is ``dense_gqa``'s decoder: a CPU rehearsal walks every
    cell with a tiny dense model."""
    if not c.get("cca_time0"):
        return _dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    z = _sizes(c)
    with jax.default_matmul_precision("highest"):
        x, _ = _decoder(params, jnp.asarray(tokens, jnp.int32), z)
        return _head(params, x, z, False)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def _routed_block(moe, h2, carry, z: Sizes, layer, control: str):
    lp = _pick(moe, layer, False, skip=EXPERTS)
    weight, _s, _p = route(h2, carry, lp, z)
    return (experts(h2, weight, moe, layer, control == "int8_experts"),
            jnp.argmax(weight, axis=-1))


def routed_block(params, c: dict, layer: int, h2, carry, control: str = ""):
    """Layer ``layer``'s router and experts on the normed inputs ``h2``
    [N, E] with the carried latents ``carry`` [N, R]: (each position's
    chosen expert's output times its probability [N, E] float32, the
    expert chosen [N]); on the host."""
    import numpy as np

    with jax.default_matmul_precision("highest"):
        out, chosen = _routed_block(
            params["layers"]["moe"], jnp.asarray(h2, jnp.float32),
            jnp.asarray(carry, jnp.float32), _sizes(c), jnp.int32(layer),
            control)
    return np.asarray(out), np.asarray(chosen)


def even_router_bias(params, c: dict, seqs, skip: int = 0, last: int = 0,
                     keep=jnp.float32):
    """``router_bias`` [layers, experts] float32 that evens every layer's
    expert loads on the token sequences ``seqs`` (an array [B, T] or a list
    of sequences of any lengths), on the positions from ``skip`` on and,
    with ``last``, on each sequence's last ``last`` positions only
    (``even_bias``): what training does to this bias, done once for
    weights that were never trained. Layer by layer: a layer's bias is
    found from its own probabilities over all the sequences and used for
    what the later layers see. One sequence at a time (one longer than
    ``BUCKET`` padded on the right to a multiple of it, so that lengths
    share programs), so that
    many sequences fit beside a serving engine's pool; ``keep`` is the type
    a sequence's residual stream is kept in from one sublayer to the next
    (every sublayer, the router and the evening compute in float32; kept
    in bfloat16, twice the tokens fit). The bias that was drawn is not
    read."""
    import numpy as np

    z = _sizes(c)
    layers = params["layers"]
    moe = dict(layers["moe"],
               router_bias=jnp.zeros_like(layers["moe"]["router_bias"]))
    padded = [_padded(np.asarray(seq, np.int32),
                      BUCKET if len(seq) > BUCKET else 1) for seq in seqs]
    rows = jnp.asarray(np.concatenate([
        (np.arange(len(ids)) >= max(skip, n - last if last else 0))
        & (np.arange(len(ids)) < n) for ids, n in padded]))
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][ids].astype(keep) for ids, _n in padded]
        carries = [jnp.zeros((len(ids), moe["router_down"].shape[-1]),
                             jnp.float32) for ids, _n in padded]
        for l in range(z.layers):
            norm, layer = layers["mlp_norm"][l], jnp.int32(l)
            for i in range(len(xs)):
                xs[i] = _attn_layer(layers["cca"], layers["attn_norm"][l],
                                    layers["attn_res"][l],
                                    xs[i].astype(jnp.float32), l, z,
                                    False)[0].astype(keep)
            p = jnp.concatenate([
                _route_probs(moe, norm, x.astype(jnp.float32), s, z, layer)
                for x, s in zip(xs, carries)])
            bias = even_bias(p, rows, z.top_k)
            moe["router_bias"] = moe["router_bias"].at[l].set(bias)
            for i in range(len(xs)):
                x, carries[i], _h2 = _moe_layer(
                    moe, norm, layers["mlp_res"][l],
                    xs[i].astype(jnp.float32), carries[i], z, layer, "")
                xs[i] = x.astype(keep)
    return moe["router_bias"]
