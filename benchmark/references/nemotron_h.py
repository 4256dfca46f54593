"""Plain reference for Nemotron-H's decoder (``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B): float32 ``jax.numpy`` at the highest
matmul precision, no cache, no kernels, the recurrence position by
position (NOT the chunked form the program prefills with), the same share
of experts and of the vocabulary left out as the program leaves out. It
reads the program's parameter tree (``polyrl_tpu/models/hybrid.py``) and
the configuration file's published keys, and shares no code with the
program. No published implementation was at hand (no network): each
reading the catalog's config does not settle is listed in
``benchmark/configs/nemotron-3-nano-30b-a3b.json`` under ``assumed``.

A layer is ``x <- x + f(rmsnorm(x))``, ONE sublayer, ``f`` by the layer's
character of ``hybrid_override_pattern``; then the final RMSNorm and the
untied head.

``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, I
= H P, G = ``n_groups``, N = ``ssm_state_size``, K = ``conv_kernel``)::

    z | xBC | dt = split(u W_in)         W_in [d, I + (I + 2 G N) + H]
    xBC[t] = silu(sum_j conv[j] xBC[t - K + 1 + j] + conv_bias)   (zeros
             before the sequence)
    x | B | C = split(xBC)     x [H, P]; B, C [G, N]; head h reads group
                               h // (H / G)
    dt = softplus(dt + dt_bias)        (no clamp: ``time_step_limit`` is
                                        not in the config)
    a = exp(dt A)       A = -exp(a_log)  [H]
    S_t[h] = a_t[h] S_{t-1}[h] + (dt_t[h] x_t[h]) (x) B_t[g(h)]     [P, N]
    y_t[h] = S_t[h] C_t[g(h)] + d_skip[h] x_t[h]
    y = y * silu(z);  y <- y / rms(y over each of G groups of I / G) * norm_w
    out = y W_out

``*``, attention (32 query heads over 2 K/V heads of 128): ``q, k, v = h
W_qkv`` split (q | k | v), NO rotary embedding and no other positions,
causal softmax of ``q . k / sqrt(128)``, ``out = o W_o``; no bias.

``E``, experts: ``s = sigmoid(h W_r)`` over all 128; the top-6 of ``s +
router_bias`` (``n_group`` 1: no group limit); weights ``s`` of the
chosen over their sum, times 2.5; expert e is ``relu(h W_up[e])^2
W_down[e]``; plus the shared expert ``relu(h Ws_up)^2 Ws_down``,
unweighted. Of the 128 experts the ``experts_held`` are computed, a choice
that falls elsewhere is left out (another chip's).

Controls of ``correct`` (``CONTROLS``): ``state_bf16`` rounds every
Mamba-2 state to bfloat16's mantissa after each token
(``jax.lax.reduce_precision``), ``int8_experts`` holds the routed experts'
matrices as weight-only int8, ``no_decay`` leaves the decay out (a = 1),
``fp8_weights`` rounds every matrix of every sublayer and the head to
float8 e4m3's three bits of mantissa (the exponent kept, as a scaled fp8
tensor keeps its range): the nearest precision below the bfloat16 the
configuration states for them.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256       # queries of an attention layer scored at once
BUCKET = 512        # a sequence is padded to whole buckets
CONTROLS = ("state_bf16", "int8_experts", "no_decay", "fp8_weights")
# the matrices ``fp8_weights`` rounds (vectors, taps and routers stay)
MATRICES = ("w_in", "w_out", "wqkv", "wo")
KINDS = {"M": "mamba2", "*": "gqa", "E": "moe"}
# the evening of a router's bias: rounds, and the first round's step
# the share of a Mamba-2 layer's heads that ``slow`` names: those that
# forget slowest (the smallest mean dt A over the sequence)
SLOW_SHARE = 4
EVEN_ROUNDS = 256
EVEN_STEP = 0.02


def _dense_gqa():
    name = "benchmark_references_dense_gqa"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "dense_gqa.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


class Sizes(NamedTuple):
    pattern: str
    heads: int          # Mamba-2's H, P, G, N, K
    head: int
    groups: int
    state: int
    taps: int
    q_heads: int        # attention's
    kv_heads: int
    head_dim: int
    eps: float
    held: tuple         # (first, count) of the routed experts held here
    top_k: int
    factor: float
    norm_topk: bool


def _sizes(c: dict) -> Sizes:
    if int(c.get("n_group", 1)) != 1:
        raise NotImplementedError("a group-limited router")
    unknown = sorted(set(c["hybrid_override_pattern"]) - set(KINDS))
    if unknown:
        raise NotImplementedError(f"layers of kind {unknown}")
    return Sizes(
        str(c["hybrid_override_pattern"]), int(c["mamba_num_heads"]),
        int(c["mamba_head_dim"]), int(c["n_groups"]),
        int(c["ssm_state_size"]), int(c["conv_kernel"]),
        int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
        int(c["head_dim"]), float(c["layer_norm_epsilon"]),
        tuple(int(v) for v in c.get("experts_held")
              or (0, int(c["n_routed_experts"]))),
        int(c["num_experts_per_tok"]), float(c["routed_scaling_factor"]),
        bool(c.get("norm_topk_prob", True)))


def is_nemotron_h(c: dict) -> bool:
    return "hybrid_override_pattern" in c


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _int8(w):
    """[..., in, out] as weight-only int8 holds it: one scale an output
    channel."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


def _low(w, control: str):
    """A matrix as the control ``control`` holds it."""
    if control == "int8_experts":
        return _int8(w)
    if control == "fp8_weights":
        return jax.lax.reduce_precision(w, 8, 3)
    return w


def mamba2(h, lp, z: Sizes, n_real, control: str = ""):
    """One sequence ``h`` [T, d] of which ``n_real`` positions are real:
    (out [T, d], the state [H, P, N] after ``n_real`` positions, each
    head's mean ``dt |A|`` over those positions [H]: how fast it
    forgets)."""
    hh, p, g, n, k = z.heads, z.head, z.groups, z.state, z.taps
    inner = hh * p
    t = h.shape[0]
    proj = h @ lp["w_in"]
    zg, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                   proj[:, 2 * inner + 2 * g * n:])
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(before[j:j + t] * lp["conv"][j] for j in range(k))
                      + lp["conv_bias"])
    x = xbc[:, :inner].reshape(t, hh, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), hh // g, 1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), hh // g, 1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                     # [T, H]
    forget = dt * jnp.exp(lp["a_log"])
    decay = jnp.exp(-forget)
    if control == "no_decay":
        decay = jnp.ones_like(decay)

    def token(carry, xs):
        s, forgot = carry
        i, x, bm, cm, dt, a, f = xs
        new = (a[:, None, None] * s
               + (dt[:, None] * x)[:, :, None] * bm[:, None, :])
        if control == "state_bf16":
            new = jax.lax.reduce_precision(new, 8, 7)
        y = jnp.einsum("hpn,hn->hp", new, cm)
        real = i < n_real
        return (jnp.where(real, new, s),
                forgot + jnp.where(real, f, 0.0)), y

    # (the heads' forgetting is summed in the scan's carry: as a reduction
    # of its own it kept every layer's in-projection alive to the end of
    # the program, 5 GB at 5,632 positions)
    (state, forgot), y = jax.lax.scan(
        token, (jnp.zeros((hh, p, n)), jnp.zeros((hh,))),
        (jnp.arange(t), x, bm, cm, dt, decay, forget))
    rate = forgot / n_real
    y = (y + lp["d_skip"][:, None] * x).reshape(t, inner) * jax.nn.silu(zg)
    y = y.reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + z.eps)
    return (y.reshape(t, inner) * lp["norm_w"]) @ lp["w_out"], state, rate


def attention(h, lp, z: Sizes):
    """One sequence ``h`` [T, d]: causal softmax attention without
    positions, ``Q_BLOCK`` queries at a time (T a multiple of it)."""
    t = h.shape[0]
    hq, hkv, d = z.q_heads, z.kv_heads, z.head_dim
    qkv = h @ lp["wqkv"]
    q = qkv[:, :hq * d].reshape(t, hkv, hq // hkv, d)
    k = qkv[:, hq * d:(hq + hkv) * d].reshape(t, hkv, d)
    v = qkv[:, (hq + hkv) * d:].reshape(t, hkv, d)
    at = jnp.arange(t)

    def block(xs):
        q, q_at = xs
        s = jnp.einsum("qgjd,kgd->gjqk", q, k) * d ** -0.5
        s = jnp.where(at[None, None, None, :] <= q_at[None, None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("gjqk,kgd->qgjd", jax.nn.softmax(s, axis=-1), v)

    qb = min(Q_BLOCK, t)
    o = jax.lax.map(block, (q.reshape(t // qb, qb, hkv, hq // hkv, d),
                            at.reshape(t // qb, qb)))
    return o.reshape(t, hq * d) @ lp["wo"]


def choose(biased, top_k: int):
    return jax.lax.top_k(biased, top_k)[1]


def route(h, router, bias, z: Sizes):
    """Routing weights [T, E_all] float32, zero off the k chosen."""
    t = h.shape[0]
    s = jax.nn.sigmoid(h @ router)
    top_i = choose(s + bias, z.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if z.norm_topk:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(t)[:, None], top_i].set(
        top_s * z.factor)


def even_bias(h, router, rows, z: Sizes):
    """The router bias [E_all] float32 that evens the experts' loads on
    the rows ``rows`` [T] bool of ``h`` [T, d], by DeepSeek-V3's rule
    without an auxiliary loss: after every round an expert with more than
    the mean load has its bias lowered by a step and one with less has it
    raised, the step annealed to zero over ``EVEN_ROUNDS`` rounds."""
    s = jax.nn.sigmoid(h @ router)
    e = s.shape[1]
    count = rows.astype(jnp.float32)
    mean = jnp.sum(count) * z.top_k / e

    def one_round(i, bias):
        top_i = choose(s + bias, z.top_k)
        load = jnp.zeros((e,), jnp.float32).at[top_i].add(count[:, None])
        return bias - EVEN_STEP * (1.0 - i / EVEN_ROUNDS) * jnp.sign(
            load - mean)

    return jax.lax.fori_loop(0, EVEN_ROUNDS, one_round,
                             jnp.zeros((e,), jnp.float32))


def routed_experts(h, moe: dict, layer: int, z: Sizes, bias=None,
                   low: str = "", first: int | None = None,
                   count: int | None = None):
    """The routed experts held here (``z.held``; or ``count`` from
    ``first`` on of the stacks, for a test of the shares) of expert layer
    ``layer`` on ``h`` [T, d] float32, WITHOUT the shared expert: [T, d].
    ``moe``: the tree's whole stacks; an expert is picked out of them and
    cast inside the loop, so that no layer's stack is ever copied."""
    f32 = jnp.float32
    bias = moe["router_bias"][layer].astype(f32) if bias is None else bias
    weight = route(h, moe["router"][layer].astype(f32), bias, z)
    first = z.held[0] if first is None else first
    count = z.held[1] if count is None else count
    mine = jax.lax.dynamic_slice_in_dim(weight, first, count, axis=1)
    stacked = moe["we_up"].shape[1]

    def pick(stack, e):
        w = jax.lax.dynamic_index_in_dim(
            stack.reshape(-1, *stack.shape[2:]), layer * stacked + e, 0,
            keepdims=False).astype(f32)
        return _low(w, low)

    def one_expert(acc, ex):
        e, w = ex
        up = jnp.maximum(h @ pick(moe["we_up"], e), 0.0)
        return acc + w[:, None] * ((up * up) @ pick(moe["we_down"], e)), None

    # the stacks hold the experts from ``z.held[0]`` on
    at = jnp.arange(count) + first - z.held[0]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (at, mine.T))
    return out


def shared_expert(h, moe: dict, layer: int, low: str = ""):
    f32 = jnp.float32
    up = jnp.maximum(h @ _low(moe["ws_up"][layer].astype(f32), low), 0.0)
    return (up * up) @ _low(moe["ws_down"][layer].astype(f32), low)


def _decoder(params, tokens, z: Sizes, n_real, control: str = "", tap=None,
             even=None, upto: int | None = None):
    """Every layer (the first ``upto`` with it) over the sequences
    ``tokens`` [B, T], one at a time in the mixers (``vmap``) and all
    positions at once in the MLPs: the hidden states [B, T, d] before the
    final norm, and what was seen on the way: ``states`` (each Mamba-2
    layer's state [B, H, P, N] after ``n_real`` positions), ``rates``
    (each Mamba-2 layer's heads' mean ``dt |A|`` [B, H]), ``moe_in``
    (with ``tap`` = (first, count): each expert layer's input at those
    positions, [B, count, d]), ``bias`` (with ``even`` = rows [B * T]
    bool: each expert layer's router bias evened on those rows, found at
    that layer and used from there on)."""
    f32 = jnp.float32
    layers = params["layers"]
    b, t = tokens.shape
    x = params["embed"][tokens].astype(f32)
    seen = {kind: 0 for kind in KINDS.values()}
    taps = {"states": [], "rates": [], "moe_in": [], "bias": []}
    for l, ch in enumerate(z.pattern[:upto]):
        kind = KINDS[ch]
        i = seen[kind]
        seen[kind] = i + 1
        h = _rms(x, layers["norm"][l].astype(f32), z.eps)
        if kind == "moe":
            moe = layers["moe"]
            rows = h.reshape(b * t, -1)
            bias = None
            if even is not None:
                bias = even_bias(rows, moe["router"][i].astype(f32), even, z)
                taps["bias"].append(bias)
            if tap is not None:
                taps["moe_in"].append(jax.lax.dynamic_slice_in_dim(
                    h, tap[0], tap[1], axis=1))
            fp8 = control if control == "fp8_weights" else ""
            y = (routed_experts(rows, moe, i, z, bias,
                                control if control == "int8_experts" else fp8)
                 + shared_expert(rows, moe, i, fp8)).reshape(x.shape)
        else:
            lp = {k: v[i].astype(f32) for k, v in layers[kind].items()}
            if control == "fp8_weights":
                lp = {k: _low(v, control) if k in MATRICES else v
                      for k, v in lp.items()}
            if kind == "mamba2":
                y, state, rate = jax.vmap(lambda hb, lp=lp: mamba2(
                    hb, lp, z, n_real, control))(h)
                taps["states"].append(state)
                taps["rates"].append(rate)
            else:
                y = jax.vmap(lambda hb, lp=lp: attention(hb, lp, z))(h)
        x = x + y
    return x, taps


def _head(params, x, z: Sizes, control: str = ""):
    x = _rms(x, params["final_norm"].astype(jnp.float32), z.eps)
    return x @ _low(params["lm_head"].astype(jnp.float32),
                    control if control == "fp8_weights" else "")


@functools.partial(jax.jit, static_argnames=("z", "n_score", "all_logits",
                                             "control", "upto"))
def _score(params, tokens, n_real, first, z, n_score, all_logits=False,
           control="", upto=None):
    """One sequence ``tokens`` [T] of which ``n_real`` are real: the
    log-probabilities of the ``n_score`` tokens from position ``first``
    on, with the walk's taps; or every position's logits."""
    # position i predicts token i + 1
    x, taps = _decoder(params, tokens[None], z, n_real, control,
                       None if all_logits else (first - 1, n_score),
                       upto=upto)
    if all_logits:
        return _head(params, x[0], z)
    taps = {"states": [s[0] for s in taps["states"]],
            "rates": [r[0] for r in taps["rates"]],
            "moe_in": [h[0] for h in taps["moe_in"]]}
    if upto is not None:
        return None, None, taps
    pred = jax.lax.dynamic_slice_in_dim(x[0], first - 1, n_score, 0)
    logp = jax.nn.log_softmax(_head(params, pred, z, control), axis=-1)
    tgt = jax.lax.dynamic_slice_in_dim(tokens, first, n_score, 0)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return lp_tok, ent, taps


def _padded(tokens, bucket: int):
    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), jnp.int32(n)


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "", upto: int | None = None) -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``), ``states`` (each Mamba-2 layer's state [H, P, N] float32
    after ALL of ``tokens``), ``slow`` (each Mamba-2 layer's slowest
    ``1 / SLOW_SHARE`` of its heads, by their mean ``dt |A|`` over the
    sequence: the heads whose state remembers longest, where a state's
    rounding adds up and its inputs' does not) and ``moe_in`` (each expert
    layer's input
    [n_score, d] at the positions that predict the scored tokens), on the
    host. ``control``: one of ``CONTROLS``; ``upto``: the first layers
    alone (no ``logprobs`` then)."""
    if control and control not in CONTROLS:
        raise ValueError(f"control {control!r}: {CONTROLS}")
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        lp, _ent, taps = _score(params, padded, n, jnp.int32(n_prompt),
                                _sizes(c), int(n_score), control=control,
                                upto=upto)
    return {"logprobs": None if lp is None else np.asarray(lp),
            "states": [np.asarray(s) for s in taps["states"]],
            "slow": [np.sort(np.argsort(np.asarray(r))[:len(r) // SLOW_SHARE])
                     for r in taps["rates"]],
            "moe_in": [np.asarray(h) for h in taps["moe_in"]]}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. A configuration without
    the family's key (a CPU rehearsal's tiny dense model) is the dense GQA
    reference's."""
    if not is_nemotron_h(c):
        return _dense_gqa().score(params, c, tokens, n_score)
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        lp, ent, _taps = _score(params, padded, n, n - n_score, _sizes(c),
                                int(n_score), control=control)
    return np.asarray(lp), np.asarray(ent)


@functools.partial(jax.jit, static_argnames=("z", "layer", "low", "first",
                                             "count"))
def _routed_block(moe, h, z, layer, low, first, count):
    return routed_experts(h, moe, layer, z, None, low, first, count)


def routed_block(params, c: dict, layer: int, h, control: str = "",
                 first: int | None = None, count: int | None = None):
    """The routed experts held here of expert layer ``layer`` (counted
    among the expert layers) on ``h`` [N, d]: each position's weighted sum
    over those of its choices that are held, WITHOUT the shared expert:
    [N, d] float32 on the host."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_routed_block(
            params["layers"]["moe"], jnp.asarray(h, jnp.float32), _sizes(c),
            int(layer), control if control == "int8_experts" else "", first,
            count))


def shared_block(params, c: dict, layer: int, h):
    """The shared expert of expert layer ``layer`` on ``h`` [N, d]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(shared_expert(jnp.asarray(h, jnp.float32),
                                        params["layers"]["moe"], int(layer)))


@functools.partial(jax.jit, static_argnames=("z", "skip"))
def _even(params, ids, z, skip):
    b, t = ids.shape
    rows = jnp.broadcast_to(jnp.arange(t) >= skip, (b, t)).reshape(-1)
    _x, taps = _decoder(params, ids, z, jnp.int32(t), even=rows)
    return jnp.stack(taps["bias"])


def even_router_bias(params, c: dict, ids, skip: int = 0):
    """``router_bias`` [expert layers, E_all] float32 that evens every
    expert layer's loads on the token sequences ``ids`` [B, T], positions
    from ``skip`` on: what training does to this bias (its whole purpose in
    ``noaux_tc``), done once for weights that were never trained. Layer by
    layer: a layer's bias is found from its own scores and used for what
    the later layers see. The bias that was drawn is not read."""
    with jax.default_matmul_precision("highest"):
        return _even(params, jnp.asarray(ids, jnp.int32), _sizes(c),
                     int(skip))


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence (T a
    multiple of ``Q_BLOCK`` or under it)."""
    with jax.default_matmul_precision("highest"):
        return _score(params, jnp.asarray(tokens, jnp.int32), len(tokens), 1,
                      _sizes(c), 0, all_logits=True)
