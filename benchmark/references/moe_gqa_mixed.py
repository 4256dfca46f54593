"""Plain reference forward of Laguna's decoder (``model_type`` ``laguna``:
Laguna-XS.2): float32 ``jax.numpy`` at the highest matmul precision, one
sequence at a time, no kernel, no cache, no batching, nothing of
``polyrl_tpu``. It reads the tree the program builds (the names below are
that tree's) and takes every size from the tree's shapes and the
published keys.

``x`` the residual stream ``[T, E]``, ``h = rmsnorm(x)``, layer ``l`` of
kind ``k`` in {full, window} (``layer_types``: ``full_attention`` /
``sliding_attention``), ``H_k`` query heads (``num_attention_heads_per_
layer``), ``Hkv`` K/V heads of size ``D``, group ``G_k = H_k / Hkv``::

    q = h Wq_k [H_k, D]   kk = h Wk [Hkv, D]   v = h Wv [Hkv, D]
    g = sigmoid(h Wg_k) [H_k]
    q, kk <- rope_k(position) on the first r_k D columns of a head
    a[j] = softmax_s(q[j] . kk[j // G_k, s] / sqrt(D) + mask_k(t, s))
    mask_full: s <= t        mask_window: s <= t and t - s < W
    x <- x + concat_j(g[j] * sum_s a[j, s] v[j // G_k, s]) Wo_k
    dense:  x <- x + (silu(h' W1) * (h' W3)) W2         (h' = rmsnorm(x))
    sparse: p = softmax(h' Wr) over ALL experts; T = top-k of p;
            w_e = f p_e / sum_T p            (f: moe_routed_scaling_factor)
            x <- x + sum_{e in T} w_e E_e(h') + S(h')   (E_e, S: SwiGLU)
    logits = rmsnorm(x) W_head                          (untied)

``rope_k``: the published ``rope_parameters`` block of the layer's type:
frequencies ``theta ** (-2i / R)`` over the ``R = r_k D`` turned columns,
rotate-half within them (columns ``i`` and ``i + R / 2`` are a pair), the
rest of a head as it is; ``rope_type`` ``yarn``: a frequency is divided by
``factor`` from the dimension up at which ``original_max_position_
embeddings`` positions make ``beta_slow`` turns (rounded up), kept below
the one at which they make ``beta_fast`` (rounded down), blended linearly
between, and cos and sin are multiplied by ``attention_factor``.

What the published config does not settle is listed in
``benchmark/configs/laguna-xs.2.json`` under ``assumed`` (the gate a head,
the softmax router with its chosen weights renormalised, no gate on the
shared expert, no q/k norm); no published implementation was at hand to
check a line against (no network). Departures from the equations: none in
the mathematics; the tree keeps ``Wq | Wk | Wv`` as one matrix ``wqkv``
(the same numbers) and, of a sparse layer's experts, those held here
(``experts_held``: a chip's share of an expert-parallel deployment): a
choice that falls on an expert held elsewhere adds nothing, in the program
and here alike, and ``sparse_layer`` takes any share.

What a serving system keeps: a full layer's rotated keys and values of
every token; a window layer's of the last W tokens. ``trace`` returns,
beside the log-probabilities, each window layer's ``[kk | v]`` rows of the
last W tokens with the position of the first (``rings``) and each sparse
layer's ``h'`` at the scored positions (``moe_in``).

Sized for a 20k-token request beside 3.2 GB of bfloat16 weights on a 16 GB
chip: one jitted program a kind of sublayer, run layer by layer (a layer's
weights are cast to float32 as its turn comes); attention in blocks of
``Q_BLOCK`` queries against blocks of ``K_BLOCK`` keys with a running
softmax; the MLPs and the head ``ROW_BLOCK`` positions at a time, the
experts one at a time.

``control``, for the benchmark's controls of ``correct`` alone:
``"low"`` is the whole forward in the nearest precision below the bfloat16
the configuration states for weights and cache (every matmul weight and
the head int8 with one scale an output channel, the ``kk`` and ``v`` a
token keeps int8 with one scale a head's row); ``"int8_experts"`` (of
``routed_block``) rounds the held experts alone; ``"window_minus"`` gives a
window layer W - 1 keys.
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
import numpy as np

Q_BLOCK = 256
K_BLOCK = 1024
ROW_BLOCK = 1024
BUCKET = 512

WINDOW_OFF = {"window_minus": -1}
# the weights that multiply activations: what ``control="low"`` rounds
MATMULS = ("wqkv", "wg", "wo", "w_gate", "w_up", "w_down", "router",
           "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down")
EXPERTS = ("we_gate", "we_up", "we_down")


def _dense_gqa():
    """``dense_gqa.py``, for a configuration without the family's keys (a
    ``--rehearse-cpu`` walk runs a tiny dense model under every plane)."""
    name = "benchmark_references_dense_gqa"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "dense_gqa.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


class Rope(NamedTuple):
    inv_freq: tuple      # R / 2 frequencies
    amplitude: float     # on cos and sin


class Sizes(NamedTuple):
    kinds: tuple         # "full" | "window" a layer
    heads: tuple         # query heads a layer
    sparse: tuple        # whether a layer's MLP is routed
    hkv: int
    d: int
    window: int
    eps: float
    top_k: int
    factor: float        # moe_routed_scaling_factor
    norm_topk: bool
    held: tuple          # (first, count) of the experts held here
    ropes: tuple         # (kind, Rope) pairs


def rope_of(block: dict, d: int) -> Rope:
    """A published ``rope_parameters`` block as frequencies over the
    turned columns and the factor on cos and sin."""
    r = int(d * float(block.get("partial_rotary_factor", 1.0)))
    theta = float(block["rope_theta"])
    inv = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    kind = block.get("rope_type", "default")
    if kind == "default":
        return Rope(tuple(inv), 1.0)
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor = float(block["factor"])
    old = float(block["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:
        return r * math.log(old / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(block["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(block["beta_slow"]))), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    amp = block.get("attention_factor")
    if amp is None:
        amp = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return Rope(tuple(inv / factor * ramp + inv * (1 - ramp)), float(amp))


KIND_OF = {"full_attention": "full", "sliding_attention": "window"}


def _sizes(params, c: dict, control: str = "", held=None) -> Sizes:
    """``held``: (first, count) of another share than the file's."""
    d = int(c["head_dim"])
    n = int(c["num_hidden_layers"])
    kinds = tuple(KIND_OF[t] for t in c["layer_types"])
    if len(kinds) != n:
        raise ValueError(f"layer_types names {len(kinds)} layers of {n}")
    router = params["layers"]["moe"]["router"].shape[-1]
    return Sizes(
        kinds, tuple(int(h) for h in c["num_attention_heads_per_layer"]),
        tuple(t == "sparse" for t in c["mlp_layer_types"]),
        int(c["num_key_value_heads"]), d,
        int(c["sliding_window"]) + WINDOW_OFF.get(control, 0),
        float(c["rms_norm_eps"]), int(c["num_experts_per_tok"]),
        float(c.get("moe_routed_scaling_factor", 1.0)),
        bool(c.get("norm_topk_prob", True)),
        tuple(held or c.get("experts_held") or (0, router)),
        tuple((KIND_OF[t], rope_of(block, d))
              for t, block in c["rope_parameters"].items()
              if t in KIND_OF))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _int8(w):
    """[.., in, out] as weight-only int8 holds it: one scale an output
    channel."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) \
        / 127.0
    return jnp.round(w / scale) * scale


def _int8_rows(x):
    """[..., n] with one int8 scale a row."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) \
        / 127.0
    return jnp.round(x / scale) * scale


def _pick(stack: dict, i, low: bool = False, skip=()) -> dict:
    """Layer ``i`` of stacked weights in float32 (``skip``: as they are
    stored); ``low``: its matmul weights rounded to int8."""
    out = {}
    for k, v in stack.items():
        w = jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
        if k not in skip:
            w = w.astype(jnp.float32)
            if low and k in MATMULS:
                w = _int8(w)
        out[k] = w
    return out


def rope(x, pos, r: Rope):
    """``x`` [T, H, D] at positions ``pos`` [T]: the first ``2 *
    len(inv_freq)`` columns of a head turned, rotate-half within them."""
    half = len(r.inv_freq)
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(
        np.asarray(r.inv_freq), jnp.float32)
    cos, sin = jnp.cos(ang) * r.amplitude, jnp.sin(ang) * r.amplitude
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def _attend(q, k, v, n_real, window: int):
    """Softmax attention of every head: q [T, H, D] over k, v [T, Hkv, D]
    -> [T, H, D]; blocks of queries against blocks of keys with a running
    softmax. ``window`` > 0: a query sees its last ``window`` keys only."""
    t, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    tq, tk = -(-t // Q_BLOCK) * Q_BLOCK, -(-t // K_BLOCK) * K_BLOCK
    qb = jnp.pad(q, ((0, tq - t), (0, 0), (0, 0))).reshape(
        tq // Q_BLOCK, Q_BLOCK, hkv, g, d)
    kb = jnp.pad(k, ((0, tk - t), (0, 0), (0, 0))).reshape(
        tk // K_BLOCK, K_BLOCK, hkv, d)
    vb = jnp.pad(v, ((0, tk - t), (0, 0), (0, 0))).reshape(
        tk // K_BLOCK, K_BLOCK, hkv, d)

    def queries(a):
        i, qi = a
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)

        def keys(carry, b):
            m, l, acc = carry
            j, kj, vj = b
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            s = jnp.einsum("qpgd,kpd->pgqk", qi, kj) / jnp.sqrt(
                jnp.float32(d))
            ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_real)
            if window:
                ok &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(ok[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                    alpha * acc + jnp.einsum("pgqk,kpd->pgqd", p, vj)), None

        shape = (hkv, g, Q_BLOCK)
        init = (jnp.full((*shape, 1), -jnp.inf), jnp.zeros((*shape, 1)),
                jnp.zeros((*shape, d)))
        (_m, l, acc), _ = jax.lax.scan(
            keys, init, (jnp.arange(tk // K_BLOCK), kb, vb))
        return (acc / jnp.maximum(l, 1e-30)).transpose(2, 0, 1, 3)

    o = jax.lax.map(queries, (jnp.arange(tq // Q_BLOCK), qb))
    return o.reshape(tq, h, d)[:t]


def attention(h, lp, heads: int, n_real, z: Sizes, r: Rope, window: int,
              low: bool = False):
    """An attention mixer over h [T, E]: (output [T, E], the layer's
    rotated keys and its values (kk, v) [T, Hkv, D]). ``low``: what a
    token keeps, as an int8 cache would hold it."""
    t = h.shape[0]
    pos = jnp.arange(t)
    qkv = (h @ lp["wqkv"]).reshape(t, heads + 2 * z.hkv, z.d)
    q = rope(qkv[:, :heads], pos, r)
    k = rope(qkv[:, heads:heads + z.hkv], pos, r)
    v = qkv[:, heads + z.hkv:]
    if low:
        k, v = _int8_rows(k), _int8_rows(v)
    o = _attend(q, k, v, n_real, window)
    if "wg" in lp:
        o = o * jax.nn.sigmoid(h @ lp["wg"])[:, :, None]
    return o.reshape(t, -1) @ lp["wo"], (k, v)


def route(h, router, z: Sizes):
    """The routing weights [T, E_all] float32 of ``h`` [T, E]: zero but
    for a position's ``top_k`` experts."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, z.top_k)
    if z.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_p * z.factor)


def routed(h, lp, z: Sizes, low: bool = False):
    """The held experts' part of a sparse layer on ``h`` [T, E]: each
    position's weighted sum over those of its choices that are held
    (``z.held``), every held expert applied to every position, one at a
    time, WITHOUT the shared expert."""
    f32 = jnp.float32
    first, count = z.held
    weight = jax.lax.dynamic_slice_in_dim(route(h, lp["router"], z), first,
                                          count, 1)

    def one_expert(acc, ex):
        gate, up, down, w = ex
        gate, up, down = (_int8(m.astype(f32)) if low else m.astype(f32)
                          for m in (gate, up, down))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["we_gate"], lp["we_up"], lp["we_down"], weight.T))
    return out


def shared(h, lp):
    return (jax.nn.silu(h @ lp["ws_gate"]) * (h @ lp["ws_up"])) \
        @ lp["ws_down"]


def _rows(fn, h):
    """``fn`` over ``h`` [T, E], ``ROW_BLOCK`` positions at a time."""
    t = h.shape[0]
    rows = min(ROW_BLOCK, t)
    blocks = jnp.pad(h, ((0, -t % rows), (0, 0))).reshape(-1, rows,
                                                          h.shape[1])
    return jax.lax.map(fn, blocks).reshape(-1, h.shape[1])[:t]


@functools.partial(jax.jit, static_argnames=("z", "heads", "kind", "low"))
def _attn_layer(layers, x, l, i, n_real, z: Sizes, heads: int, kind: str,
                low: bool):
    stack = "gqa" if kind == "full" else "gqa_window"
    y, kv = attention(_rms(x, layers["attn_norm"][l], z.eps),
                      _pick(layers[stack], i, low), heads, n_real, z,
                      dict(z.ropes)[kind],
                      z.window if kind == "window" else 0, low)
    return x + y, kv


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _dense_layer(layers, x, l, j, z: Sizes, low: bool):
    lp = _pick(layers["dense"], j, low)
    h = _rms(x, layers["mlp_norm"][l], z.eps)
    return x + _rows(lambda b: (jax.nn.silu(b @ lp["w_gate"])
                                * (b @ lp["w_up"])) @ lp["w_down"], h)


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _sparse_layer(layers, x, l, j, z: Sizes, low: bool):
    lp = _pick(layers["moe"], j, low, skip=EXPERTS)
    h = _rms(x, layers["mlp_norm"][l], z.eps)
    return x + _rows(lambda b: routed(b, lp, z, low) + shared(b, lp), h), h


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _head(params, x, z: Sizes, low: bool = False):
    x = _rms(x, params["final_norm"], z.eps)
    head = params["lm_head"].astype(jnp.float32)
    return x @ (_int8(head) if low else head)


def _decoder(params, tokens, z: Sizes, n_real, control: str = ""):
    """Every layer over one sequence ``tokens`` [T] of which ``n_real`` are
    real, layer by layer: the hidden states [T, E] before the final norm,
    each window layer's (kk, v), each sparse layer's normed input."""
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    n_real = jnp.int32(n_real)
    seen: dict = {}
    rings, moe_in = [], []
    low = control == "low"

    def nth(name):
        seen[name] = seen.get(name, 0) + 1
        return jnp.int32(seen[name] - 1)

    for l, kind in enumerate(z.kinds):
        li = jnp.int32(l)
        x, kv = _attn_layer(layers, x, li, nth(kind), n_real, z, z.heads[l],
                            kind, low)
        if kind == "window":
            rings.append(kv)
        if z.sparse[l]:
            x, h = _sparse_layer(layers, x, li, nth("moe"), z, low)
            moe_in.append(h)
        else:
            x = _dense_layer(layers, x, li, nth("dense"), z, low)
    return x, rings, moe_in


def _padded(tokens, bucket: int):
    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), n


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "") -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``); ``rings``: a window layer each, (the rows ``[kk | v]``
    [W', Hkv, 2D] of the last ``W' = min(T, W)`` tokens, oldest first, the
    position of the first); ``moe_in``: a sparse layer each, its normed
    input [n_score, E] at the positions that predict the scored tokens; on
    the host."""
    z = _sizes(params, c, control)
    tokens = list(tokens)
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        at = slice(n_prompt - 1, n_prompt - 1 + n_score)    # i predicts i + 1
        x, rings, moe_in = _decoder(params, padded, z, n, control)
        logp = jax.nn.log_softmax(_head(params, x[at], z, control == "low"),
                                  axis=-1)
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    first = max(0, n - z.window)
    rows = [(np.concatenate([np.asarray(k[first:n]), np.asarray(v[first:n])],
                            axis=-1), first) for k, v in rings]
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "rings": rows, "moe_in": [np.asarray(h[at]) for h in moe_in]}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). Without the
    family's keys in it it is ``dense_gqa``'s decoder: a CPU rehearsal
    walks every cell with a tiny dense model."""
    if not c.get("layer_types"):
        return _dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    z = _sizes(params, c)
    with jax.default_matmul_precision("highest"):
        x, _r, _m = _decoder(params, jnp.asarray(tokens, jnp.int32), z,
                             len(tokens))
        return _head(params, x, z)


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _routed_block(moe, h, j, z: Sizes, low: bool):
    lp = _pick(moe, j, skip=EXPERTS)
    return _rows(lambda b: routed(b, lp, z, low), h)


def routed_block(params, c: dict, layer: int, h, control: str = "",
                 held=None):
    """The routed experts held here (``held``: another share's (first,
    count), its experts being the tree's) of sparse layer ``layer``
    (counted among the sparse layers) on ``h`` [N, E]: each position's
    weighted sum over those of its choices that are held, WITHOUT the
    shared expert: [N, E] float32 on the host. ``control``
    ``"int8_experts"``: the experts rounded to int8."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_routed_block(
            params["layers"]["moe"], jnp.asarray(h, jnp.float32),
            jnp.int32(layer), _sizes(params, c, held=held),
            control == "int8_experts"))


def shared_block(params, c: dict, layer: int, h):
    """The shared expert of sparse layer ``layer`` on ``h`` [N, E]."""
    with jax.default_matmul_precision("highest"):
        lp = _pick(params["layers"]["moe"], layer, skip=EXPERTS)
        return np.asarray(shared(jnp.asarray(h, jnp.float32), lp))
