"""Plain reference forward of a looped language model (``model_type``
``ouro``: Ouro-2.6B; the LoopLM paper, ByteDance Seed, "Scaling Latent
Reasoning via Looped Language Models", 2025-10): float32 ``jax.numpy`` at
the highest matmul precision, one sequence at a time, no kernel, no pages,
no batching, nothing of ``polyrl_tpu``. It reads the tree the program
builds (the names below are that tree's) and takes every size from the
tree's shapes and the published keys.

ONE stack of ``L`` layers (``num_hidden_layers``) is run ``T`` times a token
(``total_ut_steps``), the SAME weights in every pass. ``x_0`` the embedding
of the tokens ``[n, E]``, ``N(.)`` an RMSNorm with a weight, ``H`` heads of
size ``D`` (one K/V head a query head), layer ``l`` in pass ``t``::

    a = Attn_l,t(N1_l(x))         x <- x + N2_l(a)
    m = (silu(N3_l(x) W1_l) * (N3_l(x) W3_l)) W2_l
    x <- x + N4_l(m)
    Attn_l,t(h): q, k, v = h Wq, h Wk, h Wv  [H, D] each
        q, k <- rope(position): rotate-half at ``rope_theta`` on all D
                columns (columns i and i + D/2 a pair)
        o[j] = softmax_{s <= i}(q[j] . k_t,l[j, s] / sqrt(D)) v_t,l[j, s]
        Attn = concat_j(o[j]) Wo
    after the last layer of EVERY pass: h_t = Nf(x), and x <- h_t
    lambda_t = sigmoid(h_t wg + bg)   p_t = lambda_t prod_{j<t}(1 - lambda_j)
    logits = h_t* Wout   at the first pass t* whose cumulative p reaches
             ``early_exit_threshold`` (the last pass takes the rest)

Pass ``t``'s layer ``l`` attends the keys and values that pass ``t``'s
layer ``l`` made: a token keeps ``T x L`` K/V pairs (the published cache
indexes ``t * L + l``). At the published threshold 1.0 the served pass is
the last for every token: ``logits = h_T Wout``, and the gates enter no
output (they are in the tree, ``exit_gate``, and not read here).

What the published config does not settle, as ``benchmark/configs/
ouro-2.6b.json`` lists it under ``assumed``: the sandwich norms and their
placement (N1 and N3 on a sublayer's input, N2 and N4 on its OUTPUT before
the residual sum); no bias on any projection and no q/k norm (no key names
one); rotate-half rope on all 128 columns, no scaling; the final norm
after every pass, its output the next pass's input; the gate's form and
that threshold 1.0 serves the last pass; that each pass keeps its own
K/V; weights N(0, 0.02) from the seed, norms 1. No published
implementation was at hand to check a line against (no network); the check
on the whole is the parameter count, 2,667,974,657.

Departures from the equations: none in the mathematics; the tree keeps
``Wq | Wk | Wv`` as one matrix ``wqkv`` (the same numbers). The passes are
a Python loop over a Python loop of layers, and the keys and values every
pass's every layer made stay in ``kept[t][l]``; one jitted program runs a
layer (its weights cast to float32 as its turn comes), attention in blocks
of ``Q_BLOCK`` queries against all keys.

``trace`` returns, beside the log-probabilities, each pass's rotated keys
and values ``[kk | v]`` of the FIRST and the LAST layer (``pass_kv``):
what a serving system's pages of that pass hold of the sequence.

``control``, for the benchmark's controls of ``correct`` alone: ``"low"``
is the whole forward in the nearest precision below the bfloat16 the
configuration states for weights and cache (every matmul weight and the
head int8 with one scale an output channel, the ``kk`` and ``v`` a token
keeps int8 with one scale a head's row); ``"passes_crossed"`` lets passes
2..T attend pass 1's keys and values and keep them as their own (the
paper's shared-cache shortcut: a different result).
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

Q_BLOCK = 512
BUCKET = 128

# the weights that multiply activations: what ``control="low"`` rounds
MATMULS = ("wqkv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


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


class Sizes(NamedTuple):
    layers: int
    passes: int
    heads: int
    d: int
    theta: float
    eps: float


def _sizes(c: dict) -> Sizes:
    if int(c["num_key_value_heads"]) != int(c["num_attention_heads"]):
        raise ValueError("one K/V head a query head")
    if float(c.get("early_exit_threshold", 1.0)) < 1.0:
        raise NotImplementedError("an exit before the last pass")
    return Sizes(int(c["num_hidden_layers"]), int(c["total_ut_steps"]),
                 int(c["num_attention_heads"]), int(c["head_dim"]),
                 float(c["rope_theta"]), float(c["rms_norm_eps"]))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _int8(w):
    """[in, out] as weight-only int8 holds it: one scale an output
    channel."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) \
        / 127.0
    return jnp.round(w / scale) * scale


def _int8_rows(x):
    """[..., n] with one int8 scale a row."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) \
        / 127.0
    return jnp.round(x / scale) * scale


def _pick(stack: dict, i, low: bool = False) -> dict:
    """Layer ``i`` of stacked weights in float32; ``low``: its matmul
    weights rounded to int8."""
    out = {}
    for k, v in stack.items():
        w = jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False).astype(
            jnp.float32)
        out[k] = _int8(w) if low and k in MATMULS else w
    return out


def rope(x, pos, theta: float):
    """``x`` [n, H, D] at positions ``pos`` [n]: rotate-half over all D
    columns."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float64) * 2.0
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv,
                                                               jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend(q, k, v):
    """Causal softmax attention a head: q, k, v [n, H, D] -> [n, H, D];
    blocks of ``Q_BLOCK`` queries against all keys."""
    n, h, d = q.shape
    block = min(Q_BLOCK, n)
    qb = jnp.pad(q, ((0, -n % block), (0, 0), (0, 0))).reshape(
        -1, block, h, d)
    kpos = jnp.arange(n)

    def queries(a):
        i, qi = a
        qpos = i * block + jnp.arange(block)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(queries, (jnp.arange(qb.shape[0]), qb))
    return o.reshape(-1, h, d)[:n]


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _layer(layers, x, l, z: Sizes, low: bool, shared_kv=None):
    """Layer ``l`` over ``x`` [n, E]: (x, the layer's rotated keys and its
    values (kk, v) [n, H, D]). ``shared_kv``: the keys and values to
    attend over and keep in place of the layer's own."""
    n = x.shape[0]
    norms = {k: layers[k][l] for k in NORMS}
    att, mlp = _pick(layers["gqa"], l, low), _pick(layers["dense"], l, low)
    h = _rms(x, norms["attn_norm"], z.eps)
    q, k, v = jnp.split((h @ att["wqkv"]).reshape(n, 3 * z.heads, z.d), 3,
                        axis=1)
    pos = jnp.arange(n)
    q, k = rope(q, pos, z.theta), rope(k, pos, z.theta)
    if low:
        k, v = _int8_rows(k), _int8_rows(v)
    if shared_kv is not None:
        k, v = shared_kv
    a = _attend(q, k, v).reshape(n, -1) @ att["wo"]
    x = x + _rms(a, norms["attn_post_norm"], z.eps)
    h = _rms(x, norms["mlp_norm"], z.eps)
    m = (jax.nn.silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"])) @ mlp["w_down"]
    return x + _rms(m, norms["mlp_post_norm"], z.eps), (k, v)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(params, x, eps: float):
    return _rms(x, params["final_norm"], eps)


@functools.partial(jax.jit, static_argnames=("low",))
def _head(params, h, low: bool = False):
    head = params["lm_head"].astype(jnp.float32)
    return h @ (_int8(head) if low else head)


def _decoder(params, tokens, z: Sizes, control: str = ""):
    """Every pass over one sequence ``tokens`` [n]: (``h_T`` [n, E], the
    last pass's output under the final norm; ``kept[t][l]``: the (kk, v)
    that pass ``t``'s layer ``l`` keeps)."""
    layers = params["layers"]
    low, crossed = control == "low", control == "passes_crossed"
    x = params["embed"][tokens].astype(jnp.float32)
    kept = []
    for t in range(z.passes):
        kept.append([])
        for l in range(z.layers):
            shared_kv = kept[0][l] if crossed and t else None
            x, kv = _layer(layers, x, jnp.int32(l), z, low, shared_kv)
            kept[t].append(kv)
        x = _final_norm(params, x, z.eps)       # h_t, the next pass's input
    return x, kept


def _padded(tokens, bucket: int):
    """Right padding: a causal forward's real positions never see it."""
    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), n


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "") -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``); ``pass_kv[t]``: the rows ``[kk | v]`` [n, H, 2D] that pass
    ``t`` keeps of every token in the FIRST and in the LAST layer, a pair;
    on the host."""
    z = _sizes(c)
    tokens = list(tokens)
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        at = slice(n_prompt - 1, n_prompt - 1 + n_score)    # i predicts i + 1
        h, kept = _decoder(params, padded, z, control)
        logp = jax.nn.log_softmax(_head(params, h[at], control == "low"),
                                  axis=-1)
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    rows = [tuple(np.concatenate([np.asarray(k[:n]), np.asarray(v[:n])],
                                 axis=-1) for k, v in (one[0], one[-1]))
            for one in kept]
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "pass_kv": rows}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). Without the
    family's keys in it it is ``dense_gqa``'s decoder: a CPU rehearsal
    walks every cell with a tiny dense model."""
    if "total_ut_steps" not in c:
        return _dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens, control: str = ""):
    """Logits [n, V] float32 of every position of one sequence."""
    z = _sizes(c)
    with jax.default_matmul_precision("highest"):
        h, _kept = _decoder(params, jnp.asarray(tokens, jnp.int32), z,
                            control)
        return _head(params, h, control == "low")
