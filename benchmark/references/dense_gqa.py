"""Plain reference forward of a dense GQA decoder (Qwen2.5 / Qwen3
families): float32 ``jax.numpy`` at the highest matmul precision, no
kernels, no cache, no batching. One sequence at a time.

Follows the published architecture: token embedding, per layer pre-norm
attention (q/k/v projections with optional bias, optional per-head RMS
norm of q and k, rotate-half RoPE, grouped-query causal softmax
attention, output projection) and a pre-norm SwiGLU MLP, final RMS norm,
output head (the transposed embedding when tied).

Departures from a textbook forward, none of which changes the result:
layers are visited with ``lax.scan`` and each layer's weights are cast to
float32 inside the step, so that the float32 copy of a 7B model never
exists at once; attention is computed for blocks of queries against all
keys so that an 8k context does not need a [heads, T, T] array; logits are
formed only for the positions asked for.

The weights are the program's parameter tree (stacked layers:
``params["layers"]["wq"]`` is [L, d, Hq*hd], and so on), read as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x [T, H, D], rotate-half convention; pos [T]."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, d2, dtype=jnp.float32) * 2.0
                           / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq, D]; causal; blocks of
    queries against all keys."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    scale = 1.0 / (d ** 0.5)
    n_blk = -(-t // Q_BLOCK)
    pad = n_blk * Q_BLOCK - t
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blk, Q_BLOCK, hkv, rep, d)
    kpos = jnp.arange(t)

    def block(i, qb):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) * scale
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (jnp.arange(n_blk), qp))
    return out.reshape(n_blk * Q_BLOCK, hq, d)[:t]


MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _int8(w):
    """A matmul weight [in, out] as weight-only int8 would hold it: one
    scale an output channel, 127 steps either side of zero."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


@functools.partial(jax.jit, static_argnames=("sizes", "n_score", "weights"))
def _score(params, tokens, sizes, n_score, weights=""):
    """Log-probability and entropy of tokens[-n_score:] given what comes
    before each, for one sequence ``tokens`` [T]. ``weights="int8"`` is the
    control of ``correct`` (``benchmark/tests/control_on_chip.py``): the
    same forward with every matmul weight rounded to int8."""
    (hq, hkv, hd, theta, eps, qk_norm, bias, tied) = sizes
    f32 = jnp.float32
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(f32)

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda a: a.astype(f32), lp)
        if weights == "int8":
            lp = {k: _int8(v) if k in MATMULS else v for k, v in lp.items()}
        h = _rms(x, lp["attn_norm"], eps)
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(t, hq, hd)
        k = k.reshape(t, hkv, hd)
        v = v.reshape(t, hkv, hd)
        if qk_norm:
            q = _rms(q, lp["q_norm"], eps)
            k = _rms(k, lp["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        x = x + _attention(q, k, v).reshape(t, hq * hd) @ lp["wo"]
        h = _rms(x, lp["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(f32), eps)
    # position i predicts token i + 1
    pred = jax.lax.dynamic_slice_in_dim(x, t - n_score - 1, n_score, 0)
    head = (params["embed"].T if tied else params["lm_head"]).astype(f32)
    if weights == "int8":
        head = _int8(head)
    logp = jax.nn.log_softmax(pred @ head, axis=-1)
    tgt = tokens[t - n_score:]
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return lp_tok, ent


def score(params, c: dict, tokens, n_score: int, weights: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names)."""
    import numpy as np

    hd = int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])
    sizes = (int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
             hd, float(c["rope_theta"]), float(c["rms_norm_eps"]),
             bool(c.get("qk_norm", False)),
             bool(c.get("attention_bias", False)),
             bool(c.get("tie_word_embeddings", False)))
    with jax.default_matmul_precision("highest"):
        lp, ent = _score(params, jnp.asarray(tokens, jnp.int32), sizes,
                         int(n_score), weights)
    return np.asarray(lp), np.asarray(ent)
