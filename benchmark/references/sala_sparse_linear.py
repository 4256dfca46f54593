"""Plain reference for MiniCPM-SALA's decoder (``minicpm_sala``): block-sparse
softmax attention layers (``minicpm4``: InfLLM-V2) among linear-attention
layers (``lightning-attn``), a SwiGLU MLP in every layer, MiniCPM's muP
scalings. ``jax.numpy`` only, float32, ``jax.default_matmul_precision(
"highest")``; no kernel, no cache, no pages, no batching: one sequence at a
time, the sparse layer as a mask built per query from the equations, the
lightning layer as the token-by-token recurrence. It shares no code with
``polyrl_tpu``; it reads the configuration file's keys (published names)
and the parameter tree's arrays. Long sequences are computed in blocks of
queries and of rows so that a 26k-token request fits beside the weights;
a layer's mixer and its MLP are each ONE jitted function, so that the
layers of a kind share a compiled program a sequence length (run op by op,
every layer's every block loop was a program of its own: five minutes of a
run's compare were the compiler's).

The forward pass, for tokens ``0 .. T-1`` (``L`` = ``num_hidden_layers``
layers run here, ``mixer_types[i]`` the kind of the i-th; ``P`` =
``published_num_hidden_layers``, the depth of the published model):

1. Stem: ``x = scale_emb * E[token]``.
2. Each layer: ``x += (scale_depth / sqrt(P)) * mixer(rms(x) * w_attn)``,
   then ``x += (scale_depth / sqrt(P)) * MLP(rms(x) * w_mlp)``, ``MLP(h) =
   W_down(silu(W_gate h) * (W_up h))``; ``rms(x) = x / sqrt(mean(x^2) +
   rms_norm_eps)``. No bias anywhere.
3. ``minicpm4`` mixer (H = ``num_attention_heads`` query heads over Hkv =
   ``num_key_value_heads`` K/V heads of D = ``head_dim``; G = H / Hkv):
   a. ``q = rms_D(h Wq) * q_norm`` [H, D], ``k = rms_D(h Wk) * k_norm``
      [Hkv, D] (rms over a head's D, a learned vector [D]), ``v = h Wv``;
      no positional encoding (``attn_use_rope`` false).
   b. pooled key ``c_j = mean(k[stride j : stride j + kernel])`` for every
      j with ``stride j + kernel <= n``, where the query at position t sees
      n = t + 1 keys (``sparse_config``: kernel_size 32, kernel_stride 16).
   c. per K/V head g: ``p_h = softmax_j(q_h . c_j / sqrt(D))`` for each of
      the group's G heads, ``s_j = sum_h p_h[j]``.
   d. block score ``b_m = max(s_j : the pooled keys j whose tokens overlap
      tokens block m .. block m + block - 1)``: with block 64, kernel 32,
      stride 16 that is ``4m - 1 <= j <= 4m + 3``.
   e. blocks ``0 .. init_blocks - 1`` and the last ``window_size / block``
      blocks up to the token's own count as +inf; the chosen set is the
      ``topk`` best of the blocks ``0 .. (n - 1) // block``, the forced ones
      among them, ties to the lower block (a stable sort).
   f. for ``n <= dense_len`` every block up to the token's own.
   g. ``o_h = softmax over the tokens s <= t of the chosen blocks of (q_h .
      k_s / sqrt(D)) v_s``; ``out = (concat_h o_h * sigmoid(h Wg)) Wo``,
      the gate [H D] wide.
4. ``lightning-attn`` mixer (Hl = ``lightning_nh`` heads of Dl =
   ``lightning_head_dim``, a key and a value head each):
   a. ``q, k = rms_D(h Wq) * q_norm, rms_D(h Wk) * k_norm``, ``v = h Wv``.
   b. rope on q and k over the whole head at ``rope_theta`` (rotate-half:
      columns i and i + Dl / 2 a pair, angle ``t * theta^(-2i / Dl)``).
   c. state a head, float32, zero before token 0: ``S_t = lambda_h S_{t-1}
      + k_t^T v_t``, ``lambda_h = exp(-slopes[h])``, the slopes the
      tree's ``[Hl]`` array of the layer; ``o_t = q_t S_t / sqrt(Dl)``.
   d. ``out = (rms_D(o) * o_norm  *  sigmoid(h Wg)) Wo``.
5. Head: ``logits = W_head (rms(x) * w_final / (hidden_size /
   dim_model_base))``.

Departures from the published description: none known; what the catalog's
row has no key for (the sparse sizes, the slopes' convention, the gate's
width, the norms' vectors) is listed with its source in
``benchmark/configs/minicpm-sala.json`` under ``assumed``.

``control`` names ONE departure, for the controls of ``correct``
(``benchmark/tests/control_sala_on_chip.py``): ``state_bf16`` (every
lightning state rounded to bfloat16's mantissa after each token,
``jax.lax.reduce_precision``), ``first_blocks`` (the first ``topk``
blocks in place of the best), ``no_decay`` (lambda = 1),
``pooled_unwritten`` (the pooled keys of ONE page, the middle one of the
sequence, read as the zeros of a store never written) and ``low`` (every
matmul weight and the head int8 with one scale an output channel).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 32        # queries of a sparse layer scored and attended at once
ROW_BLOCK = 2048    # rows of an MLP at once
NEG = -1e30

CONTROLS = ("state_bf16", "first_blocks", "no_decay", "pooled_unwritten",
            "low")
MATMULS = ("wqkv", "wg", "wo", "w_gate", "w_up", "w_down")


def _dense_gqa():
    """``dense_gqa.py``, for a configuration without the family's keys (a
    ``--rehearse-cpu`` walk runs a tiny dense model under every plane)."""
    from benchmark.lib import harness

    return harness.load_named("references", "dense_gqa")


class Sizes(NamedTuple):
    d: int
    h: int
    hkv: int
    hd: int
    lh: int
    lhd: int
    eps: float
    theta: float
    kinds: tuple
    branch: float
    scale_emb: float
    head_div: float
    stride: int
    kernel: int
    block: int
    topk: int
    init_blocks: int
    window_blocks: int
    dense_len: int


def _sizes(c: dict) -> Sizes:
    sp = c["sparse_config"]
    depth = c.get("published_num_hidden_layers", c["num_hidden_layers"])
    return Sizes(
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["lightning_nh"], c["lightning_head_dim"],
        c["rms_norm_eps"], float(c["rope_theta"]), tuple(c["mixer_types"]),
        c["scale_depth"] / math.sqrt(depth), float(c["scale_emb"]),
        c["hidden_size"] / c["dim_model_base"], sp["kernel_stride"],
        sp["kernel_size"], sp["block_size"], sp["topk"], sp["init_blocks"],
        sp["window_size"] // sp["block_size"], sp["dense_len"])


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def _int8(w):
    """``w`` [in, out] rounded to int8 with one scale an output column."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127
    return jnp.round(w / scale) * scale


def _pick(stack: dict, i: int, low: bool) -> dict:
    """Layer ``i`` of a stack, float32 (its matrices int8 under ``low``)."""
    return {k: (_int8(v[i]) if low and k in MATMULS
                else v[i].astype(jnp.float32)) for k, v in stack.items()}


def _rows(f, x):
    """``f`` over ``x`` [T, ...] in blocks of ``ROW_BLOCK`` rows."""
    t = x.shape[0]
    if t <= ROW_BLOCK:
        return f(x)
    pad = -t % ROW_BLOCK
    xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(f, xs.reshape(-1, ROW_BLOCK, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:t]


# -- the sparse layer -------------------------------------------------------------


def pooled_keys(k, z: Sizes):
    """``c_j`` [J, Hkv, D] of keys ``k`` [T, Hkv, D]: every j with ``stride
    j + kernel <= T``."""
    t = k.shape[0]
    j = max((t - z.kernel) // z.stride + 1, 0)
    at = z.stride * jnp.arange(j)[:, None] + jnp.arange(z.kernel)[None, :]
    return jnp.mean(k[at], axis=1)


def _choose(q, pooled, n, m: int, z: Sizes, control: str = ""):
    """Steps 3c-3f for queries ``q`` [Q, H, D] that see ``n`` [Q] keys each,
    against ``pooled`` [J, Hkv, D] (J >= 1): a mask [Q, Hkv, m] over the
    blocks ``0 .. m - 1``."""
    nq, j = q.shape[0], pooled.shape[0]
    qg = q.reshape(nq, z.hkv, z.h // z.hkv, z.hd)
    logits = jnp.einsum("qgjd,pgd->qgjp", qg, pooled) / math.sqrt(z.hd)
    ends = z.stride * jnp.arange(j) + z.kernel
    seen = (ends[None, :] <= n[:, None])[:, None, None, :]       # [Q,1,1,J]
    p = jax.nn.softmax(jnp.where(seen, logits, NEG), axis=-1)
    s = jnp.where(seen[:, :, 0], jnp.sum(jnp.where(seen, p, 0.0), axis=2),
                  NEG)                                           # [Q, Hkv, J]
    # the pooled keys whose tokens overlap block b's
    first = z.stride * jnp.arange(j)
    blocks = jnp.arange(m)
    overlap = ((first[None, :] < z.block * (blocks[:, None] + 1))
               & (first[None, :] + z.kernel > z.block * blocks[:, None]))
    b = jnp.max(jnp.where(overlap[None, None], s[:, :, None, :], NEG),
                axis=-1)                                         # [Q, Hkv, m]
    own = (n - 1) // z.block
    upto = blocks[None, :] <= own[:, None]                       # [Q, m]
    forced = ((blocks[None, :] < z.init_blocks)
              | (blocks[None, :] > own[:, None] - z.window_blocks))
    b = jnp.where(forced[:, None], jnp.inf, b)
    b = jnp.where(upto[:, None], b, -jnp.inf)
    if control == "first_blocks":
        b = jnp.broadcast_to(jnp.where(
            upto[:, None], -blocks.astype(jnp.float32), -jnp.inf), b.shape)
    order = jnp.argsort(-b, axis=-1, stable=True)[..., :z.topk]  # [Q,Hkv,k]
    took = jnp.any(order[..., None] == blocks, axis=-2)          # [Q,Hkv,m]
    dense = (n <= z.dense_len)[:, None, None]
    return upto[:, None] & (took | dense)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def sparse_mixer(h, lp, z: Sizes, control: str = ""):
    """Step 3 over ``h`` [T, d]: (out [T, d], the chosen blocks of the LAST
    token [Hkv, M], the pooled keys [J, Hkv, D] as the choice read
    them)."""
    t = h.shape[0]
    qkv = _rows(lambda x: x @ lp["wqkv"], h)
    nq, nk = z.h * z.hd, z.hkv * z.hd
    q = _rms(qkv[:, :nq].reshape(t, z.h, z.hd), lp["q_norm"], z.eps)
    k = _rms(qkv[:, nq:nq + nk].reshape(t, z.hkv, z.hd), lp["k_norm"], z.eps)
    v = qkv[:, nq + nk:].reshape(t, z.hkv, z.hd)
    m = -(-t // z.block)
    pooled = pooled_keys(k, z)
    read = pooled
    if control == "pooled_unwritten" and pooled.shape[0]:
        r = z.block // z.stride
        page = (pooled.shape[0] // r) // 2
        read = pooled.at[page * r:(page + 1) * r].set(0.0)
    some = read if read.shape[0] else jnp.zeros((1, z.hkv, z.hd))
    g = z.h // z.hkv
    pad = -t % Q_BLOCK if t > Q_BLOCK else 0
    qb = min(Q_BLOCK, t)
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, z.h, z.hd)
    ns = jnp.pad(jnp.arange(1, t + 1), (0, pad),
                 constant_values=1).reshape(-1, qb)
    key_at = jnp.arange(t)

    def block_of_queries(xs):
        qq, n = xs
        took = _choose(qq, some, n, m, z, control)               # [Q,Hkv,M]
        mask = jnp.repeat(took, z.block, axis=-1)[..., :t]
        mask &= key_at[None, None, :] < n[:, None, None]
        sc = jnp.einsum("qgjd,kgd->qgjk", qq.reshape(qb, z.hkv, g, z.hd),
                        k) / math.sqrt(z.hd)
        pr = jax.nn.softmax(jnp.where(mask[:, :, None], sc, NEG), axis=-1)
        return jnp.einsum("qgjk,kgd->qgjd", pr, v).reshape(qb, -1), took

    o, took = jax.lax.map(block_of_queries, (qs, ns))
    o = o.reshape(-1, z.h * z.hd)[:t]
    last = took.reshape(-1, z.hkv, m)[t - 1]
    gate = jax.nn.sigmoid(_rows(lambda x: x @ lp["wg"], h))
    return _rows(lambda x: x @ lp["wo"], o * gate), last, read


# -- the lightning layer ----------------------------------------------------------


def _rope(x, theta: float):
    """Rotate-half rope on ``x`` [T, H, D] at positions ``0 .. T-1``."""
    t, _h, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def lightning_mixer(h, lp, z: Sizes, control: str = ""):
    """Step 4 over ``h`` [T, d]: (out [T, d], the state after the last
    token [Hl, Dl, Dl])."""
    t = h.shape[0]
    qkv = _rows(lambda x: x @ lp["wqkv"], h).reshape(t, 3, z.lh, z.lhd)
    q = _rope(_rms(qkv[:, 0], lp["q_norm"], z.eps), z.theta)
    k = _rope(_rms(qkv[:, 1], lp["k_norm"], z.eps), z.theta)
    v = qkv[:, 2]
    lam = jnp.exp(-lp["slopes"])
    if control == "no_decay":
        lam = jnp.ones_like(lam)

    def token(s, xs):
        qt, kt, vt = xs
        s = lam[:, None, None] * s + kt[:, :, None] * vt[:, None, :]
        if control == "state_bf16":
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hk,hkv->hv", qt, s) / math.sqrt(z.lhd)

    state, o = jax.lax.scan(
        token, jnp.zeros((z.lh, z.lhd, z.lhd), jnp.float32), (q, k, v))
    o = _rms(o, lp["o_norm"], z.eps).reshape(t, -1)
    gate = jax.nn.sigmoid(_rows(lambda x: x @ lp["wg"], h))
    return _rows(lambda x: x @ lp["wo"], o * gate), state


# -- the decoder ------------------------------------------------------------------


@jax.jit
def _mlp(h, lp):
    return _rows(lambda x: (jax.nn.silu(x @ lp["w_gate"])
                            * (x @ lp["w_up"])) @ lp["w_down"], h)


def _decoder(params, tokens, z: Sizes, control: str = "",
             upto: int | None = None):
    """Steps 1-4, with ``upto`` only the first so many layers: (x [T, d]
    before the final norm; the first lightning layer's state; the first
    sparse layer's chosen blocks at the last token [Hkv, M] and its pooled
    keys [J, Hkv, D])."""
    layers = params["layers"]
    low = control == "low"
    x = z.scale_emb * params["embed"][tokens].astype(jnp.float32)
    seen = {"minicpm4": 0, "lightning-attn": 0}
    state = chosen = pooled = None
    for l, kind in enumerate(z.kinds[:upto]):
        i = seen[kind]
        seen[kind] = i + 1
        h = _rms(x, layers["attn_norm"][l], z.eps)
        if kind == "minicpm4":
            out, took, c = sparse_mixer(h, _pick(layers["sparse"], i, low),
                                        z, control)
            if i == 0:
                chosen, pooled = took, c
        else:
            out, s = lightning_mixer(h, _pick(layers["lightning"], i, low),
                                     z, control)
            if i == 0:
                state = s
        x = x + z.branch * out
        h = _rms(x, layers["mlp_norm"][l], z.eps)
        x = x + z.branch * _mlp(h, _pick(layers["dense"], l, low))
    return x, state, chosen, pooled


def _head(params, x, z: Sizes, low: bool = False):
    w = params["lm_head"]
    w = _int8(w) if low else w.astype(jnp.float32)
    return (_rms(x, params["final_norm"], z.eps) / z.head_div) @ w


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "", upto: int | None = None) -> dict:
    """One sequence, prompt and answer: ``logprobs`` [n_score] of the
    answer's first ``n_score`` tokens (``tokens[n_prompt: n_prompt +
    n_score]``); ``state``: the FIRST lightning layer's state [Hl, Dl, Dl]
    after ALL of ``tokens``; ``chosen``: the FIRST sparse layer's chosen
    blocks of the last token [Hkv, M]; ``pooled``: its pooled keys [J, Hkv,
    D] as the choice read them; on the host. With ``upto`` only the first
    so many layers run (a control of the first two layers' mechanisms
    needs no more) and ``logprobs`` are of no model."""
    import numpy as np

    if control and control not in CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    z = _sizes(c)
    tokens = list(tokens)
    with jax.default_matmul_precision("highest"):
        x, state, chosen, pooled = _decoder(
            params, jnp.asarray(tokens, jnp.int32), z, control, upto)
        at = slice(n_prompt - 1, n_prompt - 1 + n_score)    # i predicts i + 1
        logp = jax.nn.log_softmax(_head(params, x[at], z, control == "low"),
                                  axis=-1)
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "state": np.asarray(state), "chosen": np.asarray(chosen),
            "pooled": np.asarray(pooled)}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). Without the
    family's keys in it it is ``dense_gqa``'s decoder: a CPU rehearsal
    walks every cell with a tiny dense model."""
    if not c.get("mixer_types"):
        return _dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens, control: str = ""):
    """Logits [T, V] float32 of every position of one sequence."""
    z = _sizes(c)
    with jax.default_matmul_precision("highest"):
        x, _s, _c, _p = _decoder(params, jnp.asarray(tokens, jnp.int32), z,
                                 control)
        return _head(params, x, z)
