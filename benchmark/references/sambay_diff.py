"""Plain reference forward of the SambaY decoder (``model_type``
``phi4flash``: Phi-4-mini-flash-reasoning): float32 ``jax.numpy`` at the
highest matmul precision, one sequence at a time, the scan token by token,
no kernel, no cache, no batching, nothing of ``polyrl_tpu``. It reads the
tree the program builds (the names below are that tree's) and takes every
size from the tree's shapes and the published keys.

Sources: the published config for the sizes it has (``hidden_size`` E,
``num_hidden_layers`` L, ``num_attention_heads`` Hq, ``num_key_value_heads``
Hkv, head size D = E / Hq, ``intermediate_size``, ``sliding_window`` W,
``mb_per_layer``, ``layer_norm_eps``, a tied head); for the layer kinds and
the operators, "Decoder-Hybrid-Decoder Architecture for Efficient Reasoning
with Long Generation" (SambaY, arXiv:2507.06607), Samba (arXiv:2406.07522),
Mamba (arXiv:2312.00752), Differential Transformer (arXiv:2410.05258) and
YOCO (arXiv:2405.05254). What the config does not settle is listed in
``benchmark/configs/phi-4-mini-flash-reasoning.json`` under ``assumed``; no
published implementation was at hand to check a line against (no network).

Layer ``i`` (0-based), ``h = L / 2``, ``x`` the residual stream ``[T, E]``.
Every layer: ``x = x + mixer(LN1(x))``, ``x = x + MLP(LN2(x))``, LayerNorm
with weight and bias; a final LayerNorm; logits over the tied embedding.

- Mixer of layer ``i``: ``i < h``: Mamba where ``i % mb_per_layer == 0``,
  window attention otherwise; ``i = h``: Mamba whose scan output ``m`` is
  kept; ``i = h + 1``: full attention whose K and V are kept; ``i >= h +
  2``: a gated memory unit (GMU) where ``i % mb_per_layer == 0``, cross
  attention over layer ``h + 1``'s K and V otherwise.
- MLP: ``y = (silu(x W_gate) * (x W_up)) W_down`` (the published fused ``W1
  = [W_gate | W_up]`` kept as two matrices: the same numbers), no bias.
- Mamba-1, inner width I = 2 E, state N = 16, K = 4 taps, rank R = E / 16:
  ``xi, z = split(x W_in)``; ``c[t] = silu(sum_{j<K} conv[j] * xi[t-K+1+j]
  + conv_bias)`` (depthwise, causal, zero before the sequence); ``dt, B, C
  = split(c W_x)`` (R, N, N); ``dt = softplus(dt W_dt + dt_bias)``; ``A =
  -exp(a_log)`` (``a_log`` is kept ``[N, I]``: the program keeps a state
  with the inner width last); ``s[t] = exp(dt[t] A) * s[t-1] + (dt[t] c[t])
  B[t]^T``, ``s[-1] = 0``; ``m[t] = s[t] C[t] + d_skip * c[t]``; output
  ``(m[t] * silu(z[t])) W_out``. State and exponential in float32.
- GMU: ``(silu(x W_in) * m[t]) W_out`` with ``m[t]`` layer ``h``'s scan
  output of the SAME token, before its ``z`` gate.
- Differential attention (window, full and cross alike), no positional
  encoding, scores scaled by ``1 / sqrt(D)``: queries ``[Hq/2, 2, D]``
  (adjacent heads paired), keys and values ``[Hkv/2, 2, D]``; differential
  head ``j`` reads K/V pair ``g = j // (Hq / Hkv)``; ``a1 = softmax(q[j,0]
  k[g,0]^T)``, ``a2 = softmax(q[j,1] k[g,1]^T)``, ``o_j = (a1 - lam a2)
  [v[g,0] | v[g,1]]`` (2D wide), ``o_j = RMSNorm_2D(o_j) * sub_norm * (1 -
  lam_init)`` (eps as the LayerNorms'), heads concatenated, ``W_o`` with
  bias. ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init =
  0.8 - 0.6 exp(-0.3 i)``. Window and full layers: ``W_qkv`` with bias
  (queries, keys, values in that order); cross layers: ``W_q`` with bias.
  Causal; in a window layer key ``j`` is seen from ``t`` where ``t - W < j
  <= t`` (W keys with itself).

What a serving system keeps: a Mamba layer's ``s`` and the last K - 1 rows
of ``xi``; a window layer's K and V of the last W tokens; layer ``h + 1``'s
K and V of every token, which the cross layers read. ``trace`` returns,
beside the log-probabilities, each Mamba layer's state after the
sequence's last token (``states``, ``[I, N]``) and each window layer's
``[k0 | k1 | v0 | v1]`` rows of the last W tokens with the position of the
first (``rings``).

Sized for a 20k-token request beside 7.7 GB of bfloat16 weights on a 16 GB
chip: one jitted program a kind of sublayer, run layer by layer (a layer's
weights are cast to float32 as its turn comes); attention in blocks of
``Q_BLOCK`` queries against blocks of ``K_BLOCK`` keys with a running
softmax; the MLP and the head ``ROW_BLOCK`` positions at a time.

``control``, for the benchmark's controls of ``correct`` alone:
``"state_bf16"`` rounds every Mamba state to bfloat16 after each token
(the nearest precision below the float32 the configuration states for it);
``"window_plus"`` and ``"window_minus"`` give a window layer W + 1 and W - 1
keys; ``"low"`` is the whole forward in the nearest precision below the
bfloat16 the configuration states for weights and cache: every matmul
weight (and the tied head) int8 with one scale an output channel, the
``k`` and ``v`` a token keeps int8 with one scale a head's row; the Mamba
state stays float32 (``"state_bf16"`` is its control).
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

WINDOW_OFF = {"window_plus": 1, "window_minus": -1}
# the weights that multiply activations: what ``control="low"`` rounds
MATMULS = ("w_in", "w_x", "w_dt", "w_out", "wqkv", "wq", "wo", "w_gate",
           "w_up", "w_down")


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
    hq: int
    hkv: int
    d: int
    window: int
    every: int
    eps: float


def _sizes(c: dict, control: str = "") -> Sizes:
    hq = int(c["num_attention_heads"])
    return Sizes(int(c["num_hidden_layers"]), hq,
                 int(c["num_key_value_heads"]), int(c["hidden_size"]) // hq,
                 int(c["sliding_window"]) + WINDOW_OFF.get(control, 0),
                 int(c["mb_per_layer"]), float(c["layer_norm_eps"]))


def kinds(z: Sizes) -> list[str]:
    """The mixer of each layer, in order."""
    h = z.layers // 2
    out = []
    for i in range(z.layers):
        scan = i % z.every == 0
        if i < h:
            out.append("ssm" if scan else "swa")
        elif i < h + 2:
            out.append("ssm" if i == h else "full")
        else:
            out.append("gmu" if scan else "cross")
    return out


def _ln(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32) + b.astype(jnp.float32)


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


def _shifted(x, j: int):
    """``x`` [T, C] moved ``j`` positions later, zeros before."""
    return x if j == 0 else jnp.pad(x, ((j, 0), (0, 0)))[:x.shape[0]]


# the share of a scan's channels that ``slow`` names
SLOW_SHARE = 4


def mamba(h, lp, n_real, state_bf16: bool):
    """A Mamba-1 mixer over h [T, E]: (output [T, E], m [T, I], the state
    [I, N] after ``n_real`` tokens, the ``I / SLOW_SHARE`` channels whose
    step ``dt`` was smallest over the real tokens: the ones that remember
    longest), the recurrence token by token."""
    inner = lp["w_out"].shape[0]
    n = lp["a_log"].shape[0]
    kk = lp["conv"].shape[0]
    rank = lp["w_dt"].shape[0]
    xz = h @ lp["w_in"]
    xi, z = xz[:, :inner], xz[:, inner:]
    c = jax.nn.silu(sum(lp["conv"][j] * _shifted(xi, kk - 1 - j)
                        for j in range(kk)) + lp["conv_bias"])
    dbc = c @ lp["w_x"]
    dt = jax.nn.softplus(dbc[:, :rank] @ lp["w_dt"] + lp["dt_bias"])
    bm, cm = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a = -jnp.exp(lp["a_log"]).T                             # [I, N]
    real = jnp.arange(h.shape[0]) < n_real

    def token(s, xs):
        dt_t, c_t, b_t, c_out, ok = xs
        new = (jnp.exp(dt_t[:, None] * a) * s
               + (dt_t * c_t)[:, None] * b_t[None, :])
        if state_bf16:
            # (not a cast there and back: on a TPU the compiler may keep
            # the excess precision of such a pair, and the control then
            # reads 0.0 everywhere, as its first run on the chip did)
            new = jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)
        new = jnp.where(ok, new, s)
        return new, new @ c_out + lp["d_skip"] * c_t

    s, m = jax.lax.scan(token, jnp.zeros((inner, n), jnp.float32),
                        (dt, c, bm, cm, real))
    slow = jnp.argsort(jnp.sum(jnp.where(real[:, None], dt, 0.0), axis=0))
    return ((m * jax.nn.silu(z)) @ lp["w_out"], m, s,
            slow[:inner // SLOW_SHARE])


def _attend(q, k, v, n_real, z: Sizes, window: int):
    """Both softmaxes of every differential head: q [T, Hd, 2, D] over k, v
    [T, pairs, 2, D] -> (a1 [v0 | v1], a2 [v0 | v1]) as [T, Hd, 2, 2D];
    blocks of queries against blocks of keys with a running softmax.
    ``window`` > 0: a query sees its last ``window`` keys only."""
    t, hd = q.shape[:2]
    pairs = k.shape[1]
    g = hd // pairs
    tq, tk = -(-t // Q_BLOCK) * Q_BLOCK, -(-t // K_BLOCK) * K_BLOCK
    qb = jnp.pad(q, ((0, tq - t),) + ((0, 0),) * 3).reshape(
        tq // Q_BLOCK, Q_BLOCK, pairs, g, 2, z.d)
    kb = jnp.pad(k, ((0, tk - t),) + ((0, 0),) * 3).reshape(
        tk // K_BLOCK, K_BLOCK, pairs, 2, z.d)
    vb = jnp.pad(v, ((0, tk - t),) + ((0, 0),) * 3).reshape(
        tk // K_BLOCK, K_BLOCK, pairs, 2 * z.d)

    def queries(a):
        i, qi = a
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)

        def keys(carry, b):
            m, l, acc = carry
            j, kj, vj = b
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            s = jnp.einsum("qpgcd,kpcd->pgcqk", qi, kj) / jnp.sqrt(
                jnp.float32(z.d))
            ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_real)
            if window:
                ok &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(ok[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                    alpha * acc + jnp.einsum("pgcqk,kpw->pgcqw", p, vj)), None

        shape = (pairs, g, 2, Q_BLOCK)
        init = (jnp.full((*shape, 1), -jnp.inf), jnp.zeros((*shape, 1)),
                jnp.zeros((*shape, 2 * z.d)))
        (_m, l, acc), _ = jax.lax.scan(
            keys, init, (jnp.arange(tk // K_BLOCK), kb, vb))
        return (acc / jnp.maximum(l, 1e-30)).transpose(3, 0, 1, 2, 4)

    o = jax.lax.map(queries, (jnp.arange(tq // Q_BLOCK), qb))
    return o.reshape(tq, hd, 2, 2 * z.d)[:t]


def diff_attention(h, lp, published, n_real, z: Sizes, window: int, kv,
                   low: bool = False):
    """A differential attention mixer over h [T, E]: (output [T, E], this
    layer's (k, v) [T, pairs, 2, D], None for a cross layer, which reads
    ``kv``). ``low``: what a token keeps, as an int8 cache would hold
    it."""
    t = h.shape[0]
    hd, pairs = z.hq // 2, z.hkv // 2
    if "wq" in lp:
        q, (k, v) = h @ lp["wq"] + lp["bq"], kv
    else:
        qkv = h @ lp["wqkv"] + lp["bqkv"]
        nq, nk = z.hq * z.d, z.hkv * z.d
        q = qkv[:, :nq]
        k = qkv[:, nq:nq + nk].reshape(t, pairs, 2, z.d)
        v = qkv[:, nq + nk:].reshape(t, pairs, 2, z.d)
        if low:
            k, v = _int8_rows(k), _int8_rows(v)
    o = _attend(q.reshape(t, hd, 2, z.d), k, v, n_real, z, window)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * published.astype(jnp.float32))
    lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
           - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + init)
    o = o[:, :, 0] - lam * o[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z.eps)
    o = (o * lp["sub_norm"] * (1.0 - init)).reshape(t, -1)
    return o @ lp["wo"] + lp["bo"], (None if "wq" in lp else (k, v))


def _norm1(layers, x, l, z: Sizes):
    return _ln(x, layers["attn_norm"][l], layers["attn_norm_bias"][l], z.eps)


@functools.partial(jax.jit, static_argnames=("z", "control"))
def _ssm_layer(layers, x, l, i, n_real, z: Sizes, control: str):
    y, m, s, slow = mamba(_norm1(layers, x, l, z),
                          _pick(layers["ssm"], i, control == "low"), n_real,
                          control == "state_bf16")
    return x + y, m, s, slow


@functools.partial(jax.jit, static_argnames=("z", "window", "stack", "low"))
def _attn_layer(layers, x, l, i, n_real, kv, z: Sizes, window: int,
                stack: str, low: bool):
    y, new = diff_attention(_norm1(layers, x, l, z),
                            _pick(layers[stack], i, low), l, n_real, z,
                            window, kv, low)
    return x + y, new


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _gmu_layer(layers, x, m, l, i, z: Sizes, low: bool):
    lp = _pick(layers["gmu"], i, low)
    return x + (jax.nn.silu(_norm1(layers, x, l, z) @ lp["w_in"]) * m) \
        @ lp["w_out"]


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _mlp_layer(layers, x, l, z: Sizes, low: bool):
    lp = _pick(layers["dense"], l, low)
    h = _ln(x, layers["mlp_norm"][l], layers["mlp_norm_bias"][l], z.eps)
    t = h.shape[0]
    rows = min(ROW_BLOCK, t)
    pad = -t % rows
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1])
    y = jax.lax.map(lambda b: (jax.nn.silu(b @ lp["w_gate"])
                               * (b @ lp["w_up"])) @ lp["w_down"], blocks)
    return x + y.reshape(-1, h.shape[1])[:t]


@functools.partial(jax.jit, static_argnames=("z", "low"))
def _head(params, x, z: Sizes, low: bool = False):
    x = _ln(x, params["final_norm"], params["final_norm_bias"], z.eps)
    head = params["embed"].T.astype(jnp.float32)
    return x @ (_int8(head) if low else head)


def _decoder(params, tokens, z: Sizes, n_real, control: str = ""):
    """Every layer over one sequence ``tokens`` [T] of which ``n_real`` are
    real, layer by layer: the hidden states [T, E] before the final norm,
    each Mamba layer's (state after the last real token, slowest
    channels), each window layer's (k, v)."""
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    n_real = jnp.int32(n_real)
    seen: dict = {}
    states, rings = [], []
    m = kv = None
    low = control == "low"
    for l, kind in enumerate(kinds(z)):
        stack = {"ssm": "ssm", "swa": "attn", "full": "attn"}.get(kind, kind)
        i = jnp.int32(seen.get(stack, 0))
        seen[stack] = seen.get(stack, 0) + 1
        li = jnp.int32(l)
        if kind == "ssm":
            x, m, s, slow = _ssm_layer(
                layers, x, li, i, n_real, z,
                control if control in ("low", "state_bf16") else "")
            states.append((s, slow))
        elif kind == "gmu":
            x = _gmu_layer(layers, x, m, li, i, z, low)
        else:
            window = z.window if kind == "swa" else 0
            x, new = _attn_layer(layers, x, li, i, n_real,
                                 kv if kind == "cross" else None, z, window,
                                 stack, low)
            if kind == "swa":
                rings.append(new)
            elif kind == "full":
                kv = new
        x = _mlp_layer(layers, x, li, z, low)
    return x, states, rings


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
    n_score]``); ``states``: a Mamba layer each, its state [I, N] after ALL
    of ``tokens``; ``slow``: a Mamba layer each, the quarter of its
    channels that stepped least over ``tokens`` (``mamba``), sorted;
    ``rings``: a window layer each, (the rows ``[k0 | k1 |
    v0 | v1]`` [W', pairs, 4D] of the last ``W' = min(T, W)`` tokens, oldest
    first, the position of the first); on the host."""
    import numpy as np

    z = _sizes(c, control)
    tokens = list(tokens)
    padded, n = _padded(tokens, BUCKET)
    with jax.default_matmul_precision("highest"):
        at = slice(n_prompt - 1, n_prompt - 1 + n_score)    # i predicts i + 1
        x, states, rings = _decoder(params, padded, z, n, control)
        logp = jax.nn.log_softmax(_head(params, x[at], z, control == "low"),
                                  axis=-1)
    tgt = jnp.asarray(tokens[n_prompt:n_prompt + n_score], jnp.int32)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    first = max(0, n - z.window)
    rows = [(np.concatenate([np.asarray(k[first:n]).reshape(n - first,
                                                            k.shape[1], -1),
                             np.asarray(v[first:n]).reshape(n - first,
                                                            v.shape[1], -1)],
                            axis=-1), first) for k, v in rings]
    return {"logprobs": np.asarray(lp_tok), "entropies": np.asarray(ent),
            "states": [np.asarray(s) for s, _slow in states],
            "slow": [np.sort(np.asarray(slow)) for _s, slow in states],
            "rings": rows}


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names). Without the
    family's keys in it it is ``dense_gqa``'s decoder: a CPU rehearsal
    walks every cell with a tiny dense model."""
    if not c.get("mb_per_layer"):
        return _dense_gqa().score(params, c, tokens, n_score)
    got = trace(params, c, tokens, len(tokens) - n_score, n_score, control)
    return got["logprobs"], got["entropies"]


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    z = _sizes(c)
    with jax.default_matmul_precision("highest"):
        x, _s, _r = _decoder(params, jnp.asarray(tokens, jnp.int32), z,
                             len(tokens))
        return _head(params, x, z)
