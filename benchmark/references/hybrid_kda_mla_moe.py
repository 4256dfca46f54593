"""Plain reference forward of the Ling-3.0 hybrid decoder (``bailing_hybrid``):
float32 ``jax.numpy`` at the highest matmul precision, one sequence at a
time, no kernel, no cache, no chunked form, no batching, nothing of
``polyrl_tpu/ops`` or of ``models/decoder.py``'s blocks.

Pre-norm residual blocks, ``h = x + mixer(rms(x))``, ``out = h +
mlp(rms(h))``. Published layer ``i`` is an MLA layer where ``(i + 1) %
layer_group_size == 0`` and a KDA layer otherwise; the first
``first_k_dense_replace`` layers that are kept have the dense SwiGLU, the
rest the routed block.

KDA (H heads, key and value size ``head_dim``), token by token with
``lax.scan`` over the positions::

    q = l2norm(silu(conv(x Wq)))  k = l2norm(silu(conv(x Wk)))  v = silu(conv(x Wv))
    conv: causal, depthwise, over the last ``short_conv_kernel_size`` positions
    g_t = kda_lower_bound * sigmoid(exp(a_log_h) * (x_t Wf + f_bias))
    beta_t = sigmoid(x_t Wb)
    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t / sqrt(head_dim);  out = (rms_head(o_t) * sigmoid(x_t Wg)) Wo

MLA without a query latent, in the expanded form with a full causal
softmax::

    q = x Wq as [H, nope | rope];  [c | kr] = x Wkv_a;  c = rms(c)
    rope (interleaved pairs, absolute positions) on q's rope part and on kr
    [k_nope | v] = c Wkv_b as [H, nope | v]
    logits = (q_nope . k_nope + q_rope . kr) / sqrt(nope + rope)
    out = (softmax(logits) v * sigmoid(x Wgate)_h) Wo

Routed block (``noaux_tc``): ``s = sigmoid(x Wr)`` over ALL experts; the
choice on ``s + bias``: ``n_group`` groups, a group's score the sum of its
two highest, the best ``topk_group`` groups kept, the k highest among them
chosen; weights ``routed_scaling_factor * s_e / sum of the chosen s``.
EVERY held expert is applied to EVERY position, one at a time, with the
position's weight for it or zero (``moe_gqa.py``'s way); a choice that
falls on an expert held elsewhere (``experts_held``) adds nothing, here as
in the program. The shared expert is added whole.

The weights are the program's parameter tree, read as it is
(``polyrl_tpu/models/hybrid.py``'s docstring gives the layout). With
``layer_group_size`` absent from the configuration it is ``dense_gqa``'s
decoder: a CPU rehearsal walks every cell with a tiny dense model.

Beside the log-probabilities (``score``), ``trace`` returns what the
cell's comparison holds the program's parts to one by one: each KDA
layer's recurrent state as it stands after the sequence, and each sparse
layer's routed-block input at the scored positions, to which
``routed_block`` applies one layer's held experts. ``even_router_bias``
is part of how the benchmark makes its weights: the router's bias evened
by DeepSeek-V3's rule, with this file's own router.

``control``, for the cell's controls of ``correct``: ``"state_bf16"``
rounds the recurrent state to bfloat16 after every position;
``"int8_experts"`` rounds every routed expert's matrices to int8 with one
scale an output channel; ``"low"`` is the whole forward in the nearest
precision below the one the configuration states: both of these, and
every other matmul weight (mixers, dense and shared MLPs, router, head)
in int8 as well.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp

Q_BLOCK = 256
_BUCKET = 256
L2_EPS = 1e-6


def _dense_gqa():
    name = "benchmark_references_dense_gqa"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "dense_gqa.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rope_pairs(x, pos, theta):
    """x [T, H, R]: pairs (x[2i], x[2i+1]) turned by pos * theta**(-2i/R)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _conv(x, w):
    """Causal depthwise convolution: x [T, C], w [K, C], the kernel's last
    row on the current position."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[j] for j in range(k))


def kda(h, lp, hh: int, dd: int, lower: float, eps: float,
        state_bf16: bool = False, n_real=None):
    """The KDA mixer on ``h`` [T, d] float32; ``lp`` one layer's weights in
    float32. Returns (output [T, d], the state [H, Dk, Dv] as it stands
    after ``n_real`` positions: after all of them when None)."""
    t = h.shape[0]
    q = _l2norm(jax.nn.silu(_conv(h @ lp["wq"], lp["conv_q"])).reshape(t, hh, dd))
    k = _l2norm(jax.nn.silu(_conv(h @ lp["wk"], lp["conv_k"])).reshape(t, hh, dd))
    v = jax.nn.silu(_conv(h @ lp["wv"], lp["conv_v"])).reshape(t, hh, dd)
    f = (h @ lp["wf"] + lp["f_bias"]).reshape(t, hh, dd)
    g = lower * jax.nn.sigmoid(jnp.exp(lp["a_log"])[None, :, None] * f)
    beta = jax.nn.sigmoid(h @ lp["wb"])                        # [T, H]

    last = t if n_real is None else n_real

    def step(carry, xs):
        s, kept = carry
        q, k, v, g, beta, at = xs
        dec = jnp.exp(g)[:, :, None] * s                       # [H, Dk, Dv]
        u = v - jnp.einsum("hkv,hk->hv", dec, k)
        s = dec + beta[:, None, None] * k[:, :, None] * u[:, None, :]
        if state_bf16:
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        kept = jnp.where(at == last - 1, s, kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, q) / (dd ** 0.5)

    zero = jnp.zeros((hh, dd, dd), jnp.float32)
    (_, kept), o = jax.lax.scan(step, (zero, zero),
                                (q, k, v, g, beta, jnp.arange(t)))
    o = _rms(o, lp["o_norm"], eps).reshape(t, hh * dd)
    return (o * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"], kept


def mla(h, lp, pos, hh: int, nope: int, rope: int, vd: int, rank: int,
        theta: float, eps: float):
    """The MLA mixer on ``h`` [T, d] float32, expanded form."""
    t = h.shape[0]
    q = (h @ lp["wq"]).reshape(t, hh, nope + rope)
    kv = h @ lp["wkv_a"]
    c = _rms(kv[:, :rank], lp["kv_norm"], eps)
    kr = _rope_pairs(kv[:, None, rank:], pos, theta)           # [T, 1, rope]
    q_rope = _rope_pairs(q[..., nope:], pos, theta)
    kvb = (c @ lp["wkv_b"]).reshape(t, hh, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(kr, (t, hh, rope))], axis=-1)
    qq = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    v = kvb[..., nope:]
    scale = 1.0 / ((nope + rope) ** 0.5)
    n_blk = -(-t // Q_BLOCK)
    qp = jnp.pad(qq, ((0, n_blk * Q_BLOCK - t), (0, 0), (0, 0))).reshape(
        n_blk, Q_BLOCK, hh, nope + rope)
    kpos = jnp.arange(t)

    def block(a):
        i, qb = a
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (jnp.arange(n_blk), qp)).reshape(
        n_blk * Q_BLOCK, hh, vd)[:t]
    o = o * jax.nn.sigmoid(h @ lp["wgate"])[:, :, None]
    return o.reshape(t, hh * vd) @ lp["wo"]


# matmul weights outside the routed experts, by their names in the tree
MATMULS = ("wq", "wk", "wv", "wf", "wg", "wb", "wo", "wkv_a", "wkv_b",
           "wgate", "w_gate", "w_up", "w_down", "router", "ws_gate", "ws_up",
           "ws_down")


def _int8(w):
    """[..., in, out] as weight-only int8 holds it: one scale an output
    channel."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale


def choose(biased, top_k: int, n_group: int, topk_group: int):
    """The k experts [T, k] a position chooses from its choice scores
    ``biased`` [T, E_all]."""
    t, e = biased.shape
    if n_group > 1:
        per = e // n_group
        group = jnp.sum(jax.lax.top_k(biased.reshape(t, n_group, per), 2)[0],
                        axis=-1)
        _, keep = jax.lax.top_k(group, topk_group)
        kept = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        biased = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    return jax.lax.top_k(biased, top_k)[1]


def route(h, router, bias, top_k: int, n_group: int, topk_group: int,
          factor: float, norm_topk: bool):
    """Routing weights [T, E_all] float32, zero off the k chosen."""
    t = h.shape[0]
    s = jax.nn.sigmoid(h @ router)
    top_i = choose(s + bias, top_k, n_group, topk_group)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if norm_topk:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(t)[:, None], top_i].set(
        top_s * factor)


# the evening of a router's bias: rounds, and the first round's step
EVEN_ROUNDS = 256
EVEN_STEP = 0.02


def even_bias(h, router, rows, top_k: int, n_group: int, topk_group: int):
    """The router bias [E_all] float32 that evens the experts' loads on
    the rows ``rows`` [T] bool of ``h`` [T, d], by DeepSeek-V3's rule
    without an auxiliary loss: after every round an expert with more than
    the mean load has its bias lowered by a step and one with less has it
    raised, the step annealed to zero over ``EVEN_ROUNDS`` rounds."""
    s = jax.nn.sigmoid(h @ router)
    e = s.shape[1]
    count = rows.astype(jnp.float32)
    mean = jnp.sum(count) * top_k / e

    def one_round(i, bias):
        top_i = choose(s + bias, top_k, n_group, topk_group)
        load = jnp.zeros((e,), jnp.float32).at[top_i].add(count[:, None])
        return bias - EVEN_STEP * (1.0 - i / EVEN_ROUNDS) * jnp.sign(
            load - mean)

    return jax.lax.fori_loop(0, EVEN_ROUNDS, one_round,
                             jnp.zeros((e,), jnp.float32))


def routed_mlp(h, lp, held: tuple, top_k, n_group, topk_group, factor,
               norm_topk, int8_experts: bool = False, layer: int | None = None):
    """The routed block on ``h`` [T, d] float32 with the experts ``held``
    = (first, count) of all; ``lp`` holds one layer's router, bias and
    shared expert, and the held experts stacked [E, ..] (with ``layer``:
    the whole stacks [L, E, ..], of which that layer's are meant; an
    expert is picked out of them inside the loop, so that no layer's
    stack is ever copied)."""
    f32 = jnp.float32
    weight = route(h, lp["router"].astype(f32), lp["router_bias"].astype(f32),
                   top_k, n_group, topk_group, factor, norm_topk)
    mine = jax.lax.dynamic_slice_in_dim(weight, held[0], held[1], axis=1)
    n_held = held[1]

    def pick(stack, e):
        if layer is not None:
            stack = stack.reshape(-1, *stack.shape[2:])
            e = layer * n_held + e
        w = jax.lax.dynamic_index_in_dim(stack, e, 0, keepdims=False)
        w = w.astype(f32)
        return _int8(w) if int8_experts else w

    def one_expert(acc, ex):
        e, w = ex
        y = (jax.nn.silu(h @ pick(lp["we_gate"], e))
             * (h @ pick(lp["we_up"], e))) @ pick(lp["we_down"], e)
        return acc + w[:, None] * y, None

    out = jnp.zeros_like(h)
    if n_held:
        out, _ = jax.lax.scan(one_expert, out, (jnp.arange(n_held), mine.T))
    if "ws_gate" in lp:
        out = out + (jax.nn.silu(h @ lp["ws_gate"].astype(f32))
                     * (h @ lp["ws_up"].astype(f32))) @ lp["ws_down"].astype(f32)
    return out


def plan(c: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of each layer that is run."""
    n = int(c["num_hidden_layers"])
    kept = [int(i) for i in c.get("kept_layers") or range(n)]
    size = int(c["layer_group_size"])
    dense = int(c.get("first_k_dense_replace") or 0)
    return [("mla" if (i + 1) % size == 0 else "kda",
             "dense" if at < dense else "moe") for at, i in enumerate(kept)]


def _sizes(c: dict) -> tuple:
    held = tuple(int(v) for v in c.get("experts_held")
                 or (0, int(c["num_experts"])))
    return (tuple(plan(c)), int(c["num_attention_heads"]), int(c["head_dim"]),
            float(c["kda_lower_bound"]), float(c["rms_norm_eps"]),
            int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
            int(c["v_head_dim"]), int(c["kv_lora_rank"]),
            float(c["rope_theta"]), held, int(c["num_experts_per_tok"]),
            int(c["n_group"]), int(c["topk_group"]),
            float(c["routed_scaling_factor"]),
            bool(c.get("norm_topk_prob", True)),
            bool(c.get("tie_word_embeddings", False)))


def _decoder(params, tokens, sizes, control="", n_real=None, tap=None,
             even=None):
    """Every layer over the sequences ``tokens`` [B, T], one at a time in
    the mixers (``vmap``) and position by position in the MLPs: the hidden
    states [B, T, d] before the final norm, and what was seen on the way:
    ``states`` (each KDA layer's state [B, H, Dk, Dv] after ``n_real``
    positions), ``moe_in`` (with ``tap`` = (first, count): each sparse
    layer's routed-block input at those positions, [B, count, d]),
    ``bias`` (with ``even`` = rows [B * T] bool: each sparse layer's router
    bias evened on those rows, found at that layer and used from there
    on)."""
    (layer_plan, hh, dd, lower, eps, nope, rope, vd, rank, theta, held,
     top_k, n_group, topk_group, factor, norm_topk, _tied) = sizes
    f32 = jnp.float32
    layers = params["layers"]
    b, t = tokens.shape
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(f32)
    seen = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    taps = {"states": [], "moe_in": [], "bias": []}
    experts = ("we_gate", "we_up", "we_down")
    low = control == "low"
    for l, (mixer, mlp) in enumerate(layer_plan):
        i, j = seen[mixer], seen[mlp]
        seen[mixer], seen[mlp] = i + 1, j + 1
        lp = {k: v[i].astype(f32) for k, v in layers[mixer].items()}
        if low:
            lp = {k: _int8(v) if k in MATMULS else v for k, v in lp.items()}
        h = _rms(x, layers["attn_norm"][l].astype(f32), eps)
        if mixer == "kda":
            out, state = jax.vmap(lambda hb, lp=lp: kda(
                hb, lp, hh, dd, lower, eps, low or control == "state_bf16",
                n_real))(h)
            taps["states"].append(state)
        else:
            out = jax.vmap(lambda hb, lp=lp: mla(
                hb, lp, pos, hh, nope, rope, vd, rank, theta, eps))(h)
        x = x + out
        h = _rms(x, layers["mlp_norm"][l].astype(f32), eps).reshape(b * t, -1)
        # the experts stay whole stacks as they are stored; one is picked
        # out and cast at a time
        mp = {k: (v if k in experts else v[j].astype(f32))
              for k, v in layers[mlp].items()}
        if low:
            mp = {k: _int8(v) if k in MATMULS else v for k, v in mp.items()}
        if mlp == "dense":
            y = (jax.nn.silu(h @ mp["w_gate"]) * (h @ mp["w_up"])) \
                @ mp["w_down"]
        else:
            if even is not None:
                mp["router_bias"] = even_bias(h, mp["router"], even, top_k,
                                              n_group, topk_group)
                taps["bias"].append(mp["router_bias"])
            if tap is not None:
                taps["moe_in"].append(jax.lax.dynamic_slice_in_dim(
                    h.reshape(b, t, -1), tap[0], tap[1], axis=1))
            y = routed_mlp(h, mp, held, top_k, n_group, topk_group, factor,
                           norm_topk, low or control == "int8_experts",
                           layer=j)
        x = x + y.reshape(x.shape)
    return x, taps


def _head(params, x, sizes, control=""):
    """Final norm and the output matrix on ``x`` [.., d]: logits."""
    eps, tied = sizes[4], sizes[-1]
    x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
    head = (params["embed"].T if tied else params["lm_head"]).astype(
        jnp.float32)
    return x @ (_int8(head) if control == "low" else head)


@functools.partial(jax.jit, static_argnames=("sizes", "n_score", "all_logits",
                                             "control"))
def _score(params, tokens, n_real, first, sizes, n_score, all_logits=False,
           control=""):
    """One sequence ``tokens`` [T] of which ``n_real`` are real: the
    log-probabilities and entropies of the ``n_score`` tokens from
    position ``first`` on, with the walk's taps; or every position's
    logits."""
    # position i predicts token i + 1
    x, taps = _decoder(params, tokens[None], sizes, control, n_real,
                       None if all_logits else (first - 1, n_score))
    if all_logits:
        return _head(params, x[0], sizes, control)
    pred = jax.lax.dynamic_slice_in_dim(x[0], first - 1, n_score, 0)
    logp = jax.nn.log_softmax(_head(params, pred, sizes, control), axis=-1)
    tgt = jax.lax.dynamic_slice_in_dim(tokens, first, n_score, 0)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return lp_tok, ent, {"states": [s[0] for s in taps["states"]],
                         "moe_in": [h[0] for h in taps["moe_in"]]}


def _padded(tokens, bucket: int):
    import numpy as np

    n = len(tokens)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = tokens
    return jnp.asarray(padded), jnp.int32(n)


def score(params, c: dict, tokens, n_score: int, control: str = ""):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names)."""
    import numpy as np

    if not c.get("layer_group_size"):
        return _dense_gqa().score(params, c, tokens, n_score)
    padded, n = _padded(tokens, _BUCKET)
    with jax.default_matmul_precision("highest"):
        lp, ent, _taps = _score(params, padded, n, n - n_score, _sizes(c),
                                int(n_score), control=control)
    return np.asarray(lp), np.asarray(ent)


TRACE_BUCKET = 512


def trace(params, c: dict, tokens, n_prompt: int, n_score: int,
          control: str = "") -> dict:
    """One sequence of the hybrid family, prompt and answer: ``logprobs``
    [n_score] of the answer's first ``n_score`` tokens (``tokens[n_prompt:
    n_prompt + n_score]``), ``states`` (each KDA layer's recurrent state
    [H, Dk, Dv] float32 after ALL of ``tokens``) and ``moe_in`` (each
    sparse layer's routed-block input [n_score, d] at the positions that
    predict the scored tokens), on the host."""
    import numpy as np

    padded, n = _padded(tokens, TRACE_BUCKET)
    with jax.default_matmul_precision("highest"):
        lp, _ent, taps = _score(params, padded, n, jnp.int32(n_prompt),
                                _sizes(c), int(n_score), control=control)
    return {"logprobs": np.asarray(lp),
            "states": [np.asarray(s) for s in taps["states"]],
            "moe_in": [np.asarray(h) for h in taps["moe_in"]]}


@functools.partial(jax.jit, static_argnames=("sizes", "layer", "control"))
def _routed_block(moe, h, sizes, layer, control):
    held, top_k, n_group, topk_group, factor, norm_topk = sizes[10:16]
    experts = ("we_gate", "we_up", "we_down")
    mp = {k: (v if k in experts else v[layer].astype(jnp.float32))
          for k, v in moe.items() if not k.startswith("ws_")}
    return routed_mlp(h, mp, held, top_k, n_group, topk_group, factor,
                      norm_topk, control == "int8_experts", layer=layer)


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


@functools.partial(jax.jit, static_argnames=("sizes", "skip"))
def _even(params, ids, sizes, skip):
    b, t = ids.shape
    rows = jnp.broadcast_to(jnp.arange(t) >= skip, (b, t)).reshape(-1)
    _x, taps = _decoder(params, ids, sizes, even=rows)
    return jnp.stack(taps["bias"])


def even_router_bias(params, c: dict, ids, skip: int = 0):
    """``router_bias`` [sparse layers, E_all] float32 that evens every
    sparse layer's expert loads on the token sequences ``ids`` [B, T],
    positions from ``skip`` on: what training does to this bias (its whole
    purpose in ``noaux_tc``), done once for weights that were never
    trained. Layer by layer: a layer's bias is found from its own scores
    and used for what the later layers see. The bias that was drawn is not
    read."""
    with jax.default_matmul_precision("highest"):
        return _even(params, jnp.asarray(ids, jnp.int32), _sizes(c),
                     int(skip))


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    with jax.default_matmul_precision("highest"):
        return _score(params, jnp.asarray(tokens, jnp.int32), len(tokens), 1,
                      _sizes(c), 0, all_logits=True)
