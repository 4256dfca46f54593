"""Decoders whose layers differ in kind (``cache_spec.layer_plan``): the
Ling-3.0 / Ring hybrid family (``bailing_hybrid``) of Kimi Delta Attention
(KDA) layers, multi-head latent attention (MLA) layers and a routed MLP
with a sigmoid router behind leading dense layers; and DeepSeek-V3's
decoder (dots.vlm1 / dots.llm1 share it key for key): MLA in every layer,
the same routed MLP behind leading dense layers. One MLA block serves
both; what differs is an option of the configuration (``q_lora_rank``: a
normed query latent; ``mla_head_gate``; ``rope_scaling``: YaRN).

One block a kind, parameters stacked per kind::

    params["layers"] = {
      "attn_norm", "mlp_norm": [L, d]                  every layer
      "kda":   {wq wk wv wf wg [Lk, d, H*D], conv_q conv_k conv_v [Lk, K, H*D],
                a_log [Lk, H], f_bias [Lk, H*D], wb [Lk, d, H],
                o_norm [Lk, D], wo [Lk, H*D, d]}
      "mla":   {wq [Lm, d, H*(nope+rope)]   (or, with a query latent:
                wq_a [Lm, d, qrank], q_norm [Lm, qrank],
                wq_b [Lm, qrank, H*(nope+rope)]),
                wkv_a [Lm, d, rank+rope],
                kv_norm [Lm, rank], wkv_b [Lm, rank, H*(nope+v)],
                wgate [Lm, d, H] (with ``mla_head_gate``), wo [Lm, H*v, d]}
      "dense": {w_gate w_up [Ld, d, f], w_down [Ld, f, d]}
      "moe":   {router [Ls, d, E_all], router_bias [Ls, E_all] float32,
                we_gate we_up [Ls, E_held, d, fe], we_down [Ls, E_held, fe, d],
                ws_gate ws_up [Ls, d, fs], ws_down [Ls, fs, d]}
    }

The equations (ISSUE 33, section 1; every reading the published config
does not settle is listed in ``benchmark/configs/ling-3.0-flash.json``
under ``assumed``):

KDA, H heads of key and value size D, state ``S`` [D key, D value] a head
in float32, zero at position 0::

    q = l2norm(silu(conv(x Wq)))   k = l2norm(silu(conv(x Wk)))
    v = silu(conv(x Wv))           conv: causal, depthwise, last K positions
    g = lower * sigmoid(exp(a_log_h) * (x Wf + f_bias))   in [lower, 0]
    beta = sigmoid(x Wb)
    S' = diag(exp(g)) S ;  S = S' + beta k (v - S'^T k)^T ;  o = S^T q / sqrt(D)
    out = (rms_head(o) * sigmoid(x Wg)) Wo

MLA: the cache holds ``[rms(c) | rope(kr)]``, one row of ``rank + rope`` a
token; prefill expands it through ``wkv_b`` a block of keys at a time
(``mla_expanded``), decode folds ``wkv_b``'s key half into the query and
applies its value half after the sum (the absorbed form). The logits'
scale is ``(nope + rope) ** -0.5``, times YaRN's ``m ** 2`` where the
configuration scales its rope (``mla_scale``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import (EXPERT_KEYS, _head, _moe_mlp,
                                      _scatter_token_kv, rms_norm)
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
# positions a step of the chunked KDA form covers: within one the form
# divides by exp(sum of g), which float32 holds down to exp(-87)
_MAX_LOG_DECAY = 80.0


# the decay's bias over a head's key channels, first to last (init_params)
F_BIAS = (-8.0, -1.0)


def kda_chunk(cfg) -> int:
    return max(1, int(_MAX_LOG_DECAY // abs(cfg.kda_lower_bound)))


# -- parameters -----------------------------------------------------------------


def _counts(cfg) -> dict:
    plan = cache_spec.layer_plan(cfg)
    return {"kda": sum(p.mixer == "kda" for p in plan),
            "mla": sum(p.mixer == "mla" for p in plan),
            "dense": sum(p.mlp == "dense" for p in plan),
            "moe": sum(p.mlp == "moe" for p in plan)}


def kind_index(cfg) -> list[tuple[int, int]]:
    """For each layer: (its index among the layers of its mixer's kind,
    its index among the layers of its MLP's kind)."""
    seen: dict = {}
    out = []
    for p in cache_spec.layer_plan(cfg):
        i, j = seen.get(p.mixer, 0), seen.get(p.mlp, 0)
        seen[p.mixer], seen[p.mlp] = i + 1, j + 1
        out.append((i, j))
    return out


def init_params(rng: jax.Array, cfg) -> dict:
    """Normal(0.02) matrices, unit norms, as ``decoder.init_params``; the
    decay's bias ``f_bias`` runs from -8 to -1 over a head's key channels,
    so that a state's channels forget over a few tokens to a few thousand
    (g from -1.3 to -0.002 a token, the range the published initialisation
    of the decay spreads over; a bias of zero would forget in one token
    and the float32 state would be no part of any result), ``a_log``
    zero."""
    n = _counts(cfg)
    d, L = cfg.hidden_size, cfg.num_layers
    h, dk, dv = cache_spec.kda_dims(cfg)
    std = 0.02
    count = [0]

    def norm(*shape, dtype=None):
        count[0] += 1
        key = jax.random.fold_in(rng, count[0])
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype or cfg.dtype)

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    layers: dict = {"attn_norm": ones(L, d), "mlp_norm": ones(L, d)}
    if n["kda"]:
        k, kk = n["kda"], cfg.short_conv_kernel_size
        layers["kda"] = {
            "wq": norm(k, d, h * dk), "wk": norm(k, d, h * dk),
            "wv": norm(k, d, h * dv),
            # a convolution starts near the identity on the newest position
            "conv_q": norm(k, kk, h * dk).at[:, -1].add(1.0),
            "conv_k": norm(k, kk, h * dk).at[:, -1].add(1.0),
            "conv_v": norm(k, kk, h * dv).at[:, -1].add(1.0),
            "a_log": jnp.zeros((k, h), jnp.float32),
            "wf": norm(k, d, h * dk),
            "f_bias": jnp.broadcast_to(
                jnp.linspace(F_BIAS[0], F_BIAS[1], dk, dtype=jnp.float32),
                (k, h, dk)).reshape(k, h * dk),
            "wb": norm(k, d, h), "wg": norm(k, d, h * dv),
            "o_norm": ones(k, dv), "wo": norm(k, h * dv, d),
        }
    if n["mla"]:
        m = n["mla"]
        r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        rope, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
        qr = cfg.q_lora_rank
        query = ({"wq_a": norm(m, d, qr), "q_norm": ones(m, qr),
                  "wq_b": norm(m, qr, h * (nope + rope))} if qr
                 else {"wq": norm(m, d, h * (nope + rope))})
        layers["mla"] = {
            **query,
            "wkv_a": norm(m, d, r + rope), "kv_norm": ones(m, r),
            "wkv_b": norm(m, r, h * (nope + vd)),
            "wo": norm(m, h * vd, d),
        }
        if cfg.mla_head_gate:
            layers["mla"]["wgate"] = norm(m, d, h)
    if n["dense"]:
        f = cfg.intermediate_size
        layers["dense"] = {"w_gate": norm(n["dense"], d, f),
                           "w_up": norm(n["dense"], d, f),
                           "w_down": norm(n["dense"], f, d)}
    if n["moe"]:
        s, fe = n["moe"], cfg.moe_intermediate_size
        fs = cfg.moe_shared_expert_intermediate_size
        held = cache_spec.experts_held(cfg)[1]
        layers["moe"] = {
            "router": norm(s, d, cfg.num_experts),
            "router_bias": norm(s, cfg.num_experts, dtype=jnp.float32),
            "we_gate": norm(s, held, d, fe), "we_up": norm(s, held, d, fe),
            "we_down": norm(s, held, fe, d),
        }
        if fs:
            layers["moe"].update(ws_gate=norm(s, d, fs), ws_up=norm(s, d, fs),
                                 ws_down=norm(s, fs, d))
    params = {"embed": norm(cfg.vocab_size, d), "final_norm": ones(d),
              "layers": layers}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(d, cfg.vocab_size)
    return params


def param_specs(cfg) -> dict:
    """PartitionSpec tree matching ``init_params``: matmul weights shard
    like the dense decoder's (fsdp x tp), the experts over ``ep``, the
    small per-head vectors replicate."""
    from jax.sharding import PartitionSpec as P

    from polyrl_tpu.parallel.mesh import EP, FSDP, TP

    col, row, rep2 = P(None, FSDP, TP), P(None, TP, FSDP), P(None, None)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    rows = {"wo", "w_down", "ws_down"}

    def spec(path, leaf):
        name = path[-1].key
        if name in ("we_gate", "we_up"):
            return P(None, EP, FSDP, TP)
        if name == "we_down":
            return P(None, EP, TP, FSDP)
        if name == "embed":
            return P(TP, FSDP)
        if name == "lm_head":
            return P(FSDP, TP)
        if leaf.ndim == 3 and name.startswith(("w", "router")) \
                and name != "router_bias":
            if name in ("wb", "wgate", "router", "wkv_a"):
                return P(None, FSDP, None)
            return row if name in rows else col
        return P(*([None] * leaf.ndim)) if leaf.ndim != 2 else rep2

    return jax.tree_util.tree_map_with_path(spec, shapes)


# -- blocks ---------------------------------------------------------------------


def _rms(x, w, eps):

    return rms_norm(x, w, eps)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_inv_freq(cfg) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` frequencies of a latent layer's rope,
    float64: ``theta ** (-2i / R)``, under YaRN (``rope_scaling``,
    DeepSeek-V3's reading) divided by ``factor`` from the dimension up at
    which ``original_max_position_embeddings`` positions make ``beta_slow``
    turns (rounded up), kept below the one at which they make
    ``beta_fast`` (rounded down), blended linearly between."""
    r = cfg.qk_rope_head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    s = cfg.rope_scaling
    if s is None:
        return inv
    if s.rope_type != "yarn":
        raise NotImplementedError(
            f"rope scaling {s.rope_type!r} on a latent attention layer")

    def dim_of(turns: float) -> float:
        return (r * math.log(s.original_max_position_embeddings
                             / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(dim_of(s.beta_fast)), 0)
    high = min(math.ceil(dim_of(s.beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv / s.factor * ramp + inv * (1 - ramp)


def rope_amplitude(cfg) -> float:
    """What YaRN multiplies cos and sin by: 1 without it, and 1 where
    ``mscale`` equals ``mscale_all_dim``."""
    s = cfg.rope_scaling
    if s is None or s.rope_type != "yarn":
        return 1.0
    return (_yarn_mscale(s.factor, s.mscale)
            / _yarn_mscale(s.factor, s.mscale_all_dim))


def mla_scale(cfg) -> float:
    """The logits' scale: ``(nope + rope) ** -0.5``, times the square of
    YaRN's ``0.1 * mscale_all_dim * ln(factor) + 1`` where it is set."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s is not None and s.rope_type == "yarn" and s.mscale_all_dim:
        scale *= _yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def rope_interleaved(x, positions, inv_freq, amplitude: float = 1.0):
    """``x`` [..., T, H, R] float32, ``positions`` [..., T]: pairs
    ``(x[2i], x[2i+1])`` turned by ``pos * inv_freq[i]``."""
    r = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos = jnp.cos(ang)[..., None, :] * amplitude
    sin = jnp.sin(ang)[..., None, :] * amplitude
    pairs = x.reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _conv_window(w, window):
    """``window`` [..., K, C] (oldest first) under kernel ``w`` [K, C]."""
    return jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32),
                   axis=-2)


def _kda_inputs(cfg, lp, h_in, xc, valid=None):
    """Gates and post-convolution q, k, v of a KDA layer, float32.
    ``h_in`` [..., d] the normed input; ``xc`` [..., 3*H*D] the three
    convolutions' outputs. ``valid`` [...]: a padded position neither
    decays nor writes the state."""
    hh, dk, dv = cache_spec.kda_dims(cfg)
    lead = h_in.shape[:-1]
    xc = jax.nn.silu(xc)
    q = _l2norm(xc[..., :hh * dk].reshape(*lead, hh, dk))
    k = _l2norm(xc[..., hh * dk:2 * hh * dk].reshape(*lead, hh, dk))
    v = xc[..., 2 * hh * dk:].reshape(*lead, hh, dv)
    f = mm(h_in, lp["wf"]).astype(jnp.float32) + lp["f_bias"]
    f = f.reshape(*lead, hh, dk) * jnp.exp(lp["a_log"])[:, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(f)          # in [lower, 0]
    beta = jax.nn.sigmoid(mm(h_in, lp["wb"]).astype(jnp.float32))
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q * (dk ** -0.5), k, v, g, beta


def _kda_out(cfg, lp, h_in, o):
    """``(rms_head(o) * sigmoid(x Wg)) Wo`` from the core's ``o``
    [..., H, Dv] float32."""
    lead = h_in.shape[:-1]
    o = _rms(o, lp["o_norm"], cfg.rms_norm_eps)
    gate = jax.nn.sigmoid(mm(h_in, lp["wg"]).astype(jnp.float32))
    o = (o.reshape(*lead, -1) * gate).astype(h_in.dtype)
    return mm(o, lp["wo"])


def _kda_proj(lp, h_in):
    """The three pre-convolution projections side by side [..., 3*H*D]."""
    return jnp.concatenate(
        [mm(h_in, lp["wq"]), mm(h_in, lp["wk"]), mm(h_in, lp["wv"])], -1)


def _conv_w(lp):
    return jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], -1)


def kda_recurrent_step(state, q, k, v, g, beta):
    """One position of the recurrence for rows ``[S, H, ...]``: returns
    (new state, o [S, H, Dv]); everything float32."""
    dec = state * jnp.exp(g)[..., None]
    pred = jnp.einsum("shkv,shk->shv", dec, k, precision=_HI)
    u = beta[..., None] * (v - pred)
    new = dec + k[..., None] * u[..., None, :]
    o = jnp.einsum("shkv,shk->shv", new, q, precision=_HI)
    return new, o


def kda_chunked(state, q, k, v, g, beta, chunk: int):
    """The same recurrence over ``T`` positions in steps of ``chunk`` (T a
    multiple of it): ``state`` [B, H, Dk, Dv], q k g [B, T, H, Dk], v
    [B, T, H, Dv], beta [B, T, H], all float32. Returns (state after T,
    o [B, T, H, Dv]). Plain ``jax.numpy`` and differentiable.

    Within a step, with ``G_t`` the running sum of ``g`` from the step's
    start, ``k+ = k exp(G)``, ``k- = k exp(-G)``: the delta-corrected
    values ``u`` solve ``(I + diag(beta) tril(k+ k-^T, -1)) u = beta (v -
    k+ S0)``; ``o = q+ S0 + tril(q+ k-^T) u``; ``S = diag(exp(G_C)) S0 +
    (k- exp(G_C))^T u``."""
    b, t, h, dk = q.shape
    n = t // chunk

    def split(a):
        return a.reshape(b, n, chunk, *a.shape[2:]).swapaxes(0, 1)

    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    incl = jnp.tril(jnp.ones((chunk, chunk), bool))
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def step(s0, xs):
        q, k, v, g, beta = xs                      # [B, C, H, ...]
        cum = jnp.cumsum(g, axis=1)
        up, down = jnp.exp(cum), jnp.exp(-cum)
        kp, km, qp = k * up, k * down, q * up
        a = jnp.einsum("bthc,bihc->bhti", kp, km, precision=_HI)
        a = jnp.where(strict, a, 0.0)
        bt = beta.swapaxes(1, 2)                   # [B, H, C]
        rhs = bt[..., None] * (
            v.swapaxes(1, 2)
            - jnp.einsum("bthc,bhcv->bhtv", kp, s0, precision=_HI))
        u = jax.scipy.linalg.solve_triangular(
            eye + bt[..., None] * a, rhs, lower=True)          # [B, H, C, Dv]
        w = jnp.einsum("bthc,bihc->bhti", qp, km, precision=_HI)
        w = jnp.where(incl, w, 0.0)
        o = (jnp.einsum("bthc,bhcv->bhtv", qp, s0, precision=_HI)
             + jnp.einsum("bhti,bhiv->bhtv", w, u, precision=_HI))
        last = up[:, -1]                           # [B, H, Dk]
        s1 = (s0 * last[..., None]
              + jnp.einsum("bihc,bhiv->bhcv", km * last[:, None], u,
                           precision=_HI))
        return s1, o.swapaxes(1, 2)

    state, o = jax.lax.scan(step, state, tuple(map(split, (q, k, v, g, beta))))
    return state, o.swapaxes(0, 1).reshape(b, t, h, -1)


def _kda_sequence(cfg, lp, h_in, valid, state, conv):
    """A KDA mixer over ``h_in`` [B, T, d] from (``state`` [B, H, Dk, Dv]
    float32, ``conv`` [B, K-1, 3*H*D]) at the sequence's last valid
    position before it. ``valid`` [B, T], padding on the right. Returns
    (out [B, T, d], state, conv tail after the last valid position)."""
    b, t, _ = h_in.shape
    kk = cfg.short_conv_kernel_size
    with jax.named_scope("kda_proj"):
        x = _kda_proj(lp, h_in) * valid[..., None].astype(h_in.dtype)
        full = jnp.concatenate([conv.astype(x.dtype), x], axis=1)
        w = _conv_w(lp)
        xc = sum(full[:, j:j + t].astype(jnp.float32)
                 * w[j].astype(jnp.float32) for j in range(kk))
        q, k, v, g, beta = _kda_inputs(cfg, lp, h_in, xc, valid)
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        # the tail after the last valid position: rows n_valid .. +K-2 of full
        tail = jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, kk - 1, 0))(full, n_valid)
    with jax.named_scope("kda_core"):
        c = kda_chunk(cfg)
        pad = -t % c
        if pad:
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        state, o = kda_chunked(state.astype(jnp.float32), q, k, v, g, beta, c)
        o = o[:, :t]
    with jax.named_scope("kda_proj"):
        return _kda_out(cfg, lp, h_in, o), state, tail.astype(conv.dtype)


def _mla_qkv(cfg, lp, h_in, positions):
    """``h_in`` [..., T, d] -> (q_nope [..., T, H, nope], q_rope [..., T,
    H, rope] after rope, the queries through their normed latent where the
    configuration has one, latent rows [..., T, row] in the model's dtype:
    ``rms(c)`` beside ``rope(kr)``, zeros up to ``cache_spec.latent_row``)."""
    hh = cfg.num_heads
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    lead = h_in.shape[:-1]
    if cfg.q_lora_rank:
        cq = _rms(mm(h_in, lp["wq_a"]), lp["q_norm"], cfg.rms_norm_eps)
        q = mm(cq, lp["wq_b"])
    else:
        q = mm(h_in, lp["wq"])
    q = q.reshape(*lead, hh, nope + rope)
    kv = mm(h_in, lp["wkv_a"])
    c = _rms(kv[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
    inv, amp = rope_inv_freq(cfg), rope_amplitude(cfg)
    kr = rope_interleaved(kv[..., None, r:].astype(jnp.float32), positions,
                          inv, amp)[..., 0, :]
    q_rope = rope_interleaved(q[..., nope:].astype(jnp.float32), positions,
                              inv, amp)
    pad = cache_spec.latent_row(cfg) - r - rope
    latent = jnp.concatenate(
        [c, kr.astype(c.dtype), jnp.zeros((*lead, pad), c.dtype)], axis=-1)
    return q[..., :nope], q_rope.astype(q.dtype), latent


def _mla_out(cfg, lp, h_in, o):
    """The head-wise gate where the configuration has one, then ``Wo``;
    ``o`` [..., H, v]."""
    if cfg.mla_head_gate:
        gate = jax.nn.sigmoid(mm(h_in, lp["wgate"]).astype(jnp.float32))
        o = o.astype(jnp.float32) * gate[..., None]
    return mm(o.astype(h_in.dtype).reshape(*h_in.shape[:-1], -1), lp["wo"])


# float32 bytes the scores of one block of keys may take against all the
# queries of a call, and the fewest keys a block holds
_SCORE_BYTES = 128 << 20
_MIN_KEY_BLOCK = 128


def key_block(cfg, b: int, t: int) -> int:
    """Keys a block of ``mla_expanded`` holds for ``b`` rows of ``t``
    queries: what keeps the [B, H, T, block] float32 scores within
    ``_SCORE_BYTES``, in whole multiples of ``_MIN_KEY_BLOCK`` (at 128
    heads and a 512-token chunk: 512 keys; at 32 heads: 2048)."""
    fit = _SCORE_BYTES // (4 * b * cfg.num_heads * t)
    return max(_MIN_KEY_BLOCK, fit // _MIN_KEY_BLOCK * _MIN_KEY_BLOCK)


def mla_expanded(cfg, lp, q_nope, q_rope, latents, key_ok, q_at,
                 block: int | None = None):
    """The expanded form for a batch: queries [B, T, H, ...] against the
    latent rows ``latents`` [B, Tk, rank + rope]; ``key_ok`` [B, Tk] marks
    rows that hold a token, ``q_at`` [B, T] each query's place among the
    keys (it sees keys at or before it). Returns o [B, T, H, v] float32.

    Blocked over the keys (``block`` of them a step, ``key_block`` by
    default) with a running softmax: a block's rows are expanded through
    ``wkv_b`` when its turn comes and dropped after, so neither a whole
    prefix's K and V for all heads ([Tk, H, nope + v]: 1.07 GB at 16k keys
    and 128 heads) nor its scores ever stand at once, and a block no
    query can see (a bucket's padding past the prefix) is skipped. The
    absorbed form against the prefix would expand nothing, but multiplies
    every key by ``rank + rope`` and ``rank`` columns a head where this
    multiplies by ``nope + rope`` and ``v`` and expands once: 2.3 against
    1.2 TFLOP for a 512-token chunk over 16k keys at 128 heads."""
    hh, r, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    b, t = q_nope.shape[:2]
    tk = latents.shape[1]
    kb = min(block or key_block(cfg, b, t), tk)
    pad = -tk % kb
    if pad:
        latents = jnp.pad(latents, ((0, 0), (0, pad), (0, 0)))
        key_ok = jnp.pad(key_ok, ((0, 0), (0, pad)))
    scale = mla_scale(cfg)
    last = jnp.max(q_at)          # the furthest key any query sees

    def attend(carry, i):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(latents, i * kb, kb, 1)
        ok = jax.lax.dynamic_slice_in_dim(key_ok, i * kb, kb, 1)
        kv = mm(rows[..., :r], lp["wkv_b"]).reshape(b, kb, hh, nope + vd)
        v = kv[..., nope:]
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., r:r + rope],
                          preferred_element_type=jnp.float32)) * scale
        kpos = i * kb + jnp.arange(kb)
        seen = (ok[:, None, :]
                & (kpos[None, None, :] <= q_at[:, :, None]))[:, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, -1e30), axis=-1,
                                       keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + pv)

    def step(carry, i):
        return jax.lax.cond(i * kb <= last, attend, lambda c, _i: c,
                            carry, i), None

    init = (jnp.full((b, hh, t, 1), -1e30, jnp.float32),
            jnp.zeros((b, hh, t, 1), jnp.float32),
            jnp.zeros((b, hh, t, vd), jnp.float32))
    (_m, l, acc), _ = jax.lax.scan(step, init, jnp.arange((tk + pad) // kb))
    return (acc / jnp.maximum(l, 1e-30)).swapaxes(1, 2)


def mla_absorb(cfg, lp, q_nope, q_rope):
    """Decode's query in the latent's space: ``wkv_b``'s key half folded
    into ``q_nope``, beside the rope part, zeros up to the row: [S, H, row]."""
    hh, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    wk = lp["wkv_b"].reshape(r, hh, -1)[..., :nope]
    q_abs = jnp.einsum("shd,rhd->shr", q_nope, wk,
                       preferred_element_type=jnp.float32)
    pad = cache_spec.latent_row(cfg) - cache_spec.latent_width(cfg)
    return jnp.concatenate(
        [q_abs.astype(q_nope.dtype), q_rope,
         jnp.zeros((*q_rope.shape[:-1], pad), q_rope.dtype)], axis=-1)


def mla_unabsorb(cfg, lp, o_latent):
    """``wkv_b``'s value half applied to the attention's output over the
    latent rows ``o_latent`` [S, H, rank] -> [S, H, v]. The TPU kernel
    hands ``o_latent`` over in the pool's dtype, so the cast below is the
    oracle's alone."""
    hh, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    wv = lp["wkv_b"].reshape(r, hh, -1)[..., nope:]
    return jnp.einsum("shr,rhd->shd", o_latent.astype(lp["wkv_b"].dtype), wv,
                      preferred_element_type=jnp.float32)


# -- the layer loop -------------------------------------------------------------


def _layer_params(cfg, layers: dict, l: int) -> tuple[dict, dict]:
    """(mixer weights, MLP weights) of layer ``l``: slices of the stacks
    of its kinds; the routed experts stay whole stacks (``moe_mm`` takes
    the layer's index among the sparse layers)."""
    plan = cache_spec.layer_plan(cfg)[l]
    i, j = kind_index(cfg)[l]
    mixer = jax.tree_util.tree_map(lambda a: a[i], layers[plan.mixer])
    mlp = {k: v if k in EXPERT_KEYS
           else jax.tree_util.tree_map(lambda a: a[j], v)
           for k, v in layers[plan.mlp].items()}
    return mixer, mlp


def _mlp(cfg, x, layers, l, mlp_lp, valid):
    plan = cache_spec.layer_plan(cfg)[l]
    j = kind_index(cfg)[l][1]
    with jax.named_scope("mlp"):
        h = _rms(x, layers["mlp_norm"][l], cfg.rms_norm_eps)
        if plan.mlp == "dense":
            gate = jax.nn.silu(mm(h, mlp_lp["w_gate"]).astype(jnp.float32))
            return x + mm(gate.astype(h.dtype) * mm(h, mlp_lp["w_up"]),
                          mlp_lp["w_down"]), None
        shape = h.shape
        v = valid.reshape(-1) if valid is not None else None
        out, load = _moe_mlp(cfg, h.reshape(-1, shape[-1]), mlp_lp,
                                     v, j)
        return x + out.reshape(shape), load


def run_sequence(params, cfg, x, positions, valid, states=None,
                 prefix=None, remat: bool = False):
    """Every layer over whole (chunks of) sequences ``x`` [B, T, d] with
    right padding (``valid`` [B, T]): the trainer's forward and the
    engine's prefill. ``states``: for each KDA layer in order its (state,
    conv) rows at the chunk's start, zeros when None. ``prefix``: for each
    MLA layer in order (latent rows [B, Tp, w] of the tokens before the
    chunk, how many of them are real [B]), none when None. Returns (x,
    new states, this chunk's latent rows a MLA layer)."""
    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    b, t, _ = x.shape
    hh, dk, dv = cache_spec.kda_dims(cfg)
    new_states, latents = [], []
    for l, p in enumerate(plan):
        i = kind_index(cfg)[l][0]

        def layer(x, l=l, p=p, i=i):
            mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
            h_in = _rms(x, layers["attn_norm"][l], cfg.rms_norm_eps)
            extra = None
            if p.mixer == "kda":
                if states is None:
                    kk = cfg.short_conv_kernel_size
                    st = (jnp.zeros((b, hh, dk, dv), jnp.float32),
                          jnp.zeros((b, kk - 1, hh * (2 * dk + dv)), x.dtype))
                else:
                    st = states[i]
                out, s1, c1 = _kda_sequence(cfg, mixer_lp, h_in, valid, *st)
                extra = (s1, c1)
            elif p.mixer == "mla":
                with jax.named_scope("mla_proj"):
                    q_nope, q_rope, lat = _mla_qkv(cfg, mixer_lp, h_in,
                                                   positions)
                with jax.named_scope("mla_core"):
                    if prefix is None:
                        keys, key_ok = lat, valid
                        q_at = jnp.broadcast_to(jnp.arange(t), (b, t))
                    else:
                        pre, pre_len = prefix[i]
                        tp = pre.shape[1]
                        keys = jnp.concatenate([pre, lat], axis=1)
                        key_ok = jnp.concatenate(
                            [jnp.arange(tp)[None] < pre_len[:, None], valid],
                            axis=1)
                        q_at = jnp.broadcast_to(tp + jnp.arange(t), (b, t))
                    o = mla_expanded(cfg, mixer_lp, q_nope, q_rope, keys,
                                     key_ok, q_at)
                with jax.named_scope("mla_proj"):
                    out = _mla_out(cfg, mixer_lp, h_in, o)
                extra = lat
            else:
                raise NotImplementedError(
                    f"mixer {p.mixer!r} beside other kinds of layer")
            x = x + out
            x, _load = _mlp(cfg, x, layers, l, mlp_lp, valid)
            return x, extra

        x, extra = (jax.checkpoint(layer) if remat else layer)(x)
        (new_states if p.mixer == "kda" else latents).append(extra)
    return x, new_states, latents


def forward(params, cfg, input_ids, positions, attn_mask, remat=False,
            logits_for=None):
    """``decoder.forward`` without a cache for a model of several kinds of
    layer. A recurrent state starts from zero at a row's first valid
    token, so padding may stand on either side."""
    valid = attn_mask > 0
    x = params["embed"][input_ids]
    x, _states, _lat = run_sequence(params, cfg, x, positions, valid,
                                    remat=remat)
    return _head(cfg, params, x, logits_for)


# -- the engine's paths ---------------------------------------------------------


def _token_rows(page_ids, ps: int):
    """Rows of a pool's flat ``[N * ps, w]`` view that the pages
    ``page_ids`` [B, n] hold, in order: [B, n * ps]."""
    b, n = page_ids.shape
    return (page_ids[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(b, n * ps)


def _gather_pages(pool, page_ids):
    """``pool`` [1, N, ps, w], ``page_ids`` [B, n] -> rows [B, n*ps, w].
    By token rows of the flat view, as decode writes them
    (``decoder._scatter_token_kv``): a gather or scatter of whole pages
    made the compiler lay the pool out anew, one copy of it a use."""
    _one, n, ps, w = pool.shape
    return pool.reshape(n * ps, w)[_token_rows(page_ids, ps)]


def _scatter_tokens(pool, page_ids, rows, valid):
    """Write ``rows`` [B, T, w] to the pages ``page_ids`` [B, T // ps] of
    ``pool``; a padded position (``valid`` [B, T] false) goes to the null
    page."""
    _one, n, ps, w = pool.shape
    at = jnp.where(valid, _token_rows(page_ids, ps), 0).reshape(-1)
    flat = pool.reshape(n * ps, w).at[at].set(
        rows.reshape(-1, w).astype(pool.dtype))
    return flat.reshape(pool.shape)


def prefill(params, cfg, ids, lens, prefix_len, pools, prefix_page_ids,
            page_ids, slots):
    """A chunk of ``B`` prompts ``ids`` [B, pb] (``lens`` [B] real tokens,
    right padded) that continue ``prefix_len`` tokens (a scalar: 0 for a
    prompt's first chunk) already in ``prefix_page_ids`` [B, n_pre] and in
    the state rows ``slots`` [B]: latent rows go to ``page_ids`` [B, pb //
    page], the recurrent state after the chunk to ``slots``. Returns
    (pools, last-token logits [B, V]). A state is read only where
    ``prefix_len`` > 0: a slot's first chunk starts from zero, whatever
    the last request left there."""
    paged, state = pools
    b, pb = ids.shape
    valid = jnp.arange(pb)[None, :] < lens[:, None]
    positions = jnp.broadcast_to(prefix_len + jnp.arange(pb, dtype=jnp.int32),
                                 (b, pb))
    fresh = prefix_len == 0
    states = [(jnp.where(fresh, 0.0, s[slots]),
               jnp.where(fresh, jnp.zeros((), c.dtype), c[slots]))
              for s, c in state]
    prefix = None
    if prefix_page_ids.shape[1]:
        with jax.named_scope("mla_core"):
            prefix = [(_gather_pages(pool, prefix_page_ids),
                       jnp.broadcast_to(prefix_len, (b,))) for pool in paged]
    x = params["embed"][ids]
    x, new_states, latents = run_sequence(params, cfg, x, positions, valid,
                                          states, prefix)
    with jax.named_scope("mla_core"):
        paged = tuple(_scatter_tokens(pool, page_ids, lat, valid)
                      for pool, lat in zip(paged, latents))
    with jax.named_scope("kda_core"):
        state = tuple((s.at[slots].set(s1.astype(s.dtype)),
                       c.at[slots].set(c1.astype(c.dtype)))
                      for (s, c), (s1, c1) in zip(state, new_states))
    logits = _head(cfg, params, x, jnp.maximum(lens - 1, 0))
    return (paged, state), logits


def _set_rows(whole, rows):
    """``whole`` with its leading rows replaced by ``rows``."""
    if whole.shape[0] == rows.shape[0]:
        return rows
    return jax.lax.dynamic_update_slice_in_dim(whole, rows, 0, 0)


def load_width(cfg) -> int:
    """Entries of the load a decode step counts: a routed model's three
    (``decoder._moe_mlp``), and for a model of several kinds of layer
    three more: every (row, choice) of live rows whether or not its expert
    is held here, live rows times KDA layers, and the latent rows the
    live rows attend over, summed over the MLA layers."""
    if cache_spec.is_uniform(cfg):
        return 3 if cfg.num_experts else 0
    return 6


def kda_in_kernel(cfg) -> bool:
    """Whether a decode step of this model updates its KDA states in the
    one-pass kernel (``ops/kda_state.py``), from what its program is built
    on: a KDA layer in the plan, the state's shape and dtype, the
    backend."""
    from polyrl_tpu.ops import kda_state

    return (any(p.mixer == "kda" for p in cache_spec.layer_plan(cfg))
            and kda_state.in_kernel((0, *cache_spec.kda_dims(cfg)),
                                    cache_spec.STATE_DTYPE))


def paged_decode(params, cfg, tokens, positions, pools, page_table, seq_lens,
                 active=None, head_fn=None):
    """``decoder.forward_paged_decode`` for a model of several kinds of
    layer: one token a slot. A row without a request leaves its state
    rows as they are and writes its latent row to the null page."""
    from polyrl_tpu.ops.kda_state import kda_state_update
    from polyrl_tpu.ops.mla_attention import latent_paged_attention

    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    paged, state = list(pools[0]), list(pools[1])
    s = tokens.shape[0]
    ps = paged[0].shape[2]
    scale = mla_scale(cfg)
    live = jnp.ones((s,), bool) if active is None else active
    write_page = jnp.where(live, page_table[jnp.arange(s), seq_lens // ps], 0)
    write_off = jnp.where(live, seq_lens % ps, 0)
    attn_lens = jnp.where(live, seq_lens + 1, 0)
    n_live = jnp.sum(live.astype(jnp.int32))
    rows_read = jnp.sum(attn_lens)
    hh, dk, dv = cache_spec.kda_dims(cfg)
    r = cfg.kv_lora_rank

    x = params["embed"][tokens]
    load = jnp.zeros((load_width(cfg),), jnp.int32)
    for l, p in enumerate(plan):
        i = kind_index(cfg)[l][0]
        mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
        h_in = _rms(x, layers["attn_norm"][l], cfg.rms_norm_eps)
        if p.mixer == "kda":
            st, conv = state[i]
            with jax.named_scope("kda_proj"):
                new = _kda_proj(mixer_lp, h_in)
                window = jnp.concatenate(
                    [conv[:s], new[:, None].astype(conv.dtype)], axis=1)
                xc = _conv_window(_conv_w(mixer_lp), window)
                q, k, v, g, beta = _kda_inputs(cfg, mixer_lp, h_in, xc)
                conv = _set_rows(conv, jnp.where(
                    live[:, None, None], window[:, 1:], conv[:s]))
            with jax.named_scope("kda_core"):
                st, o = kda_state_update(st, q, k, v, g, beta, live)
            with jax.named_scope("kda_proj"):
                out = _kda_out(cfg, mixer_lp, h_in, o)
            state[i] = (st, conv)
            load = load.at[4].add(n_live)
        elif p.mixer == "mla":
            with jax.named_scope("mla_proj"):
                q_nope, q_rope, lat = _mla_qkv(cfg, mixer_lp, h_in[:, None],
                                               positions[:, None])
                q_lat = mla_absorb(cfg, mixer_lp, q_nope[:, 0], q_rope[:, 0])
            with jax.named_scope("mla_core"):
                paged[i] = _scatter_token_kv(
                    paged[i], write_page, write_off, lat)
                o_lat = latent_paged_attention(
                    q_lat, paged[i], page_table, attn_lens, r, scale)
            load = load.at[5].add(rows_read)
            with jax.named_scope("mla_proj"):
                out = _mla_out(cfg, mixer_lp, h_in,
                               mla_unabsorb(cfg, mixer_lp, o_lat))
        else:
            raise NotImplementedError(
                f"mixer {p.mixer!r} beside other kinds of layer")
        x, moe = _mlp(cfg, x + out, layers, l, mlp_lp, active)
        if moe is not None:
            load = load.at[:3].add(moe)
            load = load.at[3].add(n_live * cfg.num_experts_per_tok)
    return ((head_fn or _head)(cfg, params, x),
            (tuple(paged), tuple(state)), load)
