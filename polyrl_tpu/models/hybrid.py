"""Decoders whose layers differ in kind (``cache_spec.layer_plan``): the
Ling-3.0 / Ring hybrid family (``bailing_hybrid``) of Kimi Delta Attention
(KDA) layers, multi-head latent attention (MLA) layers and a routed MLP
with a sigmoid router behind leading dense layers; and DeepSeek-V3's
decoder (dots.vlm1 / dots.llm1 share it key for key): MLA in every layer,
the same routed MLP behind leading dense layers. One MLA block serves
both; what differs is an option of the configuration (``q_lora_rank``: a
normed query latent; ``mla_head_gate``; ``rope_scaling``: YaRN). And
ZAYA1's decoder (``zaya``): compressed convolutional attention (CCA) in
every layer, which keeps a K/V pair in pages AND convolution tails in the
slot, a top-1 routed MLP whose router is an MLP on a latent that each
layer hands to the next, and both sublayers' residuals scaled. Every one
of its layers is alike, so the stacked scan would fit its trainer's
forward; it lives here because the slot state and a second tensor carried
beside the residual stream are what this file's unrolled loop threads.

And the SambaY family (``phi4flash``: Phi-4-mini-flash-reasoning): a
decoder of Mamba-1 scans and window attention, one full-attention layer
whose K/V the cross-decoder's layers read, gated memory units on the last
scan's output of the same step, differential attention without positions
in every attention layer, LayerNorm with a bias (equations below).

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
      "cca":   {w_in [Lc, d, (Hq+Hkv)*D + Hkv*D]  (q~ | k~ | va | vb),
                conv0 [Lc, K0, (Hq+Hkv)*D], conv1 [Lc, K1, Hq+Hkv, D, D],
                tau [Lc, Hkv] float32, wo [Lc, Hq*D, d]}
      "attn_res", "mlp_res": [L, 4, d]  (a_r, b_r, a_o, b_o; ``cca`` models)
      "attn_norm_bias", "mlp_norm_bias": [L, d]   (SambaY: LayerNorm)
      "ssm":   {w_in [Ls, d, 2*I]  (xi | z), conv [Ls, K, I], conv_bias [Ls, I],
                w_x [Ls, I, R + 2*N]  (dt | B | C), w_dt [Ls, R, I],
                dt_bias [Ls, I] a_log [Ls, N, I] d_skip [Ls, I] float32,
                w_out [Ls, I, d]}                  (``ssm`` and ``ssm_mem``)
      "attn":  {wqkv [La, d, (Hq + 2*Hkv)*D], bqkv, wo [La, Hq*D, d], bo [La, d],
                lq1 lk1 lq2 lk2 [La, D] float32, sub_norm [La, 2*D]}
                                                   (``swa`` and ``diff``)
      "cross": {wq [Lc, d, Hq*D], bq, wo, bo, lq1 lk1 lq2 lk2, sub_norm}
      "gmu":   {w_in [Lg, d, I], w_out [Lg, I, d]}
      "dense": {w_gate w_up [Ld, d, f], w_down [Ld, f, d]}
      "moe":   {router [Ls, d, E_all], router_bias [Ls, E_all] float32,
                we_gate we_up [Ls, E_held, d, fe], we_down [Ls, E_held, fe, d],
                ws_gate ws_up [Ls, d, fs], ws_down [Ls, fs, d]}
               (with ``router_hidden_size`` R: router_down [Ls, d, R],
                router_gamma [Ls] float32, router_norm [Ls, R],
                router_w1 router_w2 [Ls, R, R], router [Ls, R, E_all])
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
configuration scales its rope (``mla_scale``).

CCA (ISSUE 41; the nine steps are in the docstring of
``benchmark/references/cca_moe.py``, the choices the published config
does not settle in ``benchmark/configs/zaya1-8b.json`` under ``assumed``),
Hq query heads over Hkv K/V heads of size D, ``c = [q~ ; k~]``::

    [q~ | k~ | va | vb] = x W_in
    v[t] = (va[t], vb[t-1])                     half the value heads shifted
    u[t] = sum_j conv0[j] * c[t-j]              depthwise, K0 taps
    w[t] = sum_j u[t-j] @ conv1[j, g]           head g's columns, K1 taps
    q = w_q + (q~ + repeat(k~)) / 2    k = w_k + (group_mean(q~) + k~) / 2
    q = sqrt(D) q / |q|   k = tau_g sqrt(D) k / |k|   rope on the first
        ``partial_rotary_factor`` of a head's columns, after the norm
    o = softmax(q k^T / sqrt(D)) v  (causal, grouped)    out = o Wo

Pages hold the finished ``k`` and ``v``; the slot holds the last K0-1 rows
of ``c``, the last K1-1 rows of ``u`` and the last token's ``vb``. A
sublayer's residual is ``(a_r x + b_r) + (a_o F(rms(x)) + b_o)``.

SambaY (ISSUE 43; the reference's docstring, ``benchmark/references/
sambay_diff.py``, carries every line and each choice the published config
does not settle; ``benchmark/configs/phi-4-mini-flash-reasoning.json``
lists them under ``assumed``). Mamba-1, inner width I, state N a channel::

    xi, z = split(x W_in)       c[t] = silu(sum_j conv[j] * xi[t-K+1+j] + b)
    dt, B, C = split(c W_x)     dt = softplus(dt W_dt + dt_bias)
    s[t] = exp(dt[t] A) * s[t-1] + (dt[t] c[t]) B[t]^T     A = -exp(a_log)
    m[t] = s[t] C[t] + d_skip * c[t]      out = (m[t] * silu(z[t])) W_out

The state is kept ``[N, I]`` float32 (the inner width on the lanes) with
the last K-1 rows of ``xi``; a decode step updates it in place in one
kernel a layer (``ops/ssm_state.py``), prefill scans ``ssm_step`` position
by position. A gated memory unit is ``(silu(x W_1) * m) W_2``
with ``m`` the ``ssm_mem`` layer's of the same token. Differential
attention, Hd = Hq/2 heads over Hkv/2 K/V pairs, head j on pair j // 2::

    a1 = softmax(q[j,0] k[g,0]^T / sqrt(D))   a2 = softmax(q[j,1] k[g,1]^T / sqrt(D))
    o_j = rms_2D((a1 - lam a2) [v[g,0] | v[g,1]]) * sub_norm * (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    lam_init = 0.8 - 0.6 exp(-0.3 i)          (i the published layer)

A pair's two heads lie side by side in the cache (``[k0 | k1]``, ``[v0 |
v1]``, 2D = 128 wide), and a decode step's queries are ``(q[j,0] | 0)`` and
``(0 | q[j,1])``: the paged kernels written for one softmax a head of 128
then give ``a1 [v0 | v1]`` and ``a2 [v0 | v1]`` exactly. A window layer's
keys are the last ``sliding_window`` tokens, the token itself among them."""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import (EXPERT_KEYS, _head, _latent_route,
                                      _moe_mlp, _scatter_pages_kv,
                                      _scatter_token_kv, norm, rms_norm)
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
# positions a step of the chunked KDA form covers: within one the form
# divides by exp(sum of g), which float32 holds down to exp(-87)
_MAX_LOG_DECAY = 80.0


# the decay's bias over a head's key channels, first to last (init_params)
F_BIAS = (-8.0, -1.0)
# what a router's latent keeps of the layer before's (init_params)
ROUTER_GAMMA = 0.5


def kda_chunk(cfg) -> int:
    return max(1, int(_MAX_LOG_DECAY // abs(cfg.kda_lower_bound)))


# -- parameters -----------------------------------------------------------------


# the stack of ``params["layers"]`` a mixer's weights lie in, where that
# is not the mixer's own name: kinds of one shape share a stack
_STACK = {"ssm_mem": "ssm", "swa": "attn", "diff": "attn"}
# the softplus of a scan's ``dt_bias`` as drawn: log-uniform between these
DT_INIT = (0.001, 0.1)
# a differential layer's four lambda vectors as drawn
LAMBDA_STD = 0.1


def stack_of(mixer: str) -> str:
    return _STACK.get(mixer, mixer)


def _counts(cfg) -> collections.Counter:
    """Layers a stack of mixer weights and a kind of MLP holds."""
    plan = cache_spec.layer_plan(cfg)
    return collections.Counter([stack_of(p.mixer) for p in plan]
                               + [p.mlp for p in plan])


def kind_index(cfg) -> list[tuple[int, int]]:
    """For each layer: (its index among the layers of its mixer's kind,
    its index among the layers of its MLP's kind)."""
    seen: dict = {}
    out = []
    for p in cache_spec.layer_plan(cfg):
        mixer = stack_of(p.mixer)
        i, j = seen.get(mixer, 0), seen.get(p.mlp, 0)
        seen[mixer], seen[p.mlp] = i + 1, j + 1
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

    def norm(*shape, dtype=None, scale=1.0):
        count[0] += 1
        key = jax.random.fold_in(rng, count[0])
        return (jax.random.normal(key, shape, jnp.float32)
                * (std * scale)).astype(dtype or cfg.dtype)

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
    if n["cca"]:
        m, (hq, hkv, hd) = n["cca"], cache_spec.cca_dims(cfg)
        mixed = (hq + hkv) * hd
        layers["cca"] = {
            "w_in": norm(m, d, mixed + hkv * hd),
            # both convolutions start near the identity on the newest
            # position, as KDA's do
            "conv0": norm(m, cfg.cca_time0, mixed).at[:, 0].add(1.0),
            "conv1": norm(m, cfg.cca_time1, hq + hkv, hd, hd).at[:, 0].add(
                jnp.eye(hd, dtype=cfg.dtype)),
            "tau": jnp.ones((m, hkv), jnp.float32),
            "wo": norm(m, hq * hd, d),
        }
        # a_r and a_o one, b_r and b_o drawn
        one = jnp.array([1.0, 0.0, 1.0, 0.0], cfg.dtype)[None, :, None]
        for name in ("attn_res", "mlp_res"):
            layers[name] = norm(L, 4, d) * (1 - one) + one
    if n["ssm"]:
        m = n["ssm"]
        inner, ns, kk, rank = cache_spec.ssm_dims(cfg)
        count[0] += 1
        u = jax.random.uniform(jax.random.fold_in(rng, count[0]), (m, inner))
        dt = jnp.exp(u * math.log(DT_INIT[1] / DT_INIT[0])
                     + math.log(DT_INIT[0]))
        layers["ssm"] = {
            "w_in": norm(m, d, 2 * inner),
            "conv": norm(m, kk, inner).at[:, -1].add(1.0),
            "conv_bias": jnp.zeros((m, inner), cfg.dtype),
            "w_x": norm(m, inner, rank + 2 * ns),
            "w_dt": norm(m, rank, inner),
            # the inverse of softplus at ``dt``
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, ns + 1, dtype=jnp.float32))[None, :, None], (m, ns, inner)),
            "d_skip": jnp.ones((m, inner), jnp.float32),
            "w_out": norm(m, inner, d),
        }
        for name in ("attn_norm", "mlp_norm"):
            layers[name + "_bias"] = jnp.zeros((L, d), cfg.dtype)
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

    def diff_heads(m):
        lam = {k: norm(m, hd, dtype=jnp.float32, scale=LAMBDA_STD / std)
               for k in ("lq1", "lk1", "lq2", "lk2")}
        return {**lam, "sub_norm": ones(m, 2 * hd),
                "wo": norm(m, hq * hd, d), "bo": jnp.zeros((m, d), cfg.dtype)}

    if n["attn"]:
        m, wide = n["attn"], (hq + 2 * hkv) * hd
        layers["attn"] = {"wqkv": norm(m, d, wide),
                          "bqkv": jnp.zeros((m, wide), cfg.dtype),
                          **diff_heads(m)}
    if n["cross"]:
        m = n["cross"]
        layers["cross"] = {"wq": norm(m, d, hq * hd),
                           "bq": jnp.zeros((m, hq * hd), cfg.dtype),
                           **diff_heads(m)}
    if n["gmu"]:
        inner = cache_spec.ssm_dims(cfg)[0]
        layers["gmu"] = {"w_in": norm(n["gmu"], d, inner),
                         "w_out": norm(n["gmu"], inner, d)}
    if n["dense"]:
        f = cfg.intermediate_size
        layers["dense"] = {"w_gate": norm(n["dense"], d, f),
                           "w_up": norm(n["dense"], d, f),
                           "w_down": norm(n["dense"], f, d)}
    if n["moe"]:
        s, fe = n["moe"], cfg.moe_intermediate_size
        fs = cfg.moe_shared_expert_intermediate_size
        held = cache_spec.experts_held(cfg)[1]
        r = cfg.router_hidden_size
        # the router MLP's matrices by their fan-in: at 0.02 three layers
        # shrink a normed latent to logits a hundredth wide
        fan = {"scale": r ** -0.5 / std} if r else {}
        router = ({"router_down": norm(s, d, r),
                   "router_gamma": jnp.full((s,), ROUTER_GAMMA, jnp.float32),
                   "router_norm": ones(s, r),
                   "router_w1": norm(s, r, r, **fan),
                   "router_w2": norm(s, r, r, **fan),
                   "router": norm(s, r, cfg.num_experts, **fan)} if r
                  else {"router": norm(s, d, cfg.num_experts)})
        layers["moe"] = {
            **router,
            "router_bias": norm(s, cfg.num_experts, dtype=jnp.float32),
            "we_gate": norm(s, held, d, fe), "we_up": norm(s, held, d, fe),
            "we_down": norm(s, held, fe, d),
        }
        if fs:
            layers["moe"].update(ws_gate=norm(s, d, fs), ws_up=norm(s, d, fs),
                                 ws_down=norm(s, fs, d))
    params = {"embed": norm(cfg.vocab_size, d), "final_norm": ones(d),
              "layers": layers}
    if cfg.mb_per_layer:
        params["final_norm_bias"] = jnp.zeros((d,), cfg.dtype)
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
    rows = {"wo", "w_down", "ws_down", "w_out"}

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
            if name in ("wb", "wgate", "router", "wkv_a", "w_x", "w_dt"):
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


def cca_rope(cfg, x, positions):
    """Rope on the first ``partial_rotary_factor`` of each head's columns
    of ``x`` [B, T, H, D] float32 (rotate-half within them, frequencies
    ``theta ** (-2i / rot)``), the rest as they are."""
    d = x.shape[-1]
    rot = int(d * cfg.partial_rotary_factor)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                    / rot))
    ang = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _cca_mix(cfg, lp, proj, positions, tails):
    """Steps 2 to 5 of a CCA layer over ``proj`` [B, T, (Hq+Hkv)*D + Hkv*D]
    (``x W_in``) from the tails ``(latent [B, K0-1, C], mixed [B, K1-1, C],
    value [B, Hkv*D/2])`` of the tokens before: returns (q [B, T, Hq, D],
    k [B, T, Hkv, D], v [B, T, Hkv, D] in the model's dtype, and the three
    sequences a later token's tails are rows of: ``[tail | chunk]`` of the
    latents, of the first convolution's output, of the second value half)."""
    hq, hkv, hd = cache_spec.cca_dims(cfg)
    b, t, _ = proj.shape
    mixed, half = (hq + hkv) * hd, hkv * hd // 2
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    f32 = jnp.float32
    c_tail, u_tail, vb_tail = tails
    full_c = jnp.concatenate([c_tail.astype(proj.dtype), proj[..., :mixed]], 1)
    va, vb = proj[..., mixed:mixed + half], proj[..., mixed + half:]
    full_vb = jnp.concatenate([vb_tail[:, None].astype(proj.dtype), vb], 1)
    v = jnp.concatenate([va, full_vb[:, :t]], -1).reshape(b, t, hkv, hd)
    w0 = lp["conv0"].astype(f32)
    u = sum(full_c[:, k0 - 1 - j:k0 - 1 - j + t].astype(f32) * w0[j]
            for j in range(k0)).astype(proj.dtype)
    full_u = jnp.concatenate([u_tail.astype(proj.dtype), u], 1)
    heads = full_u.reshape(b, -1, hq + hkv, hd)
    w = sum(jnp.einsum("btgd,gde->btge", heads[:, k1 - 1 - j:k1 - 1 - j + t],
                       lp["conv1"][j], preferred_element_type=f32)
            for j in range(k1))
    c = proj[..., :mixed].astype(f32).reshape(b, t, hq + hkv, hd)
    qm = c[:, :, :hq].reshape(b, t, hkv, hq // hkv, hd)
    km = c[:, :, hq:]
    q = w[:, :, :hq] + ((qm + km[:, :, :, None]) / 2).reshape(b, t, hq, hd)
    k = w[:, :, hq:] + (jnp.mean(qm, axis=3) + km) / 2
    q = _l2norm(q) * hd ** 0.5
    k = _l2norm(k) * (hd ** 0.5 * lp["tau"].astype(f32)[:, None])
    qk = cca_rope(cfg, jnp.concatenate([q, k], axis=2),
                  positions).astype(proj.dtype)
    return qk[:, :, :hq], qk[:, :, hq:], v, (full_c, full_u, full_vb)


def _cca_tails(cfg, fulls, n_valid):
    """The tails after ``n_valid`` tokens of a chunk ([B], or one whole
    number for every row: a decode step's 1), from ``_cca_mix``'s ``[tail |
    chunk]`` sequences."""
    full_c, full_u, full_vb = fulls

    def rows(full, k):
        if isinstance(n_valid, int):
            return full[:, n_valid:n_valid + k]
        return jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, k, 0))(full, n_valid)

    return (rows(full_c, cfg.cca_time0 - 1), rows(full_u, cfg.cca_time1 - 1),
            rows(full_vb, 1)[:, 0])


def _cca_sequence(cfg, lp, h_in, positions, valid, tails, prefix):
    """A CCA mixer over ``h_in`` [B, T, d] (``valid`` [B, T], padding on the
    right) from the tails at the chunk's start and, with ``prefix`` = ((k,
    v) [B, Tp, Hkv, D] of the tokens before, how many are real [B]), over
    their keys too. Returns (out [B, T, d], this chunk's (k, v), the tails
    after the last valid position)."""
    from polyrl_tpu.ops.attention import attention

    b, t, _ = h_in.shape
    with jax.named_scope("cca_proj"):
        proj = mm(h_in, lp["w_in"])
    with jax.named_scope("cca_mix"):
        q, k, v, fulls = _cca_mix(cfg, lp, proj, positions, tails)
        new_tails = _cca_tails(cfg, fulls,
                               jnp.sum(valid.astype(jnp.int32), axis=1))
    with jax.named_scope("attn_core"):
        keys, values, key_ok, tp = k, v, valid, 0
        if prefix is not None:
            (pk, pv), pre_len = prefix
            tp = pk.shape[1]
            keys = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            values = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            key_ok = jnp.concatenate(
                [jnp.arange(tp)[None] < pre_len[:, None], valid], axis=1)
        seen = (jnp.arange(tp + t)[None, :] <= tp + jnp.arange(t)[:, None])
        mask = (seen[None] & key_ok[:, None, :])[:, None]
        o = attention(q, keys, values, mask=mask).reshape(b, t, -1)
    with jax.named_scope("cca_proj"):
        return mm(o, lp["wo"]), (k, v), new_tails


def _residual(x, out, res):
    """A sublayer's residual: ``x + out``, or with ``res`` [4, d] = (a_r,
    b_r, a_o, b_o) the scaled ``(a_r x + b_r) + (a_o out + b_o)``."""
    if res is None:
        return x + out
    a_r, b_r, a_o, b_o = res.astype(jnp.float32)
    return (a_r * x.astype(jnp.float32) + b_r
            + a_o * out.astype(jnp.float32) + b_o).astype(x.dtype)



# -- the SambaY family's blocks ---------------------------------------------


def _ssm_inputs(cfg, lp, h_in, tail):
    """Everything of a Mamba layer before its recurrence, for ``h_in``
    [B, T, d] after the convolution tail ``tail`` [B, K-1, I] (the rows of
    ``xi`` before the chunk): (c [B, T, I] float32 after convolution and
    silu, z [B, T, I], dt [B, T, I] float32 after the softplus, B and C
    [B, T, N] float32, ``[tail | xi]`` [B, K-1+T, I])."""
    inner, n, kk, rank = cache_spec.ssm_dims(cfg)
    t = h_in.shape[1]
    xz = mm(h_in, lp["w_in"])
    xi, z = xz[..., :inner], xz[..., inner:]
    full = jnp.concatenate([tail.astype(xi.dtype), xi], axis=1)
    w = lp["conv"].astype(jnp.float32)
    c = jax.nn.silu(sum(full[:, j:j + t].astype(jnp.float32) * w[j]
                        for j in range(kk))
                    + lp["conv_bias"].astype(jnp.float32))
    dbc = mm(c.astype(h_in.dtype), lp["w_x"])
    dt = jax.nn.softplus(mm(dbc[..., :rank], lp["w_dt"]).astype(jnp.float32)
                         + lp["dt_bias"])
    bm = dbc[..., rank:rank + n].astype(jnp.float32)
    cm = dbc[..., rank + n:].astype(jnp.float32)
    return c, z, dt, bm, cm, full


def ssm_step(lp, state, c, dt, bm, cm):
    """One position of the selective scan for rows ``state`` [S, N, I]:
    (new state, m [S, I]); everything float32."""
    a = -jnp.exp(lp["a_log"])                              # [N, I]
    new = (jnp.exp(dt[:, None, :] * a) * state
           + (dt * c)[:, None, :] * bm[:, :, None])
    m = jnp.sum(new * cm[:, :, None], axis=1) + lp["d_skip"] * c
    return new, m


def ssm_scan(lp, state, c, dt, bm, cm):
    """``ssm_step`` over ``T`` positions, one after the other: ``state``
    [B, N, I], c dt [B, T, I], bm cm [B, T, N] -> (state after T, m [B, T,
    I]). A position with ``dt`` 0 leaves the state as it is. The one form
    that decode and the reference have: a blocked form (16 positions an
    iteration, the decays between them in one fusion) cost a 512-token
    chunk's nine scans 14.0 ms on the chip where this costs 4.7
    (``tools/trace_prefill_chunk.py``; PERF.md section 6, PR 43)."""
    def step(s, xs):
        return ssm_step(lp, s, *xs)

    state, m = jax.lax.scan(step, state, tuple(x.swapaxes(0, 1)
                                               for x in (c, dt, bm, cm)))
    return state, m.swapaxes(0, 1)


def _ssm_out(lp, m, z):
    return mm((m * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype),
              lp["w_out"])


def _ssm_sequence(cfg, lp, h_in, valid, state, tail):
    """A Mamba mixer over ``h_in`` [B, T, d] (``valid`` [B, T], padding on
    the right) from (``state`` [B, N, I] float32, ``tail`` [B, K-1, I]):
    (out [B, T, d], the scan's output ``m`` [B, T, I] float32 before its
    gate, state, tail after the last valid position)."""
    kk = cfg.ssm_conv_kernel
    with jax.named_scope("ssm_proj"):
        h_in = h_in * valid[..., None].astype(h_in.dtype)
        c, z, dt, bm, cm, full = _ssm_inputs(cfg, lp, h_in, tail)
        dt = jnp.where(valid[..., None], dt, 0.0)
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        new_tail = jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, kk - 1, 0))(full, n_valid)
    with jax.named_scope("ssm_core"):
        state, m = ssm_scan(lp, state.astype(jnp.float32), c, dt, bm, cm)
    with jax.named_scope("ssm_proj"):
        return _ssm_out(lp, m, z), m, state, new_tail.astype(tail.dtype)


def _gmu(lp, h_in, m):
    """A gated memory unit: ``(silu(x W_1) * m) W_2``."""
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(mm(h_in, lp["w_in"]).astype(jnp.float32))
        return mm((gate * m).astype(h_in.dtype), lp["w_out"])


def lambda_init(published: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published)


def _diff_qkv(cfg, lp, h_in):
    """(q [..., Hd, 2, D], and for a layer with keys of its own k and v
    [..., pairs, 2D]: a pair's two heads side by side, as they are
    cached)."""
    hd, pairs, width = cache_spec.diff_dims(cfg)
    lead = h_in.shape[:-1]
    with jax.named_scope("attn_qkv"):
        if "wq" in lp:
            q = mm(h_in, lp["wq"]) + lp["bq"]
            return q.reshape(*lead, hd, 2, width // 2), None, None
        qkv = mm(h_in, lp["wqkv"]) + lp["bqkv"]
        nq, nk = hd * width, pairs * width
        return (qkv[..., :nq].reshape(*lead, hd, 2, width // 2),
                qkv[..., nq:nq + nk].reshape(*lead, pairs, width),
                qkv[..., nq + nk:].reshape(*lead, pairs, width))


def paired_queries(q):
    """``q`` [..., Hd, 2, D] -> [..., 2 * Hd, 2D]: ``(q[j,0] | 0)`` and
    ``(0 | q[j,1])``, which against a pair's ``[k0 | k1]`` score ``q[j,0]
    k0`` and ``q[j,1] k1`` exactly."""
    zero = jnp.zeros_like(q[..., 0, :])
    both = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2)
    return both.reshape(*q.shape[:-3], 2 * q.shape[-3], 2 * q.shape[-1])


def _diff_out(cfg, lp, o, published: int):
    """From the two softmaxes' outputs ``o`` [..., Hd, 2, 2D] (``a1 [v0 |
    v1]``, ``a2 [v0 | v1]``) to the sublayer's output: the difference
    under lambda, the head-wise norm, ``(1 - lam_init)``, ``W_o``."""
    f32 = jnp.float32
    with jax.named_scope("diff_mix"):
        init = lambda_init(published)
        lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
               - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + init)
        o = o.astype(f32)
        o = rms_norm(o[..., 0, :] - lam * o[..., 1, :], lp["sub_norm"],
                     cfg.rms_norm_eps) * (1.0 - init)
        o = o.reshape(*o.shape[:-2], -1).astype(lp["wo"].dtype)
    with jax.named_scope("attn_out"):
        return mm(o, lp["wo"]) + lp["bo"]


def diff_attention(cfg, q, k, v, q_at, k_at, window: int = 0):
    """Both softmaxes of every differential head for a batch: ``q`` [B, T,
    Hd, 2, D] against keys ``k`` and values ``v`` [B, Tk, pairs, 2D];
    ``q_at`` [B, T] and ``k_at`` [B, Tk] are positions in the sequence
    (``k_at`` < 0: no key there); a query sees the keys at or before it,
    with ``window`` only the last ``window`` of them. Returns o [B, T, Hd,
    2, 2D] float32. Blocked over the keys with a running softmax, as
    ``mla_expanded`` is, so that the scores of 16k keys never stand at
    once, and a block no query sees is skipped."""
    b, t, hd = q.shape[:3]
    tk, pairs, width = k.shape[1:]
    d = width // 2
    kb = min(key_block(cfg, b, t), tk)
    pad = -tk % kb
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_at = jnp.pad(k_at, ((0, 0), (0, pad)), constant_values=-1)
    qg = q.reshape(b, t, pairs, hd // pairs, 2, d)
    scale = d ** -0.5
    last = jnp.max(q_at)

    def attend(carry, i):
        m, l, acc = carry
        kk = jax.lax.dynamic_slice_in_dim(k, i * kb, kb, 1)
        vv = jax.lax.dynamic_slice_in_dim(v, i * kb, kb, 1)
        at = jax.lax.dynamic_slice_in_dim(k_at, i * kb, kb, 1)
        s = jnp.einsum("bqgjcd,bkgcd->bgjcqk", qg,
                       kk.reshape(b, kb, pairs, 2, d),
                       preferred_element_type=jnp.float32) * scale
        seen = (at[:, None, :] >= 0) & (at[:, None, :] <= q_at[:, :, None])
        if window:
            seen &= at[:, None, :] > q_at[:, :, None] - window
        seen = seen[:, None, None, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, -1e30), axis=-1,
                                       keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bgjcqk,bkgw->bgjcqw", p.astype(vv.dtype), vv,
                        preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + pv)

    def step(carry, i):
        at = jax.lax.dynamic_slice_in_dim(k_at, i * kb, kb, 1)
        near = jnp.any((at >= 0) & (at <= last))
        return jax.lax.cond(near, attend, lambda c, _i: c, carry, i), None

    shape = (b, pairs, hd // pairs, 2, t)
    init = (jnp.full((*shape, 1), -1e30, jnp.float32),
            jnp.zeros((*shape, 1), jnp.float32),
            jnp.zeros((*shape, width), jnp.float32))
    (_m, l, acc), _ = jax.lax.scan(step, init, jnp.arange((tk + pad) // kb))
    o = acc / jnp.maximum(l, 1e-30)                   # [B, g, j, c, T, 2D]
    return o.transpose(0, 4, 1, 2, 3, 5).reshape(b, t, hd, 2, width)


def ring_pages(cfg, slots, ps: int):
    """The pages of a window layer's ring that belong to the slots
    ``slots`` [B]: [B, window / ps], fixed when the pool was made
    (``cache_spec.Ring``)."""
    n = cfg.sliding_window // ps
    return (1 + slots[:, None] * n
            + jnp.arange(n, dtype=jnp.int32)[None, :]).astype(jnp.int32)


def _ring_read(cfg, ring, slots, prefix_len):
    """What the rings of ``slots`` [B] hold after ``prefix_len`` tokens
    (a scalar): (k, v [B, window, pairs, 2D], the position of the token
    each row holds [B, window], -1 where it holds none). Token ``t`` lies
    at row ``t % window``."""
    w = cfg.sliding_window
    k, v = _gather_slabs_kv(ring, ring_pages(cfg, slots, ring[0].shape[2]))
    newest = prefix_len - 1
    at = newest - (newest - jnp.arange(w, dtype=jnp.int32)) % w
    at = jnp.where((prefix_len > 0) & (at >= 0), at, -1)
    return k, v, jnp.broadcast_to(at, (slots.shape[0], w))


def _ring_write(cfg, ring, slots, prefix_len, lens, kv, old):
    """The rings of ``slots`` [B] after a chunk's (k, v) [B, T, pairs, 2D]
    of ``lens`` [B] real tokens that follow ``prefix_len``: each of the
    last ``window`` of them at its position modulo the window, every other
    row as ``old`` has it (``_ring_read``'s (k, v) at the chunk's start).
    The whole ring is written back, by pages (``_scatter_slabs``)."""
    w = cfg.sliding_window
    ps = ring[0].shape[2]
    r = jnp.arange(w, dtype=jnp.int32)[None, :]
    last = lens[:, None] - 1
    # the chunk's newest token that lies at ring row r
    c = last - (prefix_len + last - r) % w
    pages = ring_pages(cfg, slots, ps)

    def one(a, new, was):
        rows = jnp.take_along_axis(new, jnp.maximum(c, 0)[:, :, None, None],
                                   axis=1)
        rows = jnp.where((c >= 0)[:, :, None, None], rows.astype(a.dtype),
                         was.astype(a.dtype))
        return _scatter_slabs(a, pages, rows)

    return one(ring[0], kv[0], old[0]), one(ring[1], kv[1], old[1])

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
    # the heads are cut out of the PRODUCT: without the barrier XLA moves
    # the reshape onto the weight and writes a layer's ``wq_b`` out anew,
    # heads major, before every product (75 MB a layer at 128 heads)
    q = jax.lax.optimization_barrier(q).reshape(*lead, hh, nope + rope)
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


def mla_absorb(cfg, lp, q_nope, q_rope, in_stack=None):
    """Decode's query in the latent's space: ``wkv_b``'s key half folded
    into ``q_nope``, beside the rope part, zeros up to the row: [S, H, row].

    ``in_stack``, where ``mla_in_kernel`` says so: (the stacked ``wkv_b``
    [Lm, rank, H * (nope + v)], this layer's index in it), and the product
    reads the layer where it lies (``ops/mla_proj.py``; interpreted off a
    TPU: tests alone get there). Without it the einsum on ``lp``'s slice,
    which XLA feeds from a copy of the layer with the heads major, and
    which is the oracle."""
    from polyrl_tpu.ops import mla_proj

    hh, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if in_stack is not None:
        q_abs = mla_proj.absorb(q_nope, *in_stack,
                                interpret=jax.default_backend() != "tpu")
    else:
        wk = lp["wkv_b"].reshape(r, hh, -1)[..., :nope]
        q_abs = jnp.einsum("shd,rhd->shr", q_nope, wk,
                           preferred_element_type=jnp.float32)
    pad = cache_spec.latent_row(cfg) - cache_spec.latent_width(cfg)
    return jnp.concatenate(
        [q_abs.astype(q_nope.dtype), q_rope,
         jnp.zeros((*q_rope.shape[:-1], pad), q_rope.dtype)], axis=-1)


def mla_unabsorb(cfg, lp, o_latent, in_stack=None):
    """``wkv_b``'s value half applied to the attention's output over the
    latent rows ``o_latent`` [S, H, rank] -> [S, H, v] float32;
    ``in_stack`` as for ``mla_absorb``. The TPU kernel hands ``o_latent``
    over in the pool's dtype, so the cast is the oracle's alone."""
    from polyrl_tpu.ops import mla_proj

    if in_stack is not None:
        return mla_proj.unabsorb(o_latent, *in_stack,
                                 interpret=jax.default_backend() != "tpu")
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
    mixer = jax.tree_util.tree_map(lambda a: a[i],
                                   layers[stack_of(plan.mixer)])
    mlp = {k: v if k in EXPERT_KEYS
           else jax.tree_util.tree_map(lambda a: a[j], v)
           for k, v in layers[plan.mlp].items()}
    return mixer, mlp


def _res(layers, name: str, l: int):
    """Layer ``l``'s residual scales, None for a model without them."""
    return layers[name][l] if name in layers else None


def router_carry(cfg, lead: tuple):
    """What the first layer's router is handed: zeros [*lead, R] float32,
    None for a router without a carried latent."""
    r = cfg.router_hidden_size
    return jnp.zeros((*lead, r), jnp.float32) if r else None


def _mlp(cfg, x, layers, l, mlp_lp, valid, carry=None):
    """The MLP sublayer of layer ``l`` with its residual: (x, the routed
    block's load or None, the router's latent for the next layer or
    None). ``carry`` [..., R]: the layer before's latent of each token."""
    plan = cache_spec.layer_plan(cfg)[l]
    j = kind_index(cfg)[l][1]
    res = _res(layers, "mlp_res", l)
    with jax.named_scope("mlp"):
        h = norm(layers, "mlp_norm", x, cfg.rms_norm_eps, l)
        if plan.mlp == "dense":
            gate = jax.nn.silu(mm(h, mlp_lp["w_gate"]).astype(jnp.float32))
            out = mm(gate.astype(h.dtype) * mm(h, mlp_lp["w_up"]),
                     mlp_lp["w_down"])
            return _residual(x, out, res), None, carry
        shape = h.shape
        rows = h.reshape(-1, shape[-1])
        v = valid.reshape(-1) if valid is not None else None
        route = None
        if carry is not None:
            with jax.named_scope("moe_route"):
                *route, latent = _latent_route(
                    cfg, rows, mlp_lp, carry.reshape(rows.shape[0], -1))
            carry = latent.reshape(carry.shape)
        out, load = _moe_mlp(cfg, rows, mlp_lp, v, j, route)
        return _residual(x, out.reshape(shape), res), load, carry


def run_sequence(params, cfg, x, positions, valid, states=None,
                 prefix=None, remat: bool = False):
    """Every layer over whole (chunks of) sequences ``x`` [B, T, d] with
    right padding (``valid`` [B, T]): the trainer's forward and the
    engine's prefill. ``states``: for each layer that keeps a slot, in
    order, its rows at the chunk's start (KDA: (state, conv); CCA: the
    three tails), zeros when None. ``prefix``: for each layer that keeps
    pages, in order, (what the tokens before the chunk keep there [B, Tp,
    ..]: latent rows for MLA, a (k, v) pair for CCA; how many of them are
    real [B]), none when None. Returns (x, new states, what this chunk
    keeps in pages a paged layer). A router's carried latent starts from
    zero at the first layer and never leaves the call: it is a token's
    own, layer to layer; so are the SambaY family's two: the ``ssm_mem``
    layer's scan output, which the gated memory units read, and the
    ``diff`` layer's keys and values with those before the chunk, which
    the ``cross`` layers read. A ``swa`` layer's state is (k, v, the
    positions they hold) of its ring before the chunk, None for none, and
    what it returns for it the chunk's (k, v)."""
    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    b, t, _ = x.shape
    new_states, latents = [], []
    carry = router_carry(cfg, (b, t))
    if cfg.mb_per_layer:
        carry = {}
    index = cache_spec.pool_index(cfg)
    for l, p in enumerate(plan):
        at_pages, at_slot = index[l]

        def layer(x, carry, l=l, p=p, at_pages=at_pages, at_slot=at_slot):
            mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
            h_in = norm(layers, "attn_norm", x, cfg.rms_norm_eps, l)
            st = states[at_slot] if states is not None and at_slot is not None \
                else _zero_state(cfg, p, b, x.dtype)
            kept = state = None
            if p.mixer in _SAMBAY:
                out, carry, kept, state = _sambay_sequence(
                    cfg, p, mixer_lp, h_in, positions, valid, st,
                    None if prefix is None or at_pages is None
                    else prefix[at_pages], carry)
            elif p.mixer == "kda":
                out, s1, c1 = _kda_sequence(cfg, mixer_lp, h_in, valid, *st)
                state = (s1, c1)
            elif p.mixer == "mla":
                with jax.named_scope("mla_proj"):
                    q_nope, q_rope, lat = _mla_qkv(cfg, mixer_lp, h_in,
                                                   positions)
                with jax.named_scope("mla_core"):
                    if prefix is None:
                        keys, key_ok = lat, valid
                        q_at = jnp.broadcast_to(jnp.arange(t), (b, t))
                    else:
                        pre, pre_len = prefix[at_pages]
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
                kept = lat
            elif p.mixer == "cca":
                out, kept, state = _cca_sequence(
                    cfg, mixer_lp, h_in, positions, valid, st,
                    None if prefix is None else prefix[at_pages])
            else:
                raise NotImplementedError(
                    f"mixer {p.mixer!r} beside other kinds of layer")
            x = _residual(x, out, _res(layers, "attn_res", l))
            x, _load, carry = _mlp(cfg, x, layers, l, mlp_lp, valid, carry)
            return x, carry, kept, state

        x, carry, kept, state = (jax.checkpoint(layer) if remat
                                 else layer)(x, carry)
        if at_pages is not None and p.mixer != "cross":
            latents.append(kept)
        if at_slot is not None:
            new_states.append(state)
    return x, new_states, latents


_SAMBAY = ("ssm", "ssm_mem", "swa", "diff", "gmu", "cross")


def _sambay_sequence(cfg, p, lp, h_in, positions, valid, st, prefix, carry):
    """The mixer of a SambaY layer of kind ``p.mixer`` over a chunk: (out,
    what later layers of the call read (``carry``: ``m`` of the
    ``ssm_mem`` layer, ``kv`` of the ``diff`` layer), what the chunk keeps
    in pages, its slot's new state)."""
    kept = state = None
    if p.mixer in ("ssm", "ssm_mem"):
        out, m, s1, tail = _ssm_sequence(cfg, lp, h_in, valid, *st)
        state = (s1, tail)
        if p.mixer == "ssm_mem":
            carry = {**carry, "m": m}
    elif p.mixer == "gmu":
        out = _gmu(lp, h_in, carry["m"])
    else:
        q, k, v = _diff_qkv(cfg, lp, h_in)
        at = jnp.where(valid, positions, -1)
        if p.mixer == "swa":
            scope, window = "swa_core", cfg.sliding_window
            keys, values, k_at = k, v, at
            if st is not None:
                keys = jnp.concatenate([st[0].astype(k.dtype), k], axis=1)
                values = jnp.concatenate([st[1].astype(v.dtype), v], axis=1)
                k_at = jnp.concatenate([st[2], at], axis=1)
            state = (k, v)
        else:
            scope, window = "attn_core", 0
            if p.mixer == "diff":
                keys, values, k_at, kept = k, v, at, (k, v)
                if prefix is not None:
                    (pk, pv), pre_len = prefix
                    tp = jnp.arange(pk.shape[1], dtype=jnp.int32)[None]
                    keys = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
                    values = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
                    k_at = jnp.concatenate(
                        [jnp.where(tp < pre_len[:, None], tp, -1), at], axis=1)
                carry = {**carry, "kv": (keys, values, k_at)}
            else:
                keys, values, k_at = carry["kv"]
        with jax.named_scope(scope):
            o = diff_attention(cfg, q, keys, values, positions, k_at, window)
        out = _diff_out(cfg, lp, o, p.published)
    return out, carry, kept, state


def _zero_state(cfg, p, b: int, dtype):
    """What a layer's slot holds before a sequence's first token (a
    window layer's ring: nothing, None)."""
    slot = cache_spec.slot_part(cache_spec.layer_cache(cfg, p, dtype))
    if slot is None or isinstance(slot, cache_spec.Ring):
        return None
    return tuple(jnp.zeros((b, *shape), dt) for _n, shape, dt in slot.arrays)


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
    the last request left there (and from an empty ring: a window layer's
    rows hold no token until one is written)."""
    paged, state = pools
    b, pb = ids.shape
    valid = jnp.arange(pb)[None, :] < lens[:, None]
    positions = jnp.broadcast_to(prefix_len + jnp.arange(pb, dtype=jnp.int32),
                                 (b, pb))
    fresh = prefix_len == 0
    # the mixer of each layer that keeps a slot, in order (a window
    # layer's slot is its ring)
    slot_mixers = [p.mixer for p, (_pg, at) in zip(
        cache_spec.layer_plan(cfg), cache_spec.pool_index(cfg))
        if at is not None]
    states = []
    for rows, mixer in zip(state, slot_mixers):
        if mixer == "swa":
            with jax.named_scope("swa_core"):
                states.append(_ring_read(cfg, rows, slots, prefix_len))
        else:
            states.append(tuple(
                jnp.where(fresh, jnp.zeros((), a.dtype), a[slots])
                for a in rows))
    prefix = None
    # a K/V pair's scope is ``attn_core``, a latent pool's ``mla_core``
    pair = bool(paged) and isinstance(paged[0], tuple)
    if prefix_page_ids.shape[1]:
        with jax.named_scope("attn_core" if pair else "mla_core"):
            pre_len = jnp.broadcast_to(prefix_len, (b,))
            prefix = [(_gather_prefix(cfg, pool, prefix_page_ids), pre_len)
                      for pool in paged]
    x = params["embed"][ids]
    x, new_states, latents = run_sequence(params, cfg, x, positions, valid,
                                          states, prefix)
    with jax.named_scope("attn_core" if pair else "mla_core"):
        paged = tuple(_scatter_chunk(cfg, pool, page_ids, kept, valid)
                      for pool, kept in zip(paged, latents))
    written = []
    for rows, new, was, mixer in zip(state, new_states, states, slot_mixers):
        with jax.named_scope(_SLOT_SCOPE[mixer]):
            written.append(
                _ring_write(cfg, rows, slots, prefix_len, lens, new, was[:2])
                if mixer == "swa"
                else tuple(a.at[slots].set(a1.astype(a.dtype))
                           for a, a1 in zip(rows, new)))
    state = tuple(written)
    logits = _head(cfg, params, x, jnp.maximum(lens - 1, 0))
    return (paged, state), logits


# the scope a layer's slot is written back under, by its mixer
_SLOT_SCOPE = {"kda": "kda_core", "cca": "cca_mix", "ssm": "ssm_core",
               "ssm_mem": "ssm_core", "swa": "swa_core"}


def _kv_by_slabs(cfg) -> bool:
    """Whether a K/V pair's pages move as slabs of the pool's ``[H N, ps,
    w]`` view (``_gather_slabs_kv``, ``_scatter_slabs``) and not as rows of
    its ``[H N, ps w]`` view (``_gather_kv``, ``_scatter_kv``): for the
    SambaY family's pools of ten heads, which by rows XLA lays out anew,
    1.7-2.7 GB of temporaries beside a 900 MB pool that the chip's
    compiler refused. The CCA model's programs are the rows' and are kept
    to the byte (an accepted benchmark cell's); one form for both is for
    the PR that can measure that cell (ROADMAP Queue 1 item 21)."""
    return bool(cfg.mb_per_layer)


def _gather_prefix(cfg, pool, page_ids):
    """What the pages ``page_ids`` [B, n] of a paged layer's ``pool``
    hold: a latent pool's rows [B, n * ps, w], a K/V pair's (k, v) each
    [B, n * ps, H, w]."""
    if not isinstance(pool, tuple):
        return _gather_pages(pool, page_ids)
    return (_gather_slabs_kv if _kv_by_slabs(cfg) else _gather_kv)(
        pool, page_ids)


def _scatter_chunk(cfg, pool, page_ids, kept, valid):
    """``pool`` with a chunk's ``kept`` written to its pages ``page_ids``
    [B, T // ps]. (A K/V pair's padded position lands in the tail of the
    row's last page or in the null page, where no length reaches it.)"""
    if not isinstance(pool, tuple):
        return _scatter_tokens(pool, page_ids, kept, valid)
    if _kv_by_slabs(cfg):
        return tuple(_scatter_slabs(a, page_ids, x)
                     for a, x in zip(pool, kept))
    return _scatter_kv(pool, page_ids, kept)


def _gather_kv(pool, page_ids):
    """A K/V pair of ``[Hkv, N, ps, D]`` pools, ``page_ids`` [B, n] -> (k,
    v) each [B, n*ps, Hkv, D]: whole pages, as the uniform decoder's
    prefix gather takes them."""
    def one(a):
        hkv, _n, ps, d = a.shape
        b, n = page_ids.shape
        return a[:, page_ids].transpose(1, 2, 3, 0, 4).reshape(
            b, n * ps, hkv, d)

    return one(pool[0]), one(pool[1])


def _slab_ids(a, page_ids):
    """Slabs of ``a``'s ``[H * N, ps, w]`` view that hold the pages
    ``page_ids`` [B, n] of every head, head-major: [H * B * n]. The view's
    last two dimensions are the pool's own tiles, so a gather or a scatter
    over its slabs leaves the pool's layout as it is."""
    h, n = a.shape[:2]
    return (jnp.arange(h, dtype=jnp.int32)[:, None] * n
            + page_ids.reshape(-1)[None, :].astype(jnp.int32)).reshape(-1)


def _gather_slabs_kv(pool, page_ids):
    """``_gather_kv`` by slabs (``_slab_ids``): 2,560 slabs of 16 KB for a
    prefix of 256 pages, where token rows of the flat view were 164k
    gathers of 256 B (1.4 ms less of a chunk's 56 ms on the chip)."""
    b, n_pg = page_ids.shape

    def one(a):
        h, n, ps, w = a.shape
        got = a.reshape(h * n, ps, w)[_slab_ids(a, page_ids)]
        return got.reshape(h, b, n_pg * ps, w).transpose(1, 2, 0, 3)

    return one(pool[0]), one(pool[1])


def _scatter_slabs(a, page_ids, rows):
    """``a`` [H, N, ps, w] with the pages ``page_ids`` [B, n] holding
    ``rows`` [B, n * ps, H, w], by slabs (``_slab_ids``)."""
    h, n, ps, w = a.shape
    b, n_pg = page_ids.shape
    pages = rows.reshape(b * n_pg, ps, h, w).transpose(2, 0, 1, 3)
    return a.reshape(h * n, ps, w).at[_slab_ids(a, page_ids)].set(
        pages.reshape(-1, ps, w).astype(a.dtype)).reshape(a.shape)


def _scatter_kv(pool, page_ids, kv):
    """Write a chunk's (k, v), each [B, T, Hkv, D], to the pages
    ``page_ids`` [B, T // ps] of a K/V pair of pools, whole pages at a
    time (``blocks._scatter_pages_kv``). A padded position lands in the
    tail of the row's last page or in the null page, where no length
    reaches it."""
    def one(a, new):
        hkv, _n, ps, d = a.shape
        b, t = new.shape[:2]
        pages = new.reshape(b * (t // ps), ps, hkv, d).transpose(2, 0, 1, 3)
        return _scatter_pages_kv(a, page_ids.reshape(-1), pages)

    return one(pool[0], kv[0]), one(pool[1], kv[1])


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
    live rows attend over, summed over the MLA layers; with CCA layers a
    seventh: live rows times CCA layers (the tails read and written). The
    SambaY family counts ``CACHE_ROW_KEYS`` instead."""
    if cache_spec.is_uniform(cfg):
        return 3 if cfg.num_experts else 0
    if cfg.mb_per_layer:
        return len(CACHE_ROW_KEYS)
    return 6 + any(p.mixer == "cca" for p in cache_spec.layer_plan(cfg))


# what a decode step of the SambaY family counts, in this order: live rows
# times Mamba layers (a state read and written each); keys of the shared
# pool read, summed over the layers that attend over it; keys of the rings
# read, summed over the window layers (``obs/engine_profile.py``)
CACHE_ROW_KEYS = ("ssm_state_rows", "shared_kv_rows_read",
                  "window_rows_read")


def held_state(cfg, arrays: tuple, slot: int) -> np.ndarray:
    """What ``CBEngine.recurrent_state`` reads of one layer's slot arrays
    for the engine's slot ``slot``, float32 on the host: a KDA layer's
    recurrent state (its convolution tails are left out, as ever), a CCA
    layer's tails, flattened side by side; a Mamba layer's state, ``[I,
    N]``; a window layer's ring, ``[window, pairs, 2 * 2D]`` (a row's keys
    beside its values, token ``t`` at row ``t % window``)."""
    if any(p.mixer == "cca" for p in cache_spec.layer_plan(cfg)):
        return np.concatenate([np.asarray(a[slot], np.float32).reshape(-1)
                               for a in arrays])
    if cfg.mb_per_layer and arrays[0].ndim == 4:
        # a window layer's ring: the slot's pages, rows in ring order, as
        # (k | v) [window, pairs, 2 * 2D]
        pairs, _n, ps, width = arrays[0].shape
        n = cfg.sliding_window // ps
        return np.concatenate(
            [np.asarray(a[:, 1 + slot * n:1 + (slot + 1) * n], np.float32)
             .reshape(pairs, n * ps, width).swapaxes(0, 1) for a in arrays],
            axis=-1)
    held = np.asarray(arrays[0][slot]).astype(np.float32)
    # a Mamba state is kept [N, I] and read as the published [I, N]
    return held.T if cfg.mb_per_layer else held


def kda_in_kernel(cfg) -> bool:
    """Whether a decode step of this model updates its KDA states in the
    one-pass kernel (``ops/kda_state.py``), from what its program is built
    on: a KDA layer in the plan, the state's shape and dtype, the
    backend."""
    from polyrl_tpu.ops import kda_state

    return (any(p.mixer == "kda" for p in cache_spec.layer_plan(cfg))
            and kda_state.in_kernel((0, *cache_spec.kda_dims(cfg)),
                                    cache_spec.STATE_DTYPE))


def mla_in_kernel(cfg, rows: int) -> bool:
    """Whether a decode step of ``rows`` rows multiplies its MLA layers'
    ``wkv_b`` where it lies in the stack (``ops/mla_proj.py``): an MLA
    layer in the plan, head sizes the kernels take, the backend."""
    from polyrl_tpu.ops import mla_proj

    return (any(p.mixer == "mla" for p in cache_spec.layer_plan(cfg))
            and mla_proj.in_kernel(cfg, rows))


def paged_decode(params, cfg, tokens, positions, pools, page_table, seq_lens,
                 active=None, head_fn=None):
    """``decoder.forward_paged_decode`` for a model of several kinds of
    layer: one token a slot. A row without a request leaves its state
    rows as they are and writes what it would keep in pages to the null
    page. A CCA layer writes and attends its K/V pair through the GQA
    decode kernels (``ops.paged_attention``)."""
    from polyrl_tpu.ops.kda_state import kda_state_update
    from polyrl_tpu.ops.mla_attention import latent_paged_attention
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write
    from polyrl_tpu.ops.ssm_state import ssm_state_update

    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    paged, state = list(pools[0]), list(pools[1])
    s = tokens.shape[0]
    ps = jax.tree_util.tree_leaves(paged)[0].shape[2]
    scale = mla_scale(cfg) if cfg.kv_lora_rank else None
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
    carry = router_carry(cfg, (s,))
    index = cache_spec.pool_index(cfg)
    if cfg.mb_per_layer:
        # a window layer's ring: row r's pages are slot r's, the token at
        # its position modulo the window, the row as long as it has tokens
        w = cfg.sliding_window
        ring_table = ring_pages(cfg, jnp.arange(s, dtype=jnp.int32), ps)
        at = seq_lens % w
        ring_page = jnp.where(live, ring_table[jnp.arange(s), at // ps], 0)
        ring_off = jnp.where(live, at % ps, 0)
        ring_lens = jnp.minimum(attn_lens, w)
        window_read = jnp.sum(ring_lens)
        d_scale = cfg.head_dim_ ** -0.5
    for l, p in enumerate(plan):
        at_pages, i = index[l]
        mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
        h_in = norm(layers, "attn_norm", x, cfg.rms_norm_eps, l)
        if p.mixer in ("ssm", "ssm_mem"):
            st, tail = state[i]
            with jax.named_scope("ssm_proj"):
                c, z, dt, bm, cm, full = _ssm_inputs(
                    cfg, mixer_lp, h_in[:, None], tail[:s])
                tail = _set_rows(tail, jnp.where(
                    live[:, None, None], full[:, 1:].astype(tail.dtype),
                    tail[:s]))
            with jax.named_scope("ssm_core"):
                st, m = ssm_state_update(mixer_lp, st, c[:, 0], dt[:, 0],
                                         bm[:, 0], cm[:, 0], live)
            with jax.named_scope("ssm_proj"):
                out = _ssm_out(mixer_lp, m, z[:, 0])
            state[i] = (st, tail)
            if p.mixer == "ssm_mem":
                carry = m
            load = load.at[0].add(n_live)
        elif p.mixer == "gmu":
            out = _gmu(mixer_lp, h_in, carry)
        elif p.mixer in ("swa", "diff", "cross"):
            q, k, v = _diff_qkv(cfg, mixer_lp, h_in)
            q = paired_queries(q)
            if p.mixer == "swa":
                with jax.named_scope("swa_core"):
                    state[i] = paged_kv_write(*state[i], ring_page, ring_off,
                                              k, v)
                    o = paged_attention(q, *state[i], ring_table, ring_lens,
                                        d_scale)
                load = load.at[2].add(window_read)
            else:
                with jax.named_scope("attn_core"):
                    if p.mixer == "diff":
                        paged[at_pages] = paged_kv_write(
                            *paged[at_pages], write_page, write_off, k, v)
                    o = paged_attention(q, *paged[at_pages], page_table,
                                        attn_lens, d_scale)
                load = load.at[1].add(rows_read)
            out = _diff_out(cfg, mixer_lp,
                            o.reshape(s, -1, 2, o.shape[-1]), p.published)
        elif p.mixer == "kda":
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
            wkv_b = ((layers["mla"]["wkv_b"], kind_index(cfg)[l][0])
                     if mla_in_kernel(cfg, s) else None)
            i = at_pages
            with jax.named_scope("mla_proj"):
                q_nope, q_rope, lat = _mla_qkv(cfg, mixer_lp, h_in[:, None],
                                               positions[:, None])
                q_lat = mla_absorb(cfg, mixer_lp, q_nope[:, 0], q_rope[:, 0],
                                   wkv_b)
            with jax.named_scope("mla_core"):
                paged[i] = _scatter_token_kv(
                    paged[i], write_page, write_off, lat)
                o_lat = latent_paged_attention(
                    q_lat, paged[i], page_table, attn_lens, r, scale)
            load = load.at[5].add(rows_read)
            with jax.named_scope("mla_proj"):
                out = _mla_out(cfg, mixer_lp, h_in,
                               mla_unabsorb(cfg, mixer_lp, o_lat, wkv_b))
        elif p.mixer == "cca":
            with jax.named_scope("cca_proj"):
                proj = mm(h_in, mixer_lp["w_in"])
            with jax.named_scope("cca_mix"):
                tails = tuple(a[:s] for a in state[i])
                q, k, v, fulls = _cca_mix(cfg, mixer_lp, proj[:, None],
                                          positions[:, None], tails)
                new = _cca_tails(cfg, fulls, 1)
                state[i] = tuple(
                    _set_rows(a, jnp.where(
                        live.reshape(-1, *[1] * (a.ndim - 1)),
                        n.astype(a.dtype), a[:s]))
                    for a, n in zip(state[i], new))
            with jax.named_scope("attn_core"):
                paged[at_pages] = paged_kv_write(
                    *paged[at_pages], write_page, write_off, k[:, 0], v[:, 0])
                o = paged_attention(q[:, 0], *paged[at_pages], page_table,
                                    attn_lens).reshape(s, -1)
            load = load.at[6].add(n_live)
            with jax.named_scope("cca_proj"):
                out = mm(o, mixer_lp["wo"])
        else:
            raise NotImplementedError(
                f"mixer {p.mixer!r} beside other kinds of layer")
        x = _residual(x, out, _res(layers, "attn_res", l))
        x, moe, carry = _mlp(cfg, x, layers, l, mlp_lp, active, carry)
        if moe is not None:
            load = load.at[:3].add(moe)
            load = load.at[3].add(n_live * cfg.num_experts_per_tok)
    return ((head_fn or _head)(cfg, params, x),
            (tuple(paged), tuple(state)), load)
