"""Kimi Delta Attention (the Ling-3.0 / Ring hybrid family's recurrent
layers; ISSUE 33, section 1; every reading the published config does not
settle is listed in ``benchmark/configs/ling-3.0-flash.json`` under
``assumed``). H heads of key and value size D, state ``S`` [D key, D value]
a head in float32, zero at position 0::

    q = l2norm(silu(conv(x Wq)))   k = l2norm(silu(conv(x Wk)))
    v = silu(conv(x Wv))           conv: causal, depthwise, last K positions
    g = lower * sigmoid(exp(a_log_h) * (x Wf + f_bias))   in [lower, 0]
    beta = sigmoid(x Wb)
    S' = diag(exp(g)) S ;  S = S' + beta k (v - S'^T k)^T ;  o = S^T q / sqrt(D)
    out = (rms_head(o) * sigmoid(x Wg)) Wo

The stack ``params["layers"]["kda"]``::

    wq wk wv wf wg [Lk, d, H*D], conv_q conv_k conv_v [Lk, K, H*D],
    a_log [Lk, H], f_bias [Lk, H*D], wb [Lk, d, H], o_norm [Lk, D],
    wo [Lk, H*D, d]

The slot holds the state and the last K-1 rows of the three convolutions'
inputs side by side. A decode step updates the state in one kernel a layer
(``ops/kda_state.py``; ``kda_recurrent_step`` is its oracle), prefill runs
the chunked form."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import rms_norm
from polyrl_tpu.models.mixers.base import (Kept, Mixer, l2norm, tail_after,
                                           shift_tail)
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
# positions a step of the chunked KDA form covers: within one the form
# divides by exp(sum of g), which float32 holds down to exp(-87)
_MAX_LOG_DECAY = 80.0
# the decay's bias over a head's key channels, first to last (``init``)
F_BIAS = (-8.0, -1.0)


def kda_chunk(cfg) -> int:
    return max(1, int(_MAX_LOG_DECAY // abs(cfg.kda_lower_bound)))


def init(cfg, k: int, draw) -> dict:
    """The decay's bias ``f_bias`` runs from -8 to -1 over a head's key
    channels, so that a state's channels forget over a few tokens to a few
    thousand (g from -1.3 to -0.002 a token, the range the published
    initialisation of the decay spreads over; a bias of zero would forget
    in one token and the float32 state would be no part of any result),
    ``a_log`` zero."""
    d = cfg.hidden_size
    h, dk, dv = cache_spec.kda_dims(cfg)
    kk = cfg.short_conv_kernel_size
    norm, ones = draw.normal, draw.ones
    return {"kda": {
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
    }}


def cache(cfg, p, dtype):
    h, dk, dv = cache_spec.kda_dims(cfg)
    k = cfg.short_conv_kernel_size
    return cache_spec.Slot((("state", (h, dk, dv), cache_spec.STATE_DTYPE),
                            ("conv", (k - 1, h * (2 * dk + dv)), dtype)))


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
    q = l2norm(xc[..., :hh * dk].reshape(*lead, hh, dk))
    k = l2norm(xc[..., hh * dk:2 * hh * dk].reshape(*lead, hh, dk))
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
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
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



def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] from (``state`` [B, H, Dk, Dv] float32,
    ``conv`` [B, K-1, 3*H*D]) at the sequence's last valid position before
    it: keeps the state and the conv tail after the last valid position."""
    state, conv = ctx.state
    valid = ctx.valid
    t = h_in.shape[1]
    kk = cfg.short_conv_kernel_size
    with jax.named_scope("kda_proj"):
        x = _kda_proj(lp, h_in) * valid[..., None].astype(h_in.dtype)
        full = jnp.concatenate([conv.astype(x.dtype), x], axis=1)
        w = _conv_w(lp)
        xc = sum(full[:, j:j + t].astype(jnp.float32)
                 * w[j].astype(jnp.float32) for j in range(kk))
        q, k, v, g, beta = _kda_inputs(cfg, lp, h_in, xc, valid)
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        tail = tail_after(full, n_valid, kk - 1)
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
        out = _kda_out(cfg, lp, h_in, o)
        return out, Kept(slot=(state, tail.astype(conv.dtype)))


def step(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.kda_state import kda_state_update

    st, conv = ctx.slot
    s = h_in.shape[0]
    with jax.named_scope("kda_proj"):
        new = _kda_proj(lp, h_in)
        window = jnp.concatenate(
            [conv[:s], new[:, None].astype(conv.dtype)], axis=1)
        xc = _conv_window(_conv_w(lp), window)
        q, k, v, g, beta = _kda_inputs(cfg, lp, h_in, xc)
        conv = shift_tail(conv, window, ctx.live)
    with jax.named_scope("kda_core"):
        st, o = kda_state_update(st, q, k, v, g, beta, ctx.live)
    with jax.named_scope("kda_proj"):
        out = _kda_out(cfg, lp, h_in, o)
    ctx.load.add("kda_state_rows", ctx.n_live)
    return out, Kept(slot=(st, conv))


def in_kernel(cfg, rows: int) -> bool:
    """Whether a decode step updates the states in the one-pass kernel
    (``ops/kda_state.py``), from what its program is built on: the state's
    shape and dtype, the backend."""
    from polyrl_tpu.ops import kda_state

    return kda_state.in_kernel((0, *cache_spec.kda_dims(cfg)),
                               cache_spec.STATE_DTYPE)


def held(cfg, arrays, slot: int) -> np.ndarray:
    """The recurrent state ``[H, Dk, Dv]`` (the convolution tails are left
    out, as ever)."""
    return np.asarray(arrays[0][slot]).astype(np.float32)


KDA = Mixer(
    "kda", cache, stack="kda", init=init, row_parallel=("wo",),
    replicated=("wb",), sequence=sequence, step=step, slot_scope="kda_core",
    held=held, counts=("kda_state_rows",), counts_in_routed=True,
    kernel=("kda_kernel_steps", in_kernel))
