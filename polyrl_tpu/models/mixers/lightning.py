"""Lightning (linear) attention (MiniCPM-SALA's ``lightning-attn`` layers,
``minicpm_sala``; ISSUE 56; every reading the published config does not
settle is listed in ``benchmark/configs/minicpm-sala.json`` under
``assumed``). H heads with a key and a value head each of size D, a state
``S`` [D key, D value] a head in float32, zero at position 0::

    q, k, v = h Wq, h Wk, h Wv  [H, D] each
    q, k <- rms(q) * q_norm, rms(k) * k_norm    over a head's D, a vector [D]
    q, k <- rope(position) on the whole head (rotate-half, ``rope_theta``)
    S_t = lambda_h S_{t-1} + k_t^T v_t          lambda_h = exp(-slopes[h])
    o_t = q_t S_t / sqrt(D)
    out = (rms(o) * o_norm  *  sigmoid(h Wg)) Wo     rms over a head's D

No activation on q, k or v, no delta term, a constant decay a head: the
slopes are an array ``[H]`` a layer of the parameter tree (``init`` draws
the family's ``2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)`` at the
published layer index ``l`` of ``L`` published layers), so a checkpoint or
another convention changes numbers and no code.

The stack ``params["layers"]["lightning"]``::

    wqkv [Ll, d, 3*H*D]  (q | k | v), q_norm k_norm o_norm [Ll, D],
    slopes [Ll, H] float32, wg [Ll, d, H*D], wo [Ll, H*D, d]

The slot holds the state. A decode step updates it in one kernel a layer
(``ops/lightning_state.py``; ``lightning_recurrent_step`` is its oracle),
prefill runs the chunked form (``lightning_chunked``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import rms_norm
from polyrl_tpu.models.mixers.base import (Kept, Mixer, rope_partial,
                                           yarn_inv_freq)
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
# positions a step of the chunked form covers: its [C, C] products are a
# quarter of the inter-chunk ones at 128 keys a head
CHUNK = 128


def dims(cfg) -> tuple[int, int]:
    """(heads, head size) of a lightning layer."""
    return (cfg.lightning_heads or cfg.num_heads,
            cfg.lightning_head_dim or cfg.head_dim_)


def slopes(cfg, published: int) -> np.ndarray:
    """The decay's slopes of published layer ``published``, [H] float32:
    ``2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)``."""
    h, _d = dims(cfg)
    depth = cache_spec.published_depth(cfg)
    base = 2.0 ** (-8.0 * np.arange(1, h + 1, dtype=np.float64) / h)
    return (base * (1.0 - published / max(depth - 1, 1) + 1e-5)).astype(
        np.float32)


def init(cfg, m: int, draw) -> dict:
    d = cfg.hidden_size
    h, hd = dims(cfg)
    norm, ones = draw.normal, draw.ones
    own = [p.published for p in cache_spec.layer_plan(cfg)
           if p.mixer == "lightning"]
    return {"lightning": {
        "wqkv": norm(m, d, 3 * h * hd),
        "q_norm": ones(m, hd), "k_norm": ones(m, hd), "o_norm": ones(m, hd),
        "slopes": jnp.asarray(np.stack([slopes(cfg, l) for l in own])),
        "wg": norm(m, d, h * hd), "wo": norm(m, h * hd, d),
    }}


def cache(cfg, p, dtype):
    h, hd = dims(cfg)
    return cache_spec.Slot((("state", (h, hd, hd), cache_spec.STATE_DTYPE),))


def _inputs(cfg, lp, h_in, positions):
    """q (scaled by 1 / sqrt(D)), k, v [..., H, D] float32 of a lightning
    layer: the products, the q/k norms, the rope."""
    h, hd = dims(cfg)
    lead = h_in.shape[:-1]
    w = lp["wqkv"]
    # the products leave the MXU's float32 accumulator as they are: what a
    # state adds up over hundreds of tokens is not rounded to the model's
    # type on the way in (a state's distance from the float32 reference
    # read 0.8% with bfloat16 q, k, v, half the way to a bfloat16 STATE's
    # 1.7%: my chip runs, PR 56)
    qkv = (jnp.matmul(h_in, w, preferred_element_type=jnp.float32)
           if isinstance(w, jax.Array) else mm(h_in, w).astype(jnp.float32))
    n = h * hd
    inv = yarn_inv_freq(cfg.rope_theta, hd, None)
    # the products' columns by slices, as ``gqa._qkv`` takes them (a
    # reshape to [3, H, D] had the compiler lay the whole stack out anew)
    q, k = (rope_partial(
        rms_norm(qkv[..., i * n:(i + 1) * n].reshape(*lead, h, hd),
                 lp[name], cfg.rms_norm_eps).astype(jnp.float32),
        positions, inv)
        for i, name in ((0, "q_norm"), (1, "k_norm")))
    v = qkv[..., 2 * n:].reshape(*lead, h, hd).astype(jnp.float32)
    return q * hd ** -0.5, k, v


def _out(cfg, lp, h_in, o):
    """``(rms_head(o) * sigmoid(x Wg)) Wo`` from the core's ``o``
    [..., H, D] float32."""
    lead = h_in.shape[:-1]
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
    gate = jax.nn.sigmoid(mm(h_in, lp["wg"]).astype(jnp.float32))
    return mm((o.reshape(*lead, -1) * gate).astype(h_in.dtype), lp["wo"])


def lightning_recurrent_step(state, q, k, v, decay):
    """One position of the recurrence for rows ``[S, H, ...]``: returns
    (new state, o [S, H, Dv]); ``decay`` [H]; everything float32."""
    new = state * decay[:, None, None] + k[..., None] * v[..., None, :]
    return new, jnp.einsum("shkv,shk->shv", new, q, precision=_HI)


def lightning_chunked(state, q, k, v, slope, valid, chunk: int):
    """The same recurrence over ``T`` positions in steps of ``chunk`` (T a
    multiple of it): ``state`` [B, H, D, D], q k v [B, T, H, D], ``slope``
    [H] (lambda = exp(-slope)), ``valid`` [B, T] (a padded position neither
    decays nor writes the state), all float32. Returns (state after T,
    o [B, T, H, D]). Plain ``jax.numpy`` and differentiable.

    Within a step, with ``n_i`` the valid positions of the step up to and
    with ``i``: ``o_i = lambda^n_i q_i S0 + sum_{j <= i} lambda^(n_i - n_j)
    (q_i . k_j) v_j`` and ``S = lambda^n_C S0 + sum_j lambda^(n_C - n_j)
    k_j^T v_j``; every exponent is at most 0, so nothing is divided by a
    decay and no step length overflows."""
    b, t, h, d = q.shape
    n = t // chunk
    k = k * valid[..., None, None]

    def split(a):
        return a.reshape(b, n, chunk, *a.shape[2:]).swapaxes(0, 1)

    incl = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(s0, xs):
        q, k, v, ok = xs                                  # [B, C, ...]
        seen = jnp.cumsum(ok, axis=1)                     # n_i [B, C]
        # [B, H, C]: lambda^n_i, and [B, H, C, C]: lambda^(n_i - n_j)
        since = slope[None, :, None] * seen[:, None, :]
        between = jnp.where(
            incl, since[..., :, None] - since[..., None, :], 0.0)
        w = jnp.einsum("bthc,bihc->bhti", q, k, precision=_HI)
        w = jnp.where(incl, w * jnp.exp(-between), 0.0)
        o = (jnp.einsum("bthc,bhcv->bhtv", q, s0, precision=_HI)
             * jnp.exp(-since)[..., None]
             + jnp.einsum("bhti,bihv->bhtv", w, v, precision=_HI))
        left = jnp.exp(since - since[..., -1:])           # lambda^(n_C - n_j)
        s1 = (s0 * jnp.exp(-since[..., -1])[..., None, None]
              + jnp.einsum("bihc,bihv->bhcv",
                           k * left.swapaxes(1, 2)[..., None], v,
                           precision=_HI))
        return s1, o.swapaxes(1, 2)

    state, o = jax.lax.scan(
        step, state, (*map(split, (q, k, v)), split(valid)))
    return state, o.swapaxes(0, 1).reshape(b, t, h, d)


def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] from ``state`` [B, H, D, D] float32 at the
    sequence's last valid position before it: keeps the state after the
    last valid position."""
    (state,) = ctx.state
    t = h_in.shape[1]
    with jax.named_scope("lightning_proj"):
        q, k, v = _inputs(cfg, lp, h_in, ctx.positions)
    with jax.named_scope("lightning_core"):
        c = min(CHUNK, t)
        pad = -t % c
        valid = ctx.valid.astype(jnp.float32)
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (q, k, v))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        state, o = lightning_chunked(
            state.astype(jnp.float32), q, k, v,
            lp["slopes"].astype(jnp.float32), valid, c)
        o = o[:, :t]
    with jax.named_scope("lightning_proj"):
        return _out(cfg, lp, h_in, o), Kept(slot=(state,))


def step(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.lightning_state import lightning_state_update

    (st,) = ctx.slot
    with jax.named_scope("lightning_proj"):
        q, k, v = _inputs(cfg, lp, h_in, ctx.positions)
        decay = jnp.exp(-lp["slopes"].astype(jnp.float32))
    with jax.named_scope("lightning_core"):
        st, o = lightning_state_update(st, q, k, v, decay, ctx.live)
    with jax.named_scope("lightning_proj"):
        out = _out(cfg, lp, h_in, o)
    ctx.load.add("lightning_state_rows", ctx.n_live)
    return out, Kept(slot=(st,))


def in_kernel(cfg, rows: int) -> bool:
    """Whether a decode step updates the states in the one-pass kernel
    (``ops/lightning_state.py``), from what its program is built on: the
    state's shape and dtype, the backend."""
    from polyrl_tpu.ops import lightning_state

    h, hd = dims(cfg)
    return lightning_state.in_kernel((0, h, hd, hd), cache_spec.STATE_DTYPE)


def held(cfg, arrays, slot: int) -> np.ndarray:
    """The recurrent state ``[H, D, D]``."""
    return np.asarray(arrays[0][slot]).astype(np.float32)


LIGHTNING = Mixer(
    "lightning", cache, stack="lightning", init=init, row_parallel=("wo",),
    sequence=sequence, step=step, slot_scope="lightning_core", held=held,
    counts=("lightning_state_rows",),
    kernel=("lightning_kernel_steps", in_kernel))
