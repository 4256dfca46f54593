"""Mamba-2 (SSD) mixers (Nemotron-H's ``M`` layers, ``nemotron_h``; ISSUE
58; the reference's docstring, ``benchmark/references/nemotron_h.py``,
carries every line, and each reading the published config does not settle
is listed in ``benchmark/configs/nemotron-3-nano-30b-a3b.json`` under
``assumed``). H heads of size P (inner width I = H P), G groups of R = H /
G heads that share B and C, state size N, K taps::

    z | xBC | dt = split(u W_in)          W_in [d, I + (I + 2 G N) + H]
    xBC = silu(causal depthwise conv_K(xBC) + b)     over I + 2 G N channels
    x | B | C = split(xBC)        x [H, P], B and C [G, N], head h reads
                                  group h // R
    dt = softplus(dt + dt_bias)   a = exp(dt A)   A = -exp(a_log)   [H]
    S_t[h] = a_t[h] S_{t-1}[h] + (dt_t[h] x_t[h]) (x) B_t[g(h)]   float32
    y_t[h] = S_t[h] C_t[g(h)] + d_skip[h] x_t[h]
    y = rms_G(y * silu(z)) * norm_w     over G groups of I / G columns
    out = y W_out

The stack ``params["layers"]["mamba2"]``::

    w_in [Lm, d, 2 I + 2 G N + H]  (z | x | B | C | dt),
    conv [Lm, K, I + 2 G N], conv_bias [Lm, I + 2 G N],
    dt_bias a_log d_skip [Lm, H] float32, norm_w [Lm, I], w_out [Lm, I, d]

The slot holds the state as ``[G, N, R P]`` float32 (a group's heads side
by side on the lanes, N on the sublanes: ``ops/ssd_state.py`` says why;
``held`` reads it as the published ``[H, P, N]``) and the last K-1 rows of
``xBC`` before the convolution. A decode step updates the state in one
kernel a layer (``ops/ssd_state.py``); prefill runs the chunked SSD form at
the published ``chunk_size`` (``ssd_chunked``: the decay is a scalar a head,
so every exponent of a chunk's segment sums is at most 0 and nothing
overflows, where Mamba-1's chunked form did). The in-projection leaves the
MXU's float32 accumulator as it is for ``dt``, whose product with ``A`` is
an exponent that a state multiplies by at every token; ``z`` and ``xBC``
are rounded to the model's type there, so that the convolution reads the
same numbers from a chunk as from the slot's tail."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.mixers.base import (Kept, Mixer, shift_tail,
                                           tail_after)
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
# the softplus of ``dt_bias`` as drawn: log-uniform between the published
# ``time_step_min`` and ``time_step_max``, at least ``time_step_floor``
DT_INIT = (0.001, 0.1, 1e-4)
# ``-A`` as drawn: uniform between these (Mamba-2's ``A_init_range``)
A_INIT = (1.0, 16.0)


def dims(cfg) -> tuple[int, int, int, int, int, int, int]:
    """(H, P, G, N, K, the inner width I, the convolution's channels)."""
    h, p, g, n, k = cache_spec.mamba2_dims(cfg)
    return h, p, g, n, k, h * p, h * p + 2 * g * n


def init(cfg, m: int, draw) -> dict:
    d = cfg.hidden_size
    h, _p, _g, _n, k, inner, ch = dims(cfg)
    norm = draw.normal
    lo, hi, floor = DT_INIT
    dt = jnp.maximum(jnp.exp(draw.uniform(m, h) * math.log(hi / lo)
                             + math.log(lo)), floor)
    return {"mamba2": {
        "w_in": norm(m, d, inner + ch + h),
        "conv": norm(m, k, ch).at[:, -1].add(1.0),
        "conv_bias": jnp.zeros((m, ch), cfg.dtype),
        # the inverse of softplus at ``dt``
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(A_INIT[0]
                         + draw.uniform(m, h) * (A_INIT[1] - A_INIT[0])),
        "d_skip": jnp.ones((m, h), jnp.float32),
        "norm_w": draw.ones(m, inner),
        "w_out": norm(m, inner, d),
    }}


def cache(cfg, p, dtype):
    h, hp, g, n, k, _inner, ch = dims(cfg)
    return cache_spec.Slot((
        ("state", (g, n, h // g * hp), cache_spec.STATE_DTYPE),
        ("conv", (k - 1, ch), dtype)))


def _inputs(cfg, lp, h_in, tail):
    """Everything of a layer before its recurrence, for ``h_in`` [B, T, d]
    after the convolution tail ``tail`` [B, K-1, channels]: (x [B, T, G, R
    P], B and C [B, T, G, N], dt [B, T, H] after the softplus, all
    float32; z [B, T, I]; ``[tail | xBC]`` [B, K-1+T, channels])."""
    h, hp, g, n, k, inner, ch = dims(cfg)
    b, t = h_in.shape[:2]
    w = lp["w_in"]
    zxd = (jnp.matmul(h_in, w, preferred_element_type=jnp.float32)
           if isinstance(w, jax.Array) else mm(h_in, w).astype(jnp.float32))
    z = zxd[..., :inner].astype(h_in.dtype)
    xbc = zxd[..., inner:inner + ch].astype(h_in.dtype)
    dt = jax.nn.softplus(zxd[..., inner + ch:]
                         + lp["dt_bias"].astype(jnp.float32))
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    taps = lp["conv"].astype(jnp.float32)
    c = jax.nn.silu(sum(full[:, j:j + t].astype(jnp.float32) * taps[j]
                        for j in range(k))
                    + lp["conv_bias"].astype(jnp.float32))
    x = c[..., :inner].reshape(b, t, g, inner // g)
    bm = c[..., inner:inner + g * n].reshape(b, t, g, n)
    cm = c[..., inner + g * n:].reshape(b, t, g, n)
    return x, bm, cm, dt, z, full


def _per_column(cfg, v):
    """A number a head ``v`` [..., H] spread over the head's P columns of
    the state's lanes: [..., G, R P]."""
    h, hp, g = dims(cfg)[:3]
    return jnp.repeat(v, hp, axis=-1).reshape(*v.shape[:-1], g, h // g * hp)


def _out(cfg, lp, y, x, z):
    """From the recurrence's ``y`` [..., G, R P] float32: the skip, the
    gate, the grouped RMSNorm and the out projection."""
    _h, _p, g, _n, _k, inner, _ch = dims(cfg)
    lead = y.shape[:-2]
    y = y + _per_column(cfg, lp["d_skip"].astype(jnp.float32)) * x
    y = y.reshape(*lead, inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(*lead, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y.reshape(*lead, inner) * lp["norm_w"].astype(jnp.float32)
    return mm(y.astype(z.dtype), lp["w_out"])


def ssd_chunked(state, x, bm, cm, la, chunk: int):
    """The recurrence over ``T`` positions in steps of ``chunk`` (T a
    multiple of it): ``state`` [B, G, N, W], ``x`` (dt x) [B, T, G, W],
    ``la`` (the decay's logarithm dt A a head, at most 0; 0 with ``x`` 0 at
    a padded position, which then neither decays nor writes) [B, T, H],
    ``bm`` and ``cm`` [B, T, G, N], all float32. Returns (state after T, y
    [B, T, G, W]). Plain ``jax.numpy`` and differentiable.

    Within a step, with ``s_i`` the sum of ``la`` up to and with ``i``:
    ``y_i = e^{s_i} C_i S0 + sum_{j <= i} e^{s_i - s_j} (C_i . B_j) x_j``
    and ``S = e^{s_C} S0 + sum_j e^{s_C - s_j} B_j (x) x_j``; every
    exponent is at most 0, so nothing is divided by a decay."""
    b, t, g, w = x.shape
    r = la.shape[-1] // g
    n_state = bm.shape[-1]
    steps = t // chunk

    def split(a):
        return a.reshape(b, steps, chunk, *a.shape[2:]).swapaxes(0, 1)

    incl = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]

    def step(s0, xs):
        x, bm, cm, la = xs              # [B, C, G, R, P], .., [B, C, G, R]
        since = jnp.cumsum(la, axis=1)                    # s_i
        cb = jnp.einsum("bign,bjgn->bijg", cm, bm, precision=_HI)
        between = jnp.where(
            incl, since[:, :, None] - since[:, None, :], 0.0)
        wts = jnp.where(incl, cb[..., None] * jnp.exp(between), 0.0)
        y = (jnp.einsum("bign,bgnrp->bigrp", cm, s0, precision=_HI)
             * jnp.exp(since)[..., None]
             + jnp.einsum("bijgr,bjgrp->bigrp", wts, x, precision=_HI))
        left = jnp.exp(since[:, -1:] - since)             # e^{s_C - s_j}
        s1 = (s0 * jnp.exp(since[:, -1])[:, :, None, :, None]
              + jnp.einsum("bjgn,bjgrp->bgnrp", bm, x * left[..., None],
                           precision=_HI))
        return s1, y

    state, y = jax.lax.scan(
        step, state.reshape(b, g, n_state, r, w // r),
        (split(x.reshape(b, t, g, r, w // r)), split(bm), split(cm),
         split(la.reshape(b, t, g, r))))
    return (state.reshape(b, g, n_state, w),
            y.swapaxes(0, 1).reshape(b, t, g, w))


def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] (``ctx.valid`` [B, T], padding on the right)
    from (``state`` [B, G, N, W] float32, ``tail`` [B, K-1, channels]):
    keeps the state and the tail after the last valid position."""
    state, tail = ctx.state
    valid = ctx.valid
    k = dims(cfg)[4]
    t = h_in.shape[1]
    with jax.named_scope("ssd_proj"):
        h_in = h_in * valid[..., None].astype(h_in.dtype)
        x, bm, cm, dt, z, full = _inputs(cfg, lp, h_in, tail)
        dt = jnp.where(valid[..., None], dt, 0.0)
        new_tail = tail_after(full, jnp.sum(valid.astype(jnp.int32), axis=1),
                              k - 1)
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        dtx = _per_column(cfg, dt) * x
    with jax.named_scope("ssd_core"):
        c = min(cfg.ssd_chunk_size, t)
        # a position past the chunk: neither decays nor writes
        core = [jnp.pad(v, ((0, 0), (0, -t % c)) + ((0, 0),) * (v.ndim - 2))
                for v in (dtx, bm, cm, dt * a)]
        state, y = ssd_chunked(state.astype(jnp.float32), *core, c)
        y = y[:, :t]
    with jax.named_scope("ssd_proj"):
        return _out(cfg, lp, y, x, z), Kept(
            slot=(state, new_tail.astype(tail.dtype)))


def step(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.ssd_state import ssd_state_update

    st, tail = ctx.slot
    s = h_in.shape[0]
    with jax.named_scope("ssd_proj"):
        x, bm, cm, dt, z, full = _inputs(cfg, lp, h_in[:, None], tail[:s])
        tail = shift_tail(tail, full, ctx.live)
        x, bm, cm, dt, z = x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], z[:, 0]
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        dtx = _per_column(cfg, dt) * x
        decay = _per_column(cfg, jnp.exp(dt * a))
    with jax.named_scope("ssd_core"):
        st, y = ssd_state_update(st, dtx, decay, bm, cm, ctx.live)
    with jax.named_scope("ssd_proj"):
        out = _out(cfg, lp, y, x, z)
    ctx.load.add("ssd_state_rows", ctx.n_live)
    return out, Kept(slot=(st, tail))


def in_kernel(cfg, rows: int) -> bool:
    """Whether a decode step updates the states in the one-pass kernel
    (``ops/ssd_state.py``), from what its program is built on: the state's
    shape and dtype, the backend."""
    from polyrl_tpu.ops import ssd_state

    h, hp, g, n = dims(cfg)[:4]
    return ssd_state.in_kernel((0, g, n, h // g * hp),
                               cache_spec.STATE_DTYPE)


def held(cfg, arrays, slot: int) -> np.ndarray:
    """The state, kept ``[G, N, R P]`` and read as the published ``[H, P,
    N]``."""
    h, hp, g, n = dims(cfg)[:4]
    st = np.asarray(arrays[0][slot]).astype(np.float32)
    return st.reshape(g, n, h // g, hp).transpose(0, 2, 3, 1).reshape(
        h, hp, n)


MAMBA2 = Mixer(
    "mamba2", cache, stack="mamba2", init=init, row_parallel=("w_out",),
    sequence=sequence, step=step, slot_scope="ssd_core", held=held,
    counts=("ssd_state_rows",), kernel=("ssd_kernel_steps", in_kernel))
