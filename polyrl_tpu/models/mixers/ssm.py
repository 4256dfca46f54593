"""The SambaY family's scans (``phi4flash``: Phi-4-mini-flash-reasoning;
ISSUE 43; the reference's docstring, ``benchmark/references/
sambay_diff.py``, carries every line and each choice the published config
does not settle; ``benchmark/configs/phi-4-mini-flash-reasoning.json``
lists them under ``assumed``): ``ssm``, a Mamba-1 selective scan;
``ssm_mem``, the same scan, whose output ``m`` before its gate is handed
down the layers of the same call; ``gmu``, a gated memory unit on ``m``,
which keeps nothing. Mamba-1, inner width I, state N a channel::

    xi, z = split(x W_in)       c[t] = silu(sum_j conv[j] * xi[t-K+1+j] + b)
    dt, B, C = split(c W_x)     dt = softplus(dt W_dt + dt_bias)
    s[t] = exp(dt[t] A) * s[t-1] + (dt[t] c[t]) B[t]^T     A = -exp(a_log)
    m[t] = s[t] C[t] + d_skip * c[t]      out = (m[t] * silu(z[t])) W_out

The state is kept ``[N, I]`` float32 (the inner width on the lanes) with
the last K-1 rows of ``xi``; a decode step updates it in place in one
kernel a layer (``ops/ssm_state.py``), prefill scans ``ssm_step`` position
by position. A gated memory unit is ``(silu(x W_1) * m) W_2`` with ``m``
the ``ssm_mem`` layer's of the same token. The family's norms are
LayerNorms: ``attn_norm_bias``, ``mlp_norm_bias`` [L, d] lie beside the
stacks.

The stacks ``params["layers"]["ssm"]`` (``ssm`` and ``ssm_mem``) and
``["gmu"]``::

    w_in [Ls, d, 2*I]  (xi | z), conv [Ls, K, I], conv_bias [Ls, I],
    w_x [Ls, I, R + 2*N]  (dt | B | C), w_dt [Ls, R, I],
    dt_bias [Ls, I] a_log [Ls, N, I] d_skip [Ls, I] float32, w_out [Ls, I, d]
    gmu: w_in [Lg, d, I], w_out [Lg, I, d]"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.mixers.base import (Kept, Mixer, tail_after,
                                           shift_tail)
from polyrl_tpu.models.quant import mm

# the softplus of a scan's ``dt_bias`` as drawn: log-uniform between these
DT_INIT = (0.001, 0.1)


def init(cfg, m: int, draw) -> dict:
    d, L = cfg.hidden_size, cfg.num_layers
    norm = draw.normal
    inner, ns, kk, rank = cache_spec.ssm_dims(cfg)
    u = draw.uniform(m, inner)
    dt = jnp.exp(u * math.log(DT_INIT[1] / DT_INIT[0])
                 + math.log(DT_INIT[0]))
    stack = {
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
    return {"ssm": stack,
            **{name + "_bias": jnp.zeros((L, d), cfg.dtype)
               for name in ("attn_norm", "mlp_norm")}}


def init_gmu(cfg, m: int, draw) -> dict:
    d, inner = cfg.hidden_size, cache_spec.ssm_dims(cfg)[0]
    return {"gmu": {"w_in": draw.normal(m, d, inner),
                    "w_out": draw.normal(m, inner, d)}}


def cache(cfg, p, dtype):
    # the state with the inner width on the lanes: ``[state, inner]`` is
    # whole (8, 128) tiles, ``[inner, state]`` would be padded eightfold
    # on the chip
    inner, n, k, _rank = cache_spec.ssm_dims(cfg)
    return cache_spec.Slot((("state", (n, inner), cache_spec.STATE_DTYPE),
                            ("conv", (k - 1, inner), dtype)))


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



def sequence(cfg, p, lp, h_in, ctx, hand: bool = False):
    """A scan over ``h_in`` [B, T, d] (``ctx.valid`` [B, T], padding on the
    right) from (``state`` [B, N, I] float32, ``tail`` [B, K-1, I]): keeps
    the state and the tail after the last valid position, and with
    ``hand`` hands on ``m`` [B, T, I] float32, the scan's output before
    its gate."""
    state, tail = ctx.state
    valid = ctx.valid
    kk = cfg.ssm_conv_kernel
    with jax.named_scope("ssm_proj"):
        h_in = h_in * valid[..., None].astype(h_in.dtype)
        c, z, dt, bm, cm, full = _ssm_inputs(cfg, lp, h_in, tail)
        dt = jnp.where(valid[..., None], dt, 0.0)
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        new_tail = tail_after(full, n_valid, kk - 1)
    with jax.named_scope("ssm_core"):
        state, m = ssm_scan(lp, state.astype(jnp.float32), c, dt, bm, cm)
    with jax.named_scope("ssm_proj"):
        return _ssm_out(lp, m, z), Kept(
            slot=(state, new_tail.astype(tail.dtype)),
            hands={"m": m} if hand else {})


def step(cfg, p, lp, h_in, ctx, hand: bool = False):
    from polyrl_tpu.ops.ssm_state import ssm_state_update

    st, tail = ctx.slot
    s = h_in.shape[0]
    with jax.named_scope("ssm_proj"):
        c, z, dt, bm, cm, full = _ssm_inputs(cfg, lp, h_in[:, None], tail[:s])
        tail = shift_tail(tail, full, ctx.live)
    with jax.named_scope("ssm_core"):
        st, m = ssm_state_update(lp, st, c[:, 0], dt[:, 0], bm[:, 0],
                                 cm[:, 0], ctx.live)
    with jax.named_scope("ssm_proj"):
        out = _ssm_out(lp, m, z[:, 0])
    ctx.load.add("ssm_state_rows", ctx.n_live)
    return out, Kept(slot=(st, tail), hands={"m": m} if hand else {})


def gmu(cfg, p, lp, h_in, ctx):
    """A gated memory unit, over a chunk or the step's rows: ``(silu(x
    W_1) * m) W_2``."""
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(mm(h_in, lp["w_in"]).astype(jnp.float32))
        return (mm((gate * ctx.hands["m"]).astype(h_in.dtype), lp["w_out"]),
                Kept())


def held(cfg, arrays, slot: int) -> np.ndarray:
    """The state, kept ``[N, I]`` and read as the published ``[I, N]``."""
    return np.asarray(arrays[0][slot]).astype(np.float32).T


_SCAN = dict(cache=cache, stack="ssm", init=init, row_parallel=("w_out",),
             replicated=("w_x", "w_dt"), slot_scope="ssm_core", held=held,
             counts=("ssm_state_rows",))
SSM = Mixer("ssm", sequence=sequence, step=step, **_SCAN)
SSM_MEM = Mixer("ssm_mem", sequence=functools.partial(sequence, hand=True),
                step=functools.partial(step, hand=True), **_SCAN)
GMU = Mixer("gmu", cache=lambda cfg, p, dtype: None, stack="gmu",
            init=init_gmu, row_parallel=("w_out",), sequence=gmu, step=gmu)
