"""Compressed convolutional attention (ZAYA1's decoder, ``zaya``: CCA in
every layer; ISSUE 41; the nine steps are in the docstring of
``benchmark/references/cca_moe.py``, the choices the published config does
not settle in ``benchmark/configs/zaya1-8b.json`` under ``assumed``). It
keeps a K/V pair in pages AND convolution tails in the slot. Hq query heads
over Hkv K/V heads of size D, ``c = [q~ ; k~]``::

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
sublayer's residual is ``(a_r x + b_r) + (a_o F(rms(x)) + b_o)``
(``hybrid._residual``; the scales ``attn_res``, ``mlp_res`` [L, 4, d] lie
beside the stacks). One-token decode runs the GQA kernels
(``ops.paged_attention``) on the pair.

The stack ``params["layers"]["cca"]``::

    w_in [Lc, d, (Hq+Hkv)*D + Hkv*D]  (q~ | k~ | va | vb),
    conv0 [Lc, K0, (Hq+Hkv)*D], conv1 [Lc, K1, Hq+Hkv, D, D],
    tau [Lc, Hkv] float32, wo [Lc, Hq*D, d]"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.mixers.base import (Kept, Mixer, l2norm,
                                           rope_partial, set_rows,
                                           tail_after)
from polyrl_tpu.models.quant import mm


def init(cfg, m: int, draw) -> dict:
    d, L = cfg.hidden_size, cfg.num_layers
    norm = draw.normal
    hq, hkv, hd = cache_spec.cca_dims(cfg)
    mixed = (hq + hkv) * hd
    stack = {
        "w_in": norm(m, d, mixed + hkv * hd),
        # both convolutions start near the identity on the newest
        # position, as KDA's do
        "conv0": norm(m, cfg.cca_time0, mixed).at[:, 0].add(1.0),
        "conv1": norm(m, cfg.cca_time1, hq + hkv, hd, hd).at[:, 0].add(
            jnp.eye(hd, dtype=cfg.dtype)),
        "tau": jnp.ones((m, hkv), jnp.float32),
        "wo": norm(m, hq * hd, d),
    }
    # both sublayers' residuals scaled: a_r and a_o one, b_r and b_o drawn
    one = jnp.array([1.0, 0.0, 1.0, 0.0], cfg.dtype)[None, :, None]
    return {"cca": stack,
            **{name: norm(L, 4, d) * (1 - one) + one
               for name in ("attn_res", "mlp_res")}}


def cache(cfg, p, dtype):
    hq, hkv, d = cache_spec.cca_dims(cfg)
    mixed = (hq + hkv) * d
    return cache_spec.PagedAndSlot(
        cache_spec.Paged(2, hkv, d),
        cache_spec.Slot((("latent", (cfg.cca_time0 - 1, mixed), dtype),
                         ("mixed", (cfg.cca_time1 - 1, mixed), dtype),
                         ("value", (hkv * d // 2,), dtype))))


def cca_rope(cfg, x, positions):
    """Rope on the first ``partial_rotary_factor`` of each head's columns
    of ``x`` [B, T, H, D] float32 (rotate-half within them, frequencies
    ``theta ** (-2i / rot)``), the rest as they are."""
    rot = int(x.shape[-1] * cfg.partial_rotary_factor)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                    / rot))
    return rope_partial(x, positions, inv)


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
    q = l2norm(q) * hd ** 0.5
    k = l2norm(k) * (hd ** 0.5 * lp["tau"].astype(f32)[:, None])
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
        return tail_after(full, n_valid, k)

    return (rows(full_c, cfg.cca_time0 - 1), rows(full_u, cfg.cca_time1 - 1),
            rows(full_vb, 1)[:, 0])


def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] from the three tails at the chunk's start
    and, with a prefix ((k, v) [B, Tp, Hkv, D] of the tokens before, how
    many are real [B]), over their keys too: keeps this chunk's (k, v) and
    the tails after the last valid position."""
    from polyrl_tpu.ops.attention import attention

    b, t, _ = h_in.shape
    valid = ctx.valid
    with jax.named_scope("cca_proj"):
        proj = mm(h_in, lp["w_in"])
    with jax.named_scope("cca_mix"):
        q, k, v, fulls = _cca_mix(cfg, lp, proj, ctx.positions, ctx.state)
        new_tails = _cca_tails(cfg, fulls,
                               jnp.sum(valid.astype(jnp.int32), axis=1))
    with jax.named_scope("attn_core"):
        keys, values, key_ok, tp = k, v, valid, 0
        if ctx.prefix is not None:
            (pk, pv), pre_len = ctx.prefix
            tp = pk.shape[1]
            keys = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            values = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            key_ok = jnp.concatenate(
                [jnp.arange(tp)[None] < pre_len[:, None], valid], axis=1)
        seen = (jnp.arange(tp + t)[None, :] <= tp + jnp.arange(t)[:, None])
        mask = (seen[None] & key_ok[:, None, :])[:, None]
        o = attention(q, keys, values, mask=mask).reshape(b, t, -1)
    with jax.named_scope("cca_proj"):
        return mm(o, lp["wo"]), Kept(pages=(k, v), slot=new_tails)


def step(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    s = h_in.shape[0]
    with jax.named_scope("cca_proj"):
        proj = mm(h_in, lp["w_in"])
    with jax.named_scope("cca_mix"):
        tails = tuple(a[:s] for a in ctx.slot)
        q, k, v, fulls = _cca_mix(cfg, lp, proj[:, None],
                                  ctx.positions[:, None], tails)
        new = _cca_tails(cfg, fulls, 1)
        slot = tuple(
            set_rows(a, jnp.where(
                ctx.live.reshape(-1, *[1] * (a.ndim - 1)),
                n.astype(a.dtype), a[:s]))
            for a, n in zip(ctx.slot, new))
    with jax.named_scope("attn_core"):
        pages = paged_kv_write(*ctx.pages, ctx.write_page, ctx.write_off,
                               k[:, 0], v[:, 0])
        o = paged_attention(q[:, 0], *pages, ctx.page_table,
                            ctx.attn_lens).reshape(s, -1)
    ctx.load.add("cca_tail_rows", ctx.n_live)
    with jax.named_scope("cca_proj"):
        out = mm(o, lp["wo"])
    return out, Kept(pages=pages, slot=slot)


def held(cfg, arrays, slot: int) -> np.ndarray:
    """The three tails, flattened side by side ``[(K0-1 + K1-1) * C +
    Hkv*D/2]``."""
    return np.concatenate([np.asarray(a[slot], np.float32).reshape(-1)
                           for a in arrays])


CCA = Mixer(
    "cca", cache, stack="cca", init=init, row_parallel=("wo",),
    sequence=sequence, step=step, pages_scope="attn_core",
    slot_scope="cca_mix", held=held, counts=("cca_tail_rows",))
