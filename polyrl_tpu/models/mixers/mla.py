"""Multi-head latent attention: the Ling-3.0 hybrid family's attention
layers and every layer of DeepSeek-V3's decoder (dots.vlm1 / dots.llm1
share it key for key). One block serves both; what differs is an option of
the configuration (``q_lora_rank``: a normed query latent;
``mla_head_gate``; ``rope_scaling``: YaRN).

The cache holds ``[rms(c) | rope(kr)]``, one row of ``rank + rope`` a
token; prefill expands it through ``wkv_b`` a block of keys at a time
(``mla_expanded``), decode folds ``wkv_b``'s key half into the query and
applies its value half after the sum (the absorbed form). The logits'
scale is ``(nope + rope) ** -0.5``, times YaRN's ``m ** 2`` where the
configuration scales its rope (``mla_scale``).

The stack ``params["layers"]["mla"]``::

    wq [Lm, d, H*(nope+rope)]   (or, with a query latent:
    wq_a [Lm, d, qrank], q_norm [Lm, qrank], wq_b [Lm, qrank, H*(nope+rope)]),
    wkv_a [Lm, d, rank+rope], kv_norm [Lm, rank],
    wkv_b [Lm, rank, H*(nope+v)], wgate [Lm, d, H] (with ``mla_head_gate``),
    wo [Lm, H*v, d]"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import _scatter_token_kv, rms_norm
from polyrl_tpu.models.mixers.base import (Kept, Mixer, key_block,
                                           yarn_amplitude, yarn_inv_freq,
                                           yarn_mscale)
from polyrl_tpu.models.quant import mm


def init(cfg, m: int, draw) -> dict:
    d, h = cfg.hidden_size, cfg.num_heads
    norm, ones = draw.normal, draw.ones
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
    qr = cfg.q_lora_rank
    query = ({"wq_a": norm(m, d, qr), "q_norm": ones(m, qr),
              "wq_b": norm(m, qr, h * (nope + rope))} if qr
             else {"wq": norm(m, d, h * (nope + rope))})
    stack = {
        **query,
        "wkv_a": norm(m, d, r + rope), "kv_norm": ones(m, r),
        "wkv_b": norm(m, r, h * (nope + vd)),
        "wo": norm(m, h * vd, d),
    }
    if cfg.mla_head_gate:
        stack["wgate"] = norm(m, d, h)
    return {"mla": stack}


def cache(cfg, p, dtype):
    return cache_spec.Paged(1, 1, cache_spec.latent_row(cfg))


def rope_inv_freq(cfg) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` frequencies of a latent layer's rope,
    float64, under YaRN where the configuration scales it
    (``base.yarn_inv_freq``)."""
    return yarn_inv_freq(cfg.rope_theta, cfg.qk_rope_head_dim,
                         cfg.rope_scaling)


def rope_amplitude(cfg) -> float:
    """What YaRN multiplies cos and sin by (``base.yarn_amplitude``)."""
    return yarn_amplitude(cfg.rope_scaling)


def mla_scale(cfg) -> float:
    """The logits' scale: ``(nope + rope) ** -0.5``, times the square of
    YaRN's ``0.1 * mscale_all_dim * ln(factor) + 1`` where it is set."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s is not None and s.rope_type == "yarn" and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
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


def _mla_qkv(cfg, lp, h_in, positions):
    """``h_in`` [..., T, d] -> (q_nope [..., T, H, nope], q_rope [..., T,
    H, rope] after rope, the queries through their normed latent where the
    configuration has one, latent rows [..., T, row] in the model's dtype:
    ``rms(c)`` beside ``rope(kr)``, zeros up to ``cache_spec.latent_row``)."""
    hh = cfg.num_heads
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    lead = h_in.shape[:-1]
    if cfg.q_lora_rank:
        cq = rms_norm(mm(h_in, lp["wq_a"]), lp["q_norm"], cfg.rms_norm_eps)
        q = mm(cq, lp["wq_b"])
    else:
        q = mm(h_in, lp["wq"])
    # the heads are cut out of the PRODUCT: without the barrier XLA moves
    # the reshape onto the weight and writes a layer's ``wq_b`` out anew,
    # heads major, before every product (75 MB a layer at 128 heads)
    q = jax.lax.optimization_barrier(q).reshape(*lead, hh, nope + rope)
    kv = mm(h_in, lp["wkv_a"])
    c = rms_norm(kv[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
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

    ``in_stack``, where ``in_kernel`` says so: (the stacked ``wkv_b``
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



def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] and, with a prefix, the latent rows of the
    tokens before: keeps the chunk's latent rows."""
    b, t, _ = h_in.shape
    valid = ctx.valid
    with jax.named_scope("mla_proj"):
        q_nope, q_rope, lat = _mla_qkv(cfg, lp, h_in, ctx.positions)
    with jax.named_scope("mla_core"):
        if ctx.prefix is None:
            keys, key_ok = lat, valid
            q_at = jnp.broadcast_to(jnp.arange(t), (b, t))
        else:
            pre, pre_len = ctx.prefix
            tp = pre.shape[1]
            keys = jnp.concatenate([pre, lat], axis=1)
            key_ok = jnp.concatenate(
                [jnp.arange(tp)[None] < pre_len[:, None], valid], axis=1)
            q_at = jnp.broadcast_to(tp + jnp.arange(t), (b, t))
        o = mla_expanded(cfg, lp, q_nope, q_rope, keys, key_ok, q_at)
    with jax.named_scope("mla_proj"):
        return _mla_out(cfg, lp, h_in, o), Kept(pages=lat)


def in_kernel(cfg, rows: int) -> bool:
    """Whether a decode step of ``rows`` rows multiplies ``wkv_b`` where
    it lies in the stack (``ops/mla_proj.py``): head sizes the kernels
    take, the backend."""
    from polyrl_tpu.ops import mla_proj

    return mla_proj.in_kernel(cfg, rows)


def step(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.mla_attention import latent_paged_attention

    wkv_b = ((ctx.stack["wkv_b"], ctx.index)
             if in_kernel(cfg, h_in.shape[0]) else None)
    with jax.named_scope("mla_proj"):
        q_nope, q_rope, lat = _mla_qkv(cfg, lp, h_in[:, None],
                                       ctx.positions[:, None])
        q_lat = mla_absorb(cfg, lp, q_nope[:, 0], q_rope[:, 0], wkv_b)
    with jax.named_scope("mla_core"):
        pool = _scatter_token_kv(ctx.pages, ctx.write_page, ctx.write_off,
                                 lat)
        o_lat = latent_paged_attention(
            q_lat, pool, ctx.page_table, ctx.attn_lens, cfg.kv_lora_rank,
            mla_scale(cfg))
    ctx.load.add("mla_rows_read", ctx.rows_read)
    with jax.named_scope("mla_proj"):
        out = _mla_out(cfg, lp, h_in, mla_unabsorb(cfg, lp, o_lat, wkv_b))
    return out, Kept(pages=pool)


MLA = Mixer(
    "mla", cache, stack="mla", init=init, row_parallel=("wo",),
    replicated=("wgate", "wkv_a"), sequence=sequence, step=step,
    pages_scope="mla_core", counts=("mla_rows_read",), counts_in_routed=True,
    kernel=("mla_proj_kernel_steps", in_kernel))
