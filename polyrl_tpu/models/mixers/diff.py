"""The SambaY family's attention layers (``phi4flash``; ISSUE 43):
differential attention without positions in three kinds. ``swa`` attends
over the last ``sliding_window`` keys, the token itself among them, and
keeps them in a ring of pages that belong to the slot
(``cache_spec.Ring``); ``diff`` is full attention that WRITES the one paged
K/V pair the model has; ``cross`` has queries of its own over the ``diff``
layer's keys and values (in a chunk: handed down the call as ``kv``; in a
step: the producer's pages, ``cache_spec.Reads``) and keeps nothing.
Hd = Hq/2 heads over Hkv/2 K/V pairs, head j on pair j // 2::

    a1 = softmax(q[j,0] k[g,0]^T / sqrt(D))   a2 = softmax(q[j,1] k[g,1]^T / sqrt(D))
    o_j = rms_2D((a1 - lam a2) [v[g,0] | v[g,1]]) * sub_norm * (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    lam_init = 0.8 - 0.6 exp(-0.3 i)          (i the published layer)

A pair's two heads lie side by side in the cache (``[k0 | k1]``, ``[v0 |
v1]``, 2D = 128 wide), and a decode step's queries are ``(q[j,0] | 0)`` and
``(0 | q[j,1])``: the paged kernels written for one softmax a head of 128
(``ops.paged_attention``) then give ``a1 [v0 | v1]`` and ``a2 [v0 | v1]``
exactly.

The stacks ``params["layers"]["attn"]`` (``swa`` and ``diff``) and
``["cross"]``::

    attn:  wqkv [La, d, (Hq + 2*Hkv)*D], bqkv, wo [La, Hq*D, d], bo [La, d],
           lq1 lk1 lq2 lk2 [La, D] float32, sub_norm [La, 2*D]
    cross: wq [Lc, d, Hq*D], bq, wo, bo, lq1 lk1 lq2 lk2, sub_norm"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import (_gather_slabs_kv, _scatter_slabs,
                                      rms_norm)
from polyrl_tpu.models.mixers.base import Kept, Mixer, key_block
from polyrl_tpu.models.quant import mm

# a layer's four lambda vectors as drawn
LAMBDA_STD = 0.1


def _heads(cfg, m: int, draw) -> dict:
    hq, hd, d = cfg.num_heads, cfg.head_dim_, cfg.hidden_size
    lam = {k: draw.normal(m, hd, dtype=jnp.float32,
                          scale=LAMBDA_STD / draw.std)
           for k in ("lq1", "lk1", "lq2", "lk2")}
    return {**lam, "sub_norm": draw.ones(m, 2 * hd),
            "wo": draw.normal(m, hq * hd, d),
            "bo": jnp.zeros((m, d), cfg.dtype)}


def init(cfg, m: int, draw) -> dict:
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    wide = (hq + 2 * hkv) * hd
    return {"attn": {"wqkv": draw.normal(m, cfg.hidden_size, wide),
                     "bqkv": jnp.zeros((m, wide), cfg.dtype),
                     **_heads(cfg, m, draw)}}


def init_cross(cfg, m: int, draw) -> dict:
    wide = cfg.num_heads * cfg.head_dim_
    return {"cross": {"wq": draw.normal(m, cfg.hidden_size, wide),
                      "bq": jnp.zeros((m, wide), cfg.dtype),
                      **_heads(cfg, m, draw)}}


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


def ring_read(cfg, ring, at):
    """What the rings of ``at.slots`` [B] hold after ``at.prefix_len``
    tokens: (k, v [B, window, pairs, 2D], the position of the token each
    row holds [B, window], -1 where it holds none: a ring's rows hold no
    token until one is written). Token ``t`` lies at row ``t % window``."""
    w = cfg.sliding_window
    with jax.named_scope("swa_core"):
        k, v = _gather_slabs_kv(
            ring, ring_pages(cfg, at.slots, ring[0].shape[2]))
        newest = at.prefix_len - 1
        held = newest - (newest - jnp.arange(w, dtype=jnp.int32)) % w
        held = jnp.where((at.prefix_len > 0) & (held >= 0), held, -1)
        return k, v, jnp.broadcast_to(held, (at.slots.shape[0], w))


def ring_write(cfg, ring, at, kv, old):
    """The rings of ``at.slots`` [B] after a chunk's (k, v) [B, T, pairs,
    2D] of ``at.lens`` [B] real tokens that follow ``at.prefix_len``: each
    of the last ``window`` of them at its position modulo the window,
    every other row as ``old`` has it (``ring_read``'s at the chunk's
    start). The whole ring is written back, by pages
    (``_scatter_slabs``)."""
    w = cfg.sliding_window
    ps = ring[0].shape[2]
    r = jnp.arange(w, dtype=jnp.int32)[None, :]
    last = at.lens[:, None] - 1
    # the chunk's newest token that lies at ring row r
    c = last - (at.prefix_len + last - r) % w
    pages = ring_pages(cfg, at.slots, ps)

    def one(a, new, was):
        rows = jnp.take_along_axis(new, jnp.maximum(c, 0)[:, :, None, None],
                                   axis=1)
        rows = jnp.where((c >= 0)[:, :, None, None], rows.astype(a.dtype),
                         was.astype(a.dtype))
        return _scatter_slabs(a, pages, rows)

    return one(ring[0], kv[0], old[0]), one(ring[1], kv[1], old[1])


def _attend(cfg, p, lp, q, keys, values, positions, k_at, scope: str,
            window: int = 0):
    with jax.named_scope(scope):
        o = diff_attention(cfg, q, keys, values, positions, k_at, window)
    return _diff_out(cfg, lp, o, p.published)


def _own(cfg, lp, h_in, ctx):
    """(q, k, v of the chunk, the position each of its tokens holds as a
    key, -1 for padding)."""
    q, k, v = _diff_qkv(cfg, lp, h_in)
    return q, k, v, jnp.where(ctx.valid, ctx.positions, -1)


def sequence_swa(cfg, p, lp, h_in, ctx):
    """``ctx.state``: (k, v, the positions they hold) of the ring before
    the chunk, None for none; keeps the chunk's (k, v) for the ring."""
    q, k, v, at = _own(cfg, lp, h_in, ctx)
    keys, values, k_at = k, v, at
    if ctx.state is not None:
        keys = jnp.concatenate([ctx.state[0].astype(k.dtype), k], axis=1)
        values = jnp.concatenate([ctx.state[1].astype(v.dtype), v], axis=1)
        k_at = jnp.concatenate([ctx.state[2], at], axis=1)
    out = _attend(cfg, p, lp, q, keys, values, ctx.positions, k_at,
                  "swa_core", cfg.sliding_window)
    return out, Kept(slot=(k, v))


def sequence_diff(cfg, p, lp, h_in, ctx):
    """Keeps the chunk's (k, v) for its pages and hands on ``kv``: the
    keys, the values and their positions, those before the chunk among
    them."""
    q, k, v, at = _own(cfg, lp, h_in, ctx)
    keys, values, k_at = k, v, at
    if ctx.prefix is not None:
        (pk, pv), pre_len = ctx.prefix
        tp = jnp.arange(pk.shape[1], dtype=jnp.int32)[None]
        keys = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        values = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        k_at = jnp.concatenate(
            [jnp.where(tp < pre_len[:, None], tp, -1), at], axis=1)
    out = _attend(cfg, p, lp, q, keys, values, ctx.positions, k_at,
                  "attn_core")
    return out, Kept(pages=(k, v), hands={"kv": (keys, values, k_at)})


def sequence_cross(cfg, p, lp, h_in, ctx):
    q = _own(cfg, lp, h_in, ctx)[0]
    keys, values, k_at = ctx.hands["kv"]
    return _attend(cfg, p, lp, q, keys, values, ctx.positions, k_at,
                   "attn_core"), Kept()


def per_step(cfg, ctx):
    """A step's view of the rings: row r's pages are slot r's, the token
    at its position modulo the window, the row as long as it has tokens."""
    s, ps, w = ctx.live.shape[0], ctx.page_size, cfg.sliding_window
    with jax.named_scope("glue"):   # once a step, for every window layer
        table = ring_pages(cfg, jnp.arange(s, dtype=jnp.int32), ps)
        at = ctx.seq_lens % w
        page = jnp.where(ctx.live, table[jnp.arange(s), at // ps], 0)
        off = jnp.where(ctx.live, at % ps, 0)
        lens = jnp.minimum(ctx.attn_lens, w)
        return types.SimpleNamespace(table=table, page=page, off=off,
                                     lens=lens, read=jnp.sum(lens))


def _step_out(cfg, p, lp, o, s: int):
    with jax.named_scope("diff_mix"):
        o = o.reshape(s, -1, 2, o.shape[-1])
    return _diff_out(cfg, lp, o, p.published)


def step_swa(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    ring = ctx.per["swa"]
    q, k, v = _diff_qkv(cfg, lp, h_in)
    with jax.named_scope("attn_qkv"):
        q = paired_queries(q)
    with jax.named_scope("swa_core"):
        slot = paged_kv_write(*ctx.slot, ring.page, ring.off, k, v)
        o = paged_attention(q, *slot, ring.table, ring.lens,
                            cfg.head_dim_ ** -0.5)
    ctx.load.add("window_rows_read", ring.read)
    return _step_out(cfg, p, lp, o, h_in.shape[0]), Kept(slot=slot)


def step_paged(cfg, p, lp, h_in, ctx):
    """``diff`` writes the token's keys and values to its pages and
    attends over them; ``cross`` (no keys of its own) attends over the
    producer's."""
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    q, k, v = _diff_qkv(cfg, lp, h_in)
    with jax.named_scope("attn_qkv"):
        q = paired_queries(q)
    pages = ctx.pages
    with jax.named_scope("attn_core"):
        if k is not None:
            pages = paged_kv_write(*pages, ctx.write_page, ctx.write_off,
                                   k, v)
        o = paged_attention(q, *pages, ctx.page_table, ctx.attn_lens,
                            cfg.head_dim_ ** -0.5)
    ctx.load.add("shared_kv_rows_read", ctx.rows_read)
    return (_step_out(cfg, p, lp, o, h_in.shape[0]),
            Kept(pages=None if k is None else pages))


def held(cfg, arrays, slot: int) -> np.ndarray:
    """A ring: the slot's pages, rows in ring order, as (k | v) ``[window,
    pairs, 2 * 2D]`` (token ``t`` at row ``t % window``)."""
    pairs, _n, ps, width = arrays[0].shape
    n = cfg.sliding_window // ps
    return np.concatenate(
        [np.asarray(a[:, 1 + slot * n:1 + (slot + 1) * n], np.float32)
         .reshape(pairs, n * ps, width).swapaxes(0, 1) for a in arrays],
        axis=-1)


def _pair(cfg):
    _h, pairs, width = cache_spec.diff_dims(cfg)
    return pairs, width


def _producer(cfg) -> int:
    """The place in the plan of the one ``diff`` layer, whose pages the
    ``cross`` layers read."""
    return next(l for l, p in enumerate(cache_spec.layer_plan(cfg))
                if p.mixer == "diff")


DIFF = Mixer(
    "diff", lambda cfg, p, dtype: cache_spec.Paged(2, *_pair(cfg)),
    stack="attn", init=init, sequence=sequence_diff, step=step_paged,
    row_parallel=("wo",), pages_scope="attn_core", pages_by_slabs=True,
    counts=("shared_kv_rows_read",))
SWA = Mixer(
    "swa", lambda cfg, p, dtype: cache_spec.Ring(
        *_pair(cfg), cfg.sliding_window, dtype),
    stack="attn", init=init, sequence=sequence_swa, step=step_swa,
    per_step=per_step, slot_scope="swa_core", read_slot=ring_read,
    write_slot=ring_write, held=held, row_parallel=("wo",),
    counts=("window_rows_read",))
CROSS = Mixer(
    "cross", lambda cfg, p, dtype: cache_spec.Reads(_producer(cfg)),
    stack="cross", init=init_cross, sequence=sequence_cross, step=step_paged,
    row_parallel=("wo",), counts=("shared_kv_rows_read",))
