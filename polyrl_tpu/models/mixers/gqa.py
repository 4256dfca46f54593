"""Grouped-query softmax attention with rope beside other kinds of layer,
in two kinds (``laguna``; ISSUE 47): ``gqa`` is full attention, whose keys
and values lie in pages (``cache_spec.Paged``); ``gqa_window`` attends over
the last ``sliding_window`` keys, the token itself among them, and keeps
them, rotated as they were written, in a ring of pages that belong to the
slot (``cache_spec.Ring``; ``mixers/diff.py`` has the ring). A kind has
its own count of query heads (``cache_spec.gqa_heads``) and its own rope
(``cache_spec.gqa_rope``), so its own stack of weights. H query heads over
Hkv K/V heads of size D, head j on K/V head j // (H / Hkv)::

    q = h Wq [H, D]   k = h Wk [Hkv, D]   v = h Wv [Hkv, D]
    g = sigmoid(h Wg) [H]                      (``attn_head_gate``)
    q, k <- rope(position) on the first ``partial_rotary_factor`` of a
            head's columns (rotate-half within them), the rest as they are;
            under YaRN cos and sin are scaled (``base.yarn_amplitude``)
    o_j = softmax_s(q_j . k[j // G, s] / sqrt(D) + mask) v[j // G]
    out = concat_j(g_j o_j) Wo

The uniform decoder (``decoder.py``'s stacked scan: every layer ``gqa``,
one head count, one rope) asks ``gqa``'s record for its cache alone; the
forms here run wherever ``cache_spec.layer_plan`` names the two kinds
beside each other or beside another MLP. Neither q/k norms nor projection
biases are written here.

The stacks ``params["layers"]["gqa"]`` and ``["gqa_window"]``::

    wqkv [L, d, (H + 2*Hkv)*D]  (q | k | v), wg [L, d, H], wo [L, H*D, d]"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.mixers import diff
from polyrl_tpu.models.mixers.base import (Kept, Mixer, key_block,
                                           rope_partial, yarn_amplitude,
                                           yarn_inv_freq)
from polyrl_tpu.models.quant import mm


def _kind_heads(cfg, kind: str) -> int:
    """Query heads of the layers of ``kind``: one count a kind, since a
    kind is one stack."""
    heads = {cache_spec.gqa_heads(cfg, p) for p in cache_spec.layer_plan(cfg)
             if p.mixer == kind}
    if len(heads) != 1:
        raise ValueError(f"{kind} layers of {sorted(heads)} query heads in "
                         "one stack")
    return heads.pop()


def _init(kind: str):
    def init(cfg, m: int, draw) -> dict:
        if cfg.use_qk_norm or cfg.attention_bias:
            raise NotImplementedError(
                "q/k norms or projection biases on a gqa layer beside "
                "other kinds of layer")
        h, hkv, hd, d = (_kind_heads(cfg, kind), cfg.num_kv_heads,
                         cfg.head_dim_, cfg.hidden_size)
        stack = {"wqkv": draw.normal(m, d, (h + 2 * hkv) * hd)}
        if cfg.attn_head_gate:
            stack["wg"] = draw.normal(m, d, h)
        return {kind: {**stack, "wo": draw.normal(m, h * hd, d)}}

    return init


def rope(cfg, p, x, positions):
    """The layer's rope on ``x`` [..., T, H, D] float32 at ``positions``
    [..., T]: the first ``partial_rotary_factor`` of a head's columns
    turned, the rest as they are (``base.rope_partial``); ``x`` as it is
    for attention WITHOUT positions (``attn_no_rope``: Nemotron-H's)."""
    r = cache_spec.gqa_rope(cfg, p)
    if r is None:
        return x
    rot = int(x.shape[-1] * r.partial_rotary_factor)
    return rope_partial(x, positions,
                        yarn_inv_freq(r.rope_theta, rot, r.scaling),
                        yarn_amplitude(r.scaling))


def _qkv(cfg, p, lp, h_in, positions):
    """(q [..., H, D], k and v [..., Hkv, D] in the model's type, q and k
    under the layer's rope; the gate [..., H] float32, None without)."""
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    lead = h_in.shape[:-1]
    with jax.named_scope("attn_qkv"):
        qkv = mm(h_in, lp["wqkv"])
        nq, nk = qkv.shape[-1] - 2 * hkv * hd, hkv * hd
        q, k = (rope(cfg, p, x.reshape(*lead, -1, hd).astype(jnp.float32),
                     positions).astype(qkv.dtype)
                for x in (qkv[..., :nq], qkv[..., nq:nq + nk]))
        gate = None
        if "wg" in lp:
            gate = jax.nn.sigmoid(mm(h_in, lp["wg"]).astype(jnp.float32))
        return q, k, qkv[..., nq + nk:].reshape(*lead, hkv, hd), gate


def _out(lp, o, gate):
    """From the heads' outputs ``o`` [..., H, D] to the sublayer's."""
    with jax.named_scope("attn_out"):
        if gate is not None:
            o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        return mm(o.reshape(*o.shape[:-2], -1), lp["wo"])


def gqa_attention(cfg, q, k, v, q_at, k_at, window: int = 0, chosen=None,
                  block: int = 0):
    """Softmax attention of every head for a batch: ``q`` [B, T, H, D]
    against keys ``k`` and values ``v`` [B, Tk, Hkv, D]; ``q_at`` [B, T]
    and ``k_at`` [B, Tk] are positions in the sequence (``k_at`` < 0: no
    key there); a query sees the keys at or before it, with ``window``
    only the last ``window`` of them, with ``chosen`` [B, Hkv, T, Tk /
    block] only those in the blocks of ``block`` keys (by their place in
    ``k``, Tk whole blocks) that its K/V head's mask names
    (``mixers/sparse.py``). Returns o [B, T, H, D] in ``q``'s
    type. Blocked over the keys with a running softmax, as
    ``diff_attention`` is, so that the scores of 16k keys never stand at
    once, and a block no query sees is skipped."""
    b, t, h, d = q.shape
    tk, hkv = k.shape[1:3]
    kb = min(key_block(cfg, b, t, h), tk)
    pad = -tk % kb
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_at = jnp.pad(k_at, ((0, 0), (0, pad)), constant_values=-1)
        if chosen is not None:
            chosen = jnp.pad(chosen, ((0, 0),) * 3 + ((0, pad // block),))
    qg = q.reshape(b, t, hkv, h // hkv, d)
    scale = d ** -0.5
    last = jnp.max(q_at)

    def attend(carry, i):
        m, l, acc = carry
        kk = jax.lax.dynamic_slice_in_dim(k, i * kb, kb, 1)
        vv = jax.lax.dynamic_slice_in_dim(v, i * kb, kb, 1)
        at = jax.lax.dynamic_slice_in_dim(k_at, i * kb, kb, 1)
        s = jnp.einsum("bqgjd,bkgd->bgjqk", qg, kk,
                       preferred_element_type=jnp.float32) * scale
        seen = (at[:, None, :] >= 0) & (at[:, None, :] <= q_at[:, :, None])
        if window:
            seen &= at[:, None, :] > q_at[:, :, None] - window
        seen = seen[:, None, None]
        if chosen is not None:
            took = jax.lax.dynamic_slice_in_dim(
                chosen, i * (kb // block), kb // block, 3)
            seen = seen & jnp.repeat(took, block, axis=3)[:, :, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, -1e30), axis=-1,
                                       keepdims=True))
        pr = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bgjqk,bkgd->bgjqd", pr.astype(vv.dtype), vv,
                        preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(pr, axis=-1, keepdims=True),
                alpha * acc + pv)

    def step(carry, i):
        at = jax.lax.dynamic_slice_in_dim(k_at, i * kb, kb, 1)
        near = jnp.any((at >= 0) & (at <= last))
        return jax.lax.cond(near, attend, lambda c, _i: c, carry, i), None

    shape = (b, hkv, h // hkv, t)
    init = (jnp.full((*shape, 1), -1e30, jnp.float32),
            jnp.zeros((*shape, 1), jnp.float32),
            jnp.zeros((*shape, d), jnp.float32))
    (_m, l, acc), _ = jax.lax.scan(step, init, jnp.arange((tk + pad) // kb))
    o = acc / jnp.maximum(l, 1e-30)                     # [B, g, j, T, D]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d).astype(q.dtype)


def _attend(cfg, p, lp, h_in, ctx, before, scope: str, window: int = 0):
    """A chunk's attention over its own keys and those ``before`` it ((k,
    v, the position each holds, -1 for none), None for none): (the
    sublayer's output, the chunk's (k, v))."""
    q, k, v, gate = _qkv(cfg, p, lp, h_in, ctx.positions)
    keys, values = k, v
    k_at = jnp.where(ctx.valid, ctx.positions, -1)
    if before is not None:
        keys = jnp.concatenate([before[0].astype(k.dtype), k], axis=1)
        values = jnp.concatenate([before[1].astype(v.dtype), v], axis=1)
        k_at = jnp.concatenate([before[2], k_at], axis=1)
    with jax.named_scope(scope):
        o = gqa_attention(cfg, q, keys, values, ctx.positions, k_at, window)
    return _out(lp, o, gate), (k, v)


def sequence(cfg, p, lp, h_in, ctx):
    """Full attention over ``h_in`` [B, T, d] and, with a prefix ((k, v)
    [B, Tp, Hkv, D] of the tokens before, how many are real [B]), over
    their keys too: keeps this chunk's (k, v) for its pages."""
    before = None
    if ctx.prefix is not None:
        (pk, pv), pre_len = ctx.prefix
        tp = jnp.arange(pk.shape[1], dtype=jnp.int32)[None]
        before = (pk, pv, jnp.where(tp < pre_len[:, None], tp, -1))
    out, kv = _attend(cfg, p, lp, h_in, ctx, before, "attn_core")
    return out, Kept(pages=kv)


def sequence_window(cfg, p, lp, h_in, ctx):
    """``ctx.state``: (k, v, the positions they hold) of the ring before
    the chunk, None for none; keeps the chunk's (k, v) for the ring."""
    out, kv = _attend(cfg, p, lp, h_in, ctx, ctx.state, "swa_core",
                      cfg.sliding_window)
    return out, Kept(slot=kv)


def step(cfg, p, lp, h_in, ctx):
    """Writes the token's keys and values to its pages and attends over
    them."""
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    q, k, v, gate = _qkv(cfg, p, lp, h_in, ctx.positions)
    with jax.named_scope("attn_core"):
        pages = paged_kv_write(*ctx.pages, ctx.write_page, ctx.write_off,
                               k, v)
        o = paged_attention(q, *pages, ctx.page_table, ctx.attn_lens)
    ctx.load.add("paged_rows_read", ctx.rows_read)
    return _out(lp, o, gate), Kept(pages=pages)


def step_window(cfg, p, lp, h_in, ctx):
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    ring = ctx.per[p.mixer]
    q, k, v, gate = _qkv(cfg, p, lp, h_in, ctx.positions)
    with jax.named_scope("swa_core"):
        slot = paged_kv_write(*ctx.slot, ring.page, ring.off, k, v)
        o = paged_attention(q, *slot, ring.table, ring.lens)
    ctx.load.add("window_rows_read", ring.read)
    return _out(lp, o, gate), Kept(slot=slot)


GQA = Mixer(
    "gqa", lambda cfg, p, dtype: cache_spec.Paged(
        2, cfg.num_kv_heads, cfg.head_dim_),
    stack="gqa", init=_init("gqa"), sequence=sequence, step=step,
    row_parallel=("wo",), replicated=("wg",), pages_scope="attn_core",
    pages_by_slabs=True, counts=("paged_rows_read",))
GQA_WINDOW = Mixer(
    "gqa_window", lambda cfg, p, dtype: cache_spec.Ring(
        cfg.num_kv_heads, cfg.head_dim_, cfg.sliding_window, dtype),
    stack="gqa_window", init=_init("gqa_window"), sequence=sequence_window,
    step=step_window, per_step=diff.per_step, slot_scope="swa_core",
    read_slot=diff.ring_read, write_slot=diff.ring_write, held=diff.held,
    row_parallel=("wo",), replicated=("wg",), counts=("window_rows_read",))
