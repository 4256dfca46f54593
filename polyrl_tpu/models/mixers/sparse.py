"""Block-sparse softmax attention (MiniCPM-SALA's ``minicpm4`` layers:
InfLLM-V2; ISSUE 56; every reading the published config does not settle is
listed in ``benchmark/configs/minicpm-sala.json`` under ``assumed``; the
equations one by one are the docstring of
``benchmark/references/sala_sparse_linear.py``). H query heads over Hkv K/V
heads of size D, G = H / Hkv queries a group, no positional encoding::

    q = rms(h Wq) * q_norm [H, D]   k = rms(h Wk) * k_norm [Hkv, D]
    v = h Wv [Hkv, D]               (rms over a head's D, a vector [D])
    pooled key c_j = mean(k[stride j : stride j + kernel])   kernel = 2 stride
    for the token at position t (n = t + 1 keys), a K/V head g at a time:
      p_h = softmax_j(q_h . c_j / sqrt(D))  over the j with stride j + kernel <= n
      s_j = sum of p_h[j] over the group's G heads
      b_m = max(s_j : the pooled keys that overlap block m's ``block`` keys)
      blocks below ``init_blocks`` and the last ``window / block`` blocks
      (the token's own among them) count as +inf; the chosen set is the
      ``topk`` best blocks at or before the token's own, ties to the lower
      block; with n <= ``dense_len`` every block
    o_h = softmax attention of q_h over the keys <= t of the chosen blocks
    out = (concat_h(o_h) * sigmoid(h Wg)) Wo        the gate [H * D] wide

What a sequence keeps (``cache``): the K/V pair in pages AND the pooled
keys, float32, ``[N, page / stride * Hkv, D]``: a page of ``block`` tokens
carries its ``block / stride`` pooled keys a head (pooled key j of head g
lies at row ``(j % r) * Hkv + g`` of page ``j // r`` of the row's table, r
= ``block / stride``: one page table, one allocator; the rows a page
gathers are then the row's pooled keys in order, and no transpose stands
between the gather and the scores: 1.7 ms a step at the cell's sizes, my
chip run, PR 56). The engine's page IS the model's block (``step`` refuses
another page size), so a chosen block is a page.

A decode step writes the token's key and value (``paged_kv_write``), then
under ``sparse_select``: the pooled key that the token completes (token
``stride j + kernel - 1``: the mean of the last ``kernel`` keys, read back
from the pages as two slabs of ``stride`` rows), the scores of the row's
queries against its pooled keys, the blocks' scores, and the choice
WITHOUT a sort (a block's rank is the count of blocks that beat it, a
[blocks, blocks] comparison: PR 49 found ``lax.top_k`` to be a full sort
on this chip); the chosen blocks in rising order, the row's part-filled
own block last, are a page table a (row, K/V head). ``selected_table`` is
the one function that builds it, in one of two forms of the same
equations: on a TPU at the published sizes the kernel of
``ops/sparse_select.py``, a row a program over THAT ROW'S pages of the
store, which stays in HBM (PR 57; ``in_kernel``; the engine counts
``sparse_kernel_steps``); elsewhere, and as the kernel's oracle, the jnp
form, which gathers every row's pooled keys a page at a time through the
table at its full width, as an embedding's rows are, and ranks the table's
width of blocks against itself. Then attention over
that table by the GQA decode kernel (``ops.paged_attention``) as it is:
the pools seen as ONE K/V head of ``Hkv * N`` pages and each (row, K/V
head) as a row of G query heads, so its block plan, DMA ring and HBM
pinning serve this layer too. The table and the keys it holds stay in the
row's SLOT (``picked`` [slots, Hkv, W + 1] int32, 1 KB a row a layer): what
the slot's last step attended can be read back
(``CBEngine.recurrent_state``) and held to a reference, which no output of
a model whose attention is near uniform would show. Prefill chooses per
query token by the same functions and attends through the blocks' mask over
scores blocked over the keys with a running softmax (``gqa.gqa_attention``
with ``chosen``: no ``[T, heads, n]`` score array stands whole).

The stack ``params["layers"]["sparse"]``::

    wqkv [Ls, d, (H + 2*Hkv)*D]  (q | k | v), q_norm k_norm [Ls, D],
    wg [Ls, d, H*D], wo [Ls, H*D, d]"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import rms_norm
from polyrl_tpu.models.mixers.base import Kept, Mixer, set_rows
from polyrl_tpu.models.mixers.gqa import gqa_attention
from polyrl_tpu.models.quant import mm

_HI = jax.lax.Precision.HIGHEST
NEG = -1e30
# queries whose choice is worked out at once in prefill: the [queries,
# blocks, blocks] comparison of a piece is 35M entries at 520 blocks
_CHOICE_QUERIES = 64


def geometry(cfg) -> tuple[int, int, int, int]:
    """(tokens a pooled key starts after the one before, tokens it spans,
    tokens a block, pooled keys that START in a block)."""
    stride, kernel, block = (cfg.sparse_kernel_stride, cfg.sparse_kernel_size,
                             cfg.sparse_block_size)
    if kernel != 2 * stride or block % stride:
        raise NotImplementedError(
            f"pooled keys of {kernel} tokens every {stride} in blocks of "
            f"{block}: the kernel is two strides and a block whole strides")
    return stride, kernel, block, block // stride


def init(cfg, m: int, draw) -> dict:
    if not cfg.use_qk_norm:
        raise NotImplementedError("a sparse layer without q/k norms")
    h, hkv, hd, d = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
                     cfg.hidden_size)
    return {"sparse": {
        "wqkv": draw.normal(m, d, (h + 2 * hkv) * hd),
        "q_norm": draw.ones(m, hd), "k_norm": draw.ones(m, hd),
        "wg": draw.normal(m, d, h * hd), "wo": draw.normal(m, h * hd, d),
    }}


def table_width(cfg) -> int:
    """The most pages a (row, K/V head) attends: ``topk``, or the blocks
    of ``dense_len`` keys."""
    return max(cfg.sparse_topk,
               -(-cfg.sparse_dense_len // cfg.sparse_block_size))


def cache(cfg, p, dtype):
    hkv = cfg.num_kv_heads
    return cache_spec.PagedAndSlot(
        cache_spec.Paged(2, hkv, cfg.head_dim_, pooled=geometry(cfg)[0]),
        cache_spec.Slot((("picked", (hkv, table_width(cfg) + 1),
                          jnp.int32),)))


def _qkv(cfg, lp, h_in):
    """(q [..., H, D], k and v [..., Hkv, D] in the model's type, q and k
    under their norms; the gate [..., H * D] float32)."""
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    lead = h_in.shape[:-1]
    with jax.named_scope("attn_qkv"):
        qkv = mm(h_in, lp["wqkv"])
        nq, nk = qkv.shape[-1] - 2 * hkv * hd, hkv * hd
        q = rms_norm(qkv[..., :nq].reshape(*lead, -1, hd), lp["q_norm"],
                     cfg.rms_norm_eps)
        k = rms_norm(qkv[..., nq:nq + nk].reshape(*lead, hkv, hd),
                     lp["k_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(mm(h_in, lp["wg"]).astype(jnp.float32))
        return q, k, qkv[..., nq + nk:].reshape(*lead, hkv, hd), gate


def _out(lp, o, gate):
    """From the heads' outputs ``o`` [..., H, D] to the sublayer's."""
    with jax.named_scope("attn_out"):
        o = o.reshape(*o.shape[:-2], -1)
        return mm((o.astype(jnp.float32) * gate).astype(o.dtype), lp["wo"])


# -- the selection ----------------------------------------------------------------


def pooled_keys(cfg, keys):
    """The pooled keys of ``keys`` [B, Tk, Hkv, D] (key ``i`` at position
    ``i``; Tk whole strides): [B, Tk / stride, Hkv, D] float32, entry j the
    mean of keys ``stride j .. stride j + kernel - 1`` (the last entry's
    second half is missing: no position sees it)."""
    stride, kernel, _block, _r = geometry(cfg)
    b, tk, hkv, d = keys.shape
    halves = jnp.sum(keys.astype(jnp.float32).reshape(
        b, tk // stride, stride, hkv, d), axis=2)
    after = jnp.concatenate([halves[:, 1:], jnp.zeros_like(halves[:, :1])], 1)
    return (halves + after) / kernel


def block_scores(cfg, q, pooled, n):
    """``b_m`` for queries ``q`` [B, T, H, D] that see ``n`` [B, T] keys
    each, against the pooled keys ``pooled`` [B, J, Hkv, D] float32 (J =
    blocks * pooled keys a block): [B, Hkv, T, blocks] float32, ``NEG``
    for a block none of whose pooled keys is complete."""
    stride, kernel, _block, r = geometry(cfg)
    b, t, h, d = q.shape
    j, hkv = pooled.shape[1:3]
    qg = q.astype(jnp.float32).reshape(b, t, hkv, h // hkv, d)
    logits = jnp.einsum("btgqd,bjgd->bgqtj", qg, pooled,
                        precision=_HI) * d ** -0.5
    ends = stride * jnp.arange(j, dtype=jnp.int32) + kernel
    seen = (ends[None, None, :] <= n[:, :, None])[:, None, None]  # [B,1,1,T,J]
    top = jnp.max(jnp.where(seen, logits, NEG), axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(logits - top), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    s = jnp.where(seen[:, :, 0], jnp.sum(p, axis=2), NEG)        # [B,g,T,J]
    s = s.reshape(b, hkv, t, j // r, r)
    # the pooled key that starts a stride before the block reaches into it
    before = jnp.concatenate(
        [jnp.full_like(s[..., :1, -1], NEG), s[..., :-1, -1]], axis=-1)
    return jnp.maximum(jnp.max(s, axis=-1), before)


def choose(cfg, scores, n):
    """The chosen blocks of queries that see ``n`` [B, T] keys, from
    ``block_scores``' [B, Hkv, T, M]: a mask [B, Hkv, T, M]."""
    _stride, _kernel, block, _r = geometry(cfg)
    m = scores.shape[-1]
    at = jnp.arange(m, dtype=jnp.int32)
    own = ((n - 1) // block)[:, None, :, None]                   # [B,1,T,1]
    near = at > own - cfg.sparse_window_size // block
    forced = (at < cfg.sparse_init_blocks) | near
    sc = jnp.where(forced, jnp.inf, scores)
    # a block's rank: the blocks that beat it, an equal one before it too
    beats = (sc[..., None, :] > sc[..., :, None]) | (
        (sc[..., None, :] == sc[..., :, None]) & (at[None, :] < at[:, None]))
    beats &= (at <= own)[..., None, :]
    rank = jnp.sum(beats.astype(jnp.int32), axis=-1)
    dense = (n <= cfg.sparse_dense_len)[:, None, :, None]
    return (at <= own) & ((rank < cfg.sparse_topk) | dense)


def chosen_blocks(cfg, q, pooled, n):
    """``choose(block_scores(...))``, a piece of the queries at a time."""
    b, t = n.shape
    piece = _CHOICE_QUERIES
    if t <= piece or t % piece:
        return choose(cfg, block_scores(cfg, q, pooled, n), n)

    def one(xs):
        qq, nn = xs                                   # [B, piece, ..]
        return choose(cfg, block_scores(cfg, qq, pooled, nn), nn)

    cut = lambda a: a.reshape(b, t // piece, piece, *a.shape[2:]).swapaxes(0, 1)
    got = jax.lax.map(one, (cut(q), cut(n)))          # [t/piece, B, g, piece, M]
    return got.transpose(1, 2, 0, 3, 4).reshape(b, got.shape[2], t, -1)


# -- whole (chunks of) sequences ---------------------------------------------------


def sequence(cfg, p, lp, h_in, ctx):
    """Over ``h_in`` [B, T, d] (T whole blocks, right padded, its first
    token at the same position ``ctx.positions[0, 0]`` in every row) and,
    with a prefix ((k, v) [B, Tp, Hkv, D] of the tokens before, key ``i``
    at position ``i``; how many are real [B]), over their keys too. Keeps
    the chunk's (k, v) and the pooled keys its tokens complete, [B, T /
    stride, Hkv, D] float32, entry ``i`` the pooled key ``start / stride -
    1 + i``, with which of them are whole [B, T / stride]."""
    stride, kernel, block, _r = geometry(cfg)
    b, t, _ = h_in.shape
    q, k, v, gate = _qkv(cfg, lp, h_in)
    with jax.named_scope("glue"):
        start = ctx.positions[0, 0]
        n_chunk = jnp.sum(ctx.valid.astype(jnp.int32), axis=1)
    keys, values = k, v
    if ctx.prefix is not None:
        with jax.named_scope("attn_core"):
            (pk, pv), _pre_len = ctx.prefix
            room = ((0, 0), (0, t), (0, 0), (0, 0))
            keys, values = (
                jax.lax.dynamic_update_slice_in_dim(
                    jnp.pad(old.astype(new.dtype), room), new, start, 1)
                for old, new in ((pk, k), (pv, v)))
    with jax.named_scope("sparse_select"):
        pooled = pooled_keys(cfg, keys)
        chosen = chosen_blocks(cfg, q, pooled, ctx.positions + 1)
        # what the chunk's tokens complete: pooled keys start / stride - 1 ..
        first = start // stride
        own = jax.lax.dynamic_slice_in_dim(
            jnp.pad(pooled, ((0, 0), (1, 0), (0, 0), (0, 0))), first,
            t // stride, 1)
        j = first - 1 + jnp.arange(t // stride, dtype=jnp.int32)
        whole = (j >= 0)[None] & (
            stride * j[None] + kernel <= (start + n_chunk)[:, None])
    with jax.named_scope("attn_core"):
        # key i stands at position i: the blocks' mask goes by place
        k_at = jnp.broadcast_to(
            jnp.arange(keys.shape[1], dtype=jnp.int32), keys.shape[:2])
        o = gqa_attention(cfg, q, keys, values, ctx.positions, k_at,
                          chosen=chosen, block=block)
    # the slot keeps a decode step's table alone: a chunk leaves it be
    return _out(lp, o, gate), Kept(pages=(k, v, own, whole), slot=ctx.state)


def scatter(cfg, pool, prefix_page_ids, page_ids, kept, start):
    """``pool`` (k, v, pooled) with a chunk's ``kept`` (``sequence``'s) in
    its pages ``page_ids`` [B, T / page]: the K/V pair by slabs, the pooled
    keys by rows of the store's ``[N * r * Hkv, D]`` view. The first of
    them (pooled key ``start / stride - 1``) lies in the LAST page of the
    prefix ``prefix_page_ids`` [B, n_pre], of which ``start / page`` are
    real."""
    from polyrl_tpu.models.blocks import _scatter_slabs

    _stride, _kernel, _block, r = geometry(cfg)
    k_pool, v_pool, c_pool = pool
    k, v, own, whole = kept
    b, n_own, hkv, d = own.shape
    k_pool, v_pool = (_scatter_slabs(a, page_ids, x)
                      for a, x in ((k_pool, k), (v_pool, v)))
    if prefix_page_ids.shape[1]:
        last = jnp.take_along_axis(
            prefix_page_ids,
            jnp.broadcast_to(jnp.maximum(start // k_pool.shape[2] - 1, 0),
                             (b, 1)), axis=1)
    else:
        last = jnp.zeros((b, 1), page_ids.dtype)
    pages = jnp.concatenate(
        [last, jnp.repeat(page_ids, r, axis=1)[:, :n_own - 1]], axis=1)
    slot = (jnp.arange(n_own, dtype=jnp.int32) - 1) % r
    rows = (jnp.where(whole, pages, 0)[:, :, None] * (hkv * r)
            + jnp.where(whole, slot[None], 0)[:, :, None] * hkv
            + jnp.arange(hkv, dtype=jnp.int32)[None, None, :])
    flat = c_pool.reshape(-1, d).at[rows.reshape(-1)].set(
        own.reshape(-1, d).astype(c_pool.dtype))
    return k_pool, v_pool, flat.reshape(c_pool.shape)


# -- one token a row ----------------------------------------------------------------


def _complete_pooled(cfg, k_pool, c_pool, ctx):
    """``c_pool`` with the pooled key that a row's token completes (the
    token at ``stride j + kernel - 1``: the mean of the row's last
    ``kernel`` keys, two slabs of ``stride`` rows of ``k_pool``, the
    token's own among them) at row ``(j % r) * Hkv + g`` of page ``j //
    r`` of the row's table; a row that completes none writes the null
    page. The scatter of ``S * Hkv`` rows is XLA's. (Beside the jnp form
    of ``selected_table`` the chip's compiler keeps the scatter's whole
    result, 94 MB at the cell's sizes, in VMEM for the gather that follows
    and copies it back, 0.41 ms a step for three layers: my chip runs, PR
    56. The kernel takes the store as an operand pinned to HBM
    (``_in_hbm``), so there is no gather to serve and no copy: PR 57.)"""
    stride, kernel, _block, r = geometry(cfg)
    hkv, n_pages, ps, d = k_pool.shape
    n = ctx.attn_lens                                           # [S]
    done = ctx.live & (n >= kernel) & (n % stride == 0)
    j = jnp.maximum(n // stride - 2, 0)
    # the slabs that hold tokens n - kernel .. n - 1
    at = jnp.maximum(n - kernel, 0)[:, None] + stride * jnp.arange(2)[None]
    page = jnp.take_along_axis(ctx.page_table, at // ps, axis=1)  # [S, 2]
    slab = page * (ps // stride) + (at % ps) // stride
    ids = (jnp.arange(hkv, dtype=jnp.int32)[None, :, None]
           * (n_pages * (ps // stride)) + slab[:, None, :])       # [S,Hkv,2]
    got = k_pool.reshape(-1, stride, d)[ids.reshape(-1)].reshape(
        *ids.shape, stride, d)
    mean = jnp.sum(got.astype(jnp.float32), axis=(2, 3)) / kernel  # [S,Hkv,D]
    home = jnp.take_along_axis(ctx.page_table, (j // r)[:, None], axis=1)[:, 0]
    rows = (jnp.where(done, home, 0)[:, None] * (hkv * r)
            + jnp.where(done, j % r, 0)[:, None] * hkv
            + jnp.arange(hkv, dtype=jnp.int32)[None, :])
    flat = c_pool.reshape(-1, d).at[rows.reshape(-1)].set(
        mean.reshape(-1, d).astype(c_pool.dtype))
    return flat.reshape(c_pool.shape)


def in_kernel(cfg, rows: int = 0) -> bool:
    """Whether a decode step chooses its blocks in the kernel
    (``ops/sparse_select.py``), from what its program is built on: the
    backend, the pooled store's shape and dtype (a page's pooled keys of
    every K/V head one float32 tile; the engine's page is the block, or
    ``step`` raises), heads of 128."""
    from polyrl_tpu.ops import sparse_select

    hkv = cfg.num_kv_heads
    return sparse_select.in_kernel(
        (0, geometry(cfg)[3] * hkv, cfg.head_dim_), cache_spec.POOLED_DTYPE,
        cfg.head_dim_, cfg.num_heads // hkv)


def selected_table(cfg, q, c_pool, ctx, n_pages: int):
    """The pages each (row, K/V head) attends, as ``paged_attention`` takes
    them from the pools seen as one head of ``Hkv * n_pages`` pages:
    (table [S * Hkv, W] of page numbers, head g's offset by ``g *
    n_pages``, the chosen blocks in rising order and so the row's own,
    part-filled, last; the keys they hold [S * Hkv]; the chosen blocks a
    (row, head) [S, Hkv]). W: the most a row takes (``table_width``) or
    the row's own table's width. Built by the kernel that walks each row's
    own pooled pages where ``in_kernel`` says so, else by the jnp form
    below, at the table's full width: the oracle, and the path off a TPU."""
    from polyrl_tpu.ops import sparse_select

    stride, kernel, block, r = geometry(cfg)
    s, width = ctx.page_table.shape
    hkv, d = cfg.num_kv_heads, cfg.head_dim_
    w = min(width, table_width(cfg))
    if in_kernel(cfg):
        _scores, table, count = sparse_select.sparse_select_pallas(
            q, c_pool, ctx.page_table, jnp.where(ctx.live, ctx.attn_lens, 0),
            stride=stride, kernel=kernel, block=block, topk=cfg.sparse_topk,
            init_blocks=cfg.sparse_init_blocks,
            near_blocks=cfg.sparse_window_size // block,
            dense_len=cfg.sparse_dense_len, width=w, n_pages=n_pages)
    else:
        # a page's rows are its pooled keys in order, a K/V head after the
        # other within each: the gathered pages ARE the row's pooled keys
        pooled = c_pool[ctx.page_table].reshape(s, width * r, hkv, d)
        n = jnp.maximum(ctx.attn_lens, 1)[:, None]                # [S, 1]
        chosen = choose(cfg, block_scores(cfg, q[:, None], pooled, n),
                        n)[:, :, 0]
        chosen &= ctx.live[:, None, None]                         # [S,Hkv,M]
        count = jnp.sum(chosen.astype(jnp.int32), axis=-1)
        place = jnp.cumsum(chosen.astype(jnp.int32), axis=-1) - 1
        hit = chosen[..., None] & (
            place[..., None] == jnp.arange(w, dtype=jnp.int32))   # [S,Hkv,M,W]
        pages = (ctx.page_table[:, None, :]
                 + jnp.arange(hkv, dtype=jnp.int32)[None, :, None] * n_pages)
        table = jnp.sum(jnp.where(hit, pages[..., None], 0), axis=2)
    own = (ctx.attn_lens - 1) // block
    lens = jnp.where(count > 0, (count - 1) * block
                     + (ctx.attn_lens - own * block)[:, None], 0)
    return table.reshape(s * hkv, w), lens.reshape(-1), count


def step(cfg, p, lp, h_in, ctx):
    """Writes the token's key and value to its page, completes a pooled
    key where the token ends one, chooses the row's blocks a K/V head and
    attends over them."""
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    stride, kernel, block, _r = geometry(cfg)
    if ctx.page_size != block:
        raise ValueError(f"pages of {ctx.page_size} tokens under blocks of "
                         f"{block}: a sparse layer's page is its block")
    s = h_in.shape[0]
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q, k, v, gate = _qkv(cfg, lp, h_in)
    k_pool, v_pool, c_pool = ctx.pages
    n_pages = k_pool.shape[1]
    with jax.named_scope("attn_core"):
        k_pool, v_pool = paged_kv_write(k_pool, v_pool, ctx.write_page,
                                        ctx.write_off, k, v)
    with jax.named_scope("sparse_select"):
        c_pool = _complete_pooled(cfg, k_pool, c_pool, ctx)
        table, lens, count = selected_table(cfg, q, c_pool, ctx, n_pages)
        over = ctx.live & (ctx.attn_lens > cfg.sparse_dense_len)
        scored = jnp.sum(jnp.where(
            over, jnp.maximum((ctx.attn_lens - kernel) // stride + 1, 0), 0))
        pages_read = jnp.sum(count)
        dense_rows = jnp.sum((ctx.live & ~over).astype(jnp.int32))
        (picked,) = ctx.slot
        spare = picked.shape[2] - 1 - table.shape[1]
        mine = jnp.concatenate(
            [jnp.pad(table.reshape(s, hkv, -1), ((0, 0), (0, 0), (0, spare))),
             lens.reshape(s, hkv, 1)], axis=2)
        picked = set_rows(picked, jnp.where(ctx.live[:, None, None], mine,
                                            picked[:s]))
    with jax.named_scope("attn_core"):
        o = paged_attention(
            q.reshape(s * hkv, h // hkv, d),
            k_pool.reshape(1, hkv * n_pages, block, d),
            v_pool.reshape(1, hkv * n_pages, block, d), table,
            lens).reshape(s, h, d)
    ctx.load.add("sparse_pages_read", pages_read)
    ctx.load.add("sparse_pooled_scored", scored)
    ctx.load.add("sparse_dense_rows", dense_rows)
    return _out(lp, o, gate), Kept(pages=(k_pool, v_pool, c_pool),
                                   slot=(picked,))


def _no_rows(cfg, arrays, at):
    return ()


def _as_they_are(cfg, arrays, at, new, was):
    return arrays


def held(cfg, arrays, slot: int) -> np.ndarray:
    """What the slot's last decode step attended, [Hkv, W + 1] int32: a
    K/V head's table of pages (``selected_table``'s row: head g's numbers
    offset by ``g * N``) and, last, the keys they hold."""
    return np.asarray(arrays[0][slot])


SPARSE = Mixer(
    "sparse", cache, stack="sparse", init=init, sequence=sequence, step=step,
    row_parallel=("wo",), pages_scope="attn_core", pages_by_slabs=True,
    scatter=scatter, slot_scope="sparse_select", read_slot=_no_rows,
    write_slot=_as_they_are, held=held,
    counts=("sparse_pages_read", "sparse_pooled_scored", "sparse_dense_rows"),
    kernel=("sparse_kernel_steps", in_kernel))
