"""The choice of a block-sparse layer's decode step (``mixers/sparse.py``:
``block_scores`` and ``choose``, their equations unchanged), a row a
program, over THAT ROW'S pages of the pooled store.

``ops/paged_attention.py``'s neighbour, and its kernel's shape: the page
table and the lengths ride as scalar prefetch, the pooled store ``[N, r *
Hkv, D]`` float32 stays in HBM (``_in_hbm``) and a row's ``ceil(n /
block)`` pages of it (a page's entry is one ``(8, 128)`` tile, 4 KB, every
K/V head's pooled keys in it: one descriptor a page serves all heads) are
brought in by hand-written DMAs, the NEXT row's while this one is worked.
What XLA's form of the stage gathers at the table's full width
(``c_pool[page_table]``: 176 MB a layer written and read again at the
cell's sizes), and the compiler's copy of the whole store through VMEM
around that gather, are not made.

For a row of ``n`` keys, a K/V head ``g`` at a time, with the pooled key
``j`` of the head at row ``(j % r) * Hkv + g`` of the row's page ``j //
r``:

1. ``logits = q_g c^T / sqrt(D)`` ``[G, J]`` in float32. The queries of a
   bf16 model ARE bf16 numbers and a pooled key is split into three bf16
   pieces that sum to it exactly, so three MXU passes with float32
   accumulation multiply float32 by float32 (what ``Precision.HIGHEST``
   does in six, three of them by the query's zero pieces); float32 queries
   are split alike and take the six. The keys come out along the lanes, a
   page a lane and a sublane of the page's tile an array: ``r * Hkv``
   arrays ``[G, pages]``, so everything after the product is dense in the
   blocks.
2. the softmax over the SEEN pooled keys (``stride j + kernel <= n``),
   two passes over logits that stand whole in VMEM, the sum over the
   group's heads, the max over the pooled keys that overlap a block (the
   one that starts a stride before it too): ``b_m``.
3. the choice among the row's own blocks: the forced ones, then by rank (a
   block's rank is the count of blocks that beat it, ties to the lower
   block), ``topk``; every block for a row at or under ``dense_len``;
   nothing for a row of length 0 (a row without a request). The chosen
   pages in rising order are the row's table for ``ops.paged_attention``,
   head ``g``'s page numbers offset by ``g * n_pages``.

Both the products and the comparisons run over the lane tiles (128 pages)
that the row's pages reach, not over the table's width.

``mixers.sparse.selected_table`` is the dispatcher: where ``in_kernel``
says so (a TPU, a float32 store of one tile a page, heads of 128) the
kernel; elsewhere its jnp form (the oracle, as ``paged_attention`` keeps
``paged_attention_ref``). What says that the kernel ran is the engine's
``sparse_kernel_steps`` and the kernel's own
event, ``sparse_select``, in a device trace."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from polyrl_tpu.ops.paged_attention import _in_hbm

NEG = -1e30
_LANES, _SUBLANES = 128, 8
# pages a group of descriptors: a group is issued whole (its last pages
# may lie past the row's end: the table's own entries there, never scored)
_GROUP = 16


def accepts(store_shape, dtype, head_dim: int, group: int) -> bool:
    """Whether the kernel takes a pooled store of this shape: float32, a
    page's pooled keys of every K/V head one ``(8, 128)`` tile, a group of
    whole sublane tiles of query heads."""
    return (jnp.dtype(dtype) == jnp.float32 and len(store_shape) == 3
            and store_shape[1] == _SUBLANES and store_shape[2] == _LANES
            and head_dim == _LANES and group % _SUBLANES == 0)


def in_kernel(store_shape, dtype, head_dim: int, group: int) -> bool:
    """Whether ``select`` runs the kernel for this store here."""
    return (jax.default_backend() == "tpu"
            and accepts(store_shape, dtype, head_dim, group))


def _pieces(x):
    """``x`` as bf16 arrays that sum to it: itself if it is bf16, else the
    three pieces of a float32."""
    if x.dtype == jnp.bfloat16:
        return [x]
    out = []
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        out.append(piece)
        x = x - piece.astype(jnp.float32)
    return out


def _rows(rows):
    """Up to eight arrays ``[1, n]`` as the sublanes of one ``[8, n]``."""
    at = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, rows[0].shape[1]), 0)
    out = jnp.zeros(at.shape, rows[0].dtype)
    for i, x in enumerate(rows):
        out = jnp.where(at == i, x, out)
    return out


def _kernel(lens_ref, table_ref,   # scalar prefetch: [S], [S * P] first rows
            q_ref,       # [1, Hkv, G, D]
            pages_ref,   # [1, 1, Pp] float32: the row's page numbers
            c_hbm,       # [N * 8, D] float32, left in HBM
            scores_ref,  # [1, Hkv, Pp] float32
            picked_ref,  # [1, Hkv, W] int32
            count_ref,   # [1, Hkv, 128] int32
            cbuf,        # VMEM [2 * Pp * 8, D]: this row's pages, the next's
            lbuf,        # VMEM [8, G, Pp]: the row's logits, then their exp
            sbuf,        # VMEM [8, Pp]: row g, what ranks head g's blocks
            rbuf,        # VMEM [8, Pp]: row g, their ranks (a tile the row
                         # does not reach keeps what it held: masked)
            colbuf,      # VMEM [Pp, 8]: ``sbuf``, then places and pages,
                         # a block a sublane
            wide,        # VMEM [Hkv, Pp, 128]: ``colbuf``'s column g over
                         # the lanes
            sems,        # DMA [2]
            *, hkv: int, stride: int, kernel: int, block: int,
            topk: int, init_blocks: int, near_blocks: int, dense_len: int,
            n_pages: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = _SUBLANES // hkv
    pp = lbuf.shape[2]
    g_heads = lbuf.shape[1]
    w = picked_ref.shape[2]
    n_rows = pl.num_programs(0)
    row = pl.program_id(0)
    n = lens_ref[row]
    scale = q_ref.shape[3] ** -0.5

    def groups(length):
        return (length + block * _GROUP - 1) // (block * _GROUP)

    def group_dma(rw, t, slot, start: bool):
        """Start, or wait for, the ``_GROUP`` pages ``t * _GROUP ..`` of
        row ``rw`` into buffer ``slot``. (A descriptor is what paces the
        kernel: the group's two bases are worked out once and a page's
        part is a static offset, the table holds a page's first ROW of the
        store seen as rows, and so a descriptor is one scalar load, two
        address sums and the copy: 4.3 bundles.)"""
        if not start:
            whole = cbuf.at[pl.ds(0, _GROUP * _SUBLANES)]
            pltpu.make_async_copy(whole, whole, sems.at[slot]).wait()
            return
        entry = rw * pp + t * _GROUP
        to = pl.multiple_of((slot * pp + t * _GROUP) * _SUBLANES,
                            _GROUP * _SUBLANES)
        for u in range(_GROUP):
            at = pl.multiple_of(table_ref[entry + u], _SUBLANES)
            pltpu.make_async_copy(
                c_hbm.at[pl.ds(at, _SUBLANES)],
                cbuf.at[pl.ds(to + u * _SUBLANES, _SUBLANES)],
                sems.at[slot]).start()

    def row_dma(rw, slot, start: bool):
        jax.lax.fori_loop(
            0, groups(lens_ref[rw]),
            lambda t, _: group_dma(rw, t, slot, start), None)

    slot = jax.lax.rem(row, 2)

    @pl.when(row == 0)
    def _first_program():
        # what a group brings past a row's end, and what no group brought,
        # is never scored: but it is multiplied, so it is finite
        cbuf[...] = jnp.zeros_like(cbuf)
        row_dma(row, slot, start=True)

    # (in a loop of their own: laid between the products, in one buffer or
    # in two, the scheduler keeps descriptors and products apart, 934
    # bundles a tile for 535 and 469: the LLO dumps, PR 57)
    @pl.when(row + 1 < n_rows)
    def _next_row():
        row_dma(row + 1, 1 - slot, start=True)

    row_dma(row, slot, start=False)

    # -- 1. the logits, a lane tile of pages at a time ---------------------
    n_tiles = (n + block * _LANES - 1) // (block * _LANES)
    q_pieces = [_pieces(q_ref[0, g]) for g in range(hkv)]
    # (a query's piece, a key's piece) of each product kept, the smallest
    # first: float32 sums
    products = sorted(((a, b) for a in range(len(q_pieces[0]))
                       for b in range(3) if a + b < 3),
                      key=lambda ab: -(ab[0] + ab[1]))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def seen(c, jj):
        """Which pooled keys ``jj`` of the tile's pages the row sees."""
        return stride * ((c * _LANES + lane) * r + jj) + kernel <= n

    def tile_logits(c, tops):
        at = pl.multiple_of(c * _LANES, _LANES)
        tops = list(tops)
        for k in range(_SUBLANES):
            # sublane k of the tile's 128 pages: a strided load
            keys = _pieces(cbuf[pl.ds((slot * pp + at) * _SUBLANES + k,
                                      _LANES, stride=_SUBLANES), :])
            acc = None
            for a, b in products:
                # bf16 pieces: one pass multiplies them exactly, whatever
                # the process-wide default says
                part = jax.lax.dot_general(
                    q_pieces[k % hkv][a], keys[b], (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                acc = part if acc is None else acc + part
            masked = jnp.where(seen(c, k // hkv), acc * scale, NEG)
            lbuf[k, :, pl.ds(at, _LANES)] = masked
            tops[k % hkv] = jnp.maximum(
                tops[k % hkv], jnp.max(masked, axis=1, keepdims=True))
        return tuple(tops)

    tops = jax.lax.fori_loop(
        0, n_tiles, tile_logits,
        tuple(jnp.full((g_heads, 1), NEG, jnp.float32) for _ in range(hkv)))

    # -- 2. the blocks' scores ----------------------------------------------
    def tile_exp(c, sums):
        at = pl.multiple_of(c * _LANES, _LANES)
        sums = list(sums)
        for k in range(_SUBLANES):
            e = jnp.where(seen(c, k // hkv),
                          jnp.exp(lbuf[k, :, pl.ds(at, _LANES)]
                                  - tops[k % hkv]), 0.0)
            lbuf[k, :, pl.ds(at, _LANES)] = e
            sums[k % hkv] = sums[k % hkv] + jnp.sum(e, axis=1, keepdims=True)
        return tuple(sums)

    sums = jax.lax.fori_loop(
        0, n_tiles, tile_exp,
        tuple(jnp.zeros((g_heads, 1), jnp.float32) for _ in range(hkv)))
    sums = [jnp.maximum(z, 1e-30) for z in sums]
    own = (n - 1) // block
    scores_ref[0] = jnp.full(scores_ref.shape[1:], NEG, jnp.float32)
    sbuf[...] = jnp.full(sbuf.shape, -jnp.inf, jnp.float32)

    def tile_scores(c, last):
        """``b_m`` of the tile's blocks, and what ranks them: a forced
        block as +inf; ``last``: the row's last pooled key's share in the
        tile before, which reaches into this one's first block."""
        at = pl.multiple_of(c * _LANES, _LANES)
        block_at = c * _LANES + lane
        forced = (block_at < init_blocks) | (block_at > own - near_blocks)
        last = list(last)
        for g in range(hkv):
            s = [jnp.where(seen(c, jj), jnp.sum(
                lbuf[jj * hkv + g, :, pl.ds(at, _LANES)] / sums[g], axis=0,
                keepdims=True), NEG) for jj in range(r)]          # [1, 128]
            before = jnp.where(lane == 0, last[g], pltpu.roll(s[-1], 1, 1))
            b = functools.reduce(jnp.maximum, s + [before])
            scores_ref[0, g:g + 1, pl.ds(at, _LANES)] = b
            sbuf[g:g + 1, pl.ds(at, _LANES)] = jnp.where(
                block_at <= own, jnp.where(forced, jnp.inf, b), -jnp.inf)
            last[g] = s[-1][:, _LANES - 1:]
        return tuple(last)

    jax.lax.fori_loop(0, n_tiles, tile_scores, tuple(
        jnp.full((1, 1), NEG, jnp.float32) for _ in range(hkv)))

    # -- 3. the choice ------------------------------------------------------
    # a block's rank: the blocks that beat it, an equal one before it too
    colbuf[...] = sbuf[...].T                                     # [Pp, 8]
    below = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))

    def count(beats):
        return jnp.sum(beats.astype(jnp.float32), axis=0, keepdims=True)

    def tile_spread(c, _):
        """A tile's blocks a sublane, spread over the lanes once: every
        tile of the rank compares against them."""
        rows = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
        for g in range(hkv):
            wide[g, rows, :] = jnp.broadcast_to(colbuf[rows, g:g + 1],
                                                (_LANES, _LANES))
        return _

    jax.lax.fori_loop(0, n_tiles, tile_spread, None)

    def tile_rank(c, _):
        at = pl.multiple_of(c * _LANES, _LANES)
        for g in range(hkv):
            mine = sbuf[g:g + 1, pl.ds(at, _LANES)]               # [1, 128]

            def theirs(c2):
                return wide[g, pl.ds(pl.multiple_of(c2 * _LANES, _LANES),
                                     _LANES), :]                  # [128, 128]

            diag = theirs(c)
            rank = count((diag > mine) | ((diag == mine) & below))
            rank = jax.lax.fori_loop(
                0, c, lambda c2, acc: acc + count(theirs(c2) >= mine), rank)
            rank = jax.lax.fori_loop(
                c + 1, n_tiles, lambda c2, acc: acc + count(theirs(c2) > mine),
                rank)
            rbuf[g:g + 1, pl.ds(at, _LANES)] = rank
        return _

    jax.lax.fori_loop(0, n_tiles, tile_rank, None)
    at = jax.lax.broadcasted_iota(jnp.int32, (1, pp), 1)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
             ).astype(jnp.float32)
    dense = n <= dense_len
    places = []
    for g in range(hkv):
        chosen = (at <= own) & ((rbuf[g:g + 1, :] < topk) | dense)
        # a chosen block's place in the table: the chosen ones before it
        took = _rows([chosen.astype(jnp.float32)])
        place, before = [], jnp.zeros((1, 1), jnp.float32)
        for c in range(pp // _LANES):
            # 0 and 1 in float32 sums: one pass is exact
            within = jax.lax.dot_general(
                took[:, c * _LANES:(c + 1) * _LANES], upper,
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)[:1]           # [1, 128]
            place.append(within + before)
            before = before + within[:, _LANES - 1:]
        count_ref[0, g:g + 1, :] = jnp.broadcast_to(
            before.astype(jnp.int32), (1, _LANES))
        places.append(jnp.where(chosen, jnp.concatenate(place, axis=1) - 1.0,
                                -1.0))
    colbuf[...] = _rows(places + [pages_ref[0]]).T                # [Pp, 8]
    slot_at = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1).astype(
        jnp.float32)

    def tile_pick(c, picked):
        """The tile's chosen pages, each at its place of the table."""
        rows = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
        page = colbuf[rows, hkv:hkv + 1]                          # [128, 1]
        return tuple(
            picked[g] + jnp.sum(jnp.where(
                colbuf[rows, g:g + 1] == slot_at, page + float(g * n_pages),
                0.0), axis=0, keepdims=True) for g in range(hkv))

    picked = jax.lax.fori_loop(0, n_tiles, tile_pick, tuple(
        jnp.zeros((1, w), jnp.float32) for _ in range(hkv)))
    for g in range(hkv):
        picked_ref[0, g:g + 1, :] = picked[g].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "stride", "kernel", "block", "topk", "init_blocks", "near_blocks",
    "dense_len", "width", "n_pages", "interpret"))
def sparse_select_pallas(q, c_pool, page_table, lens, *, stride: int,
                         kernel: int, block: int, topk: int, init_blocks: int,
                         near_blocks: int, dense_len: int, width: int,
                         n_pages: int, interpret: bool = False):
    """The kernel: for queries ``q`` [S, H, D], the pooled store ``c_pool``
    [N, r * Hkv, D] float32, the rows' page table [S, P] and keys ``lens``
    [S] (0: a row without a request, which chooses nothing): (the blocks'
    scores [S, Hkv, P] float32, ``NEG`` for a block none of whose pooled
    keys is complete; the chosen pages in rising order [S, Hkv, ``width``]
    int32, head g's offset by ``g * n_pages``, 0 past the row's count; the
    count [S, Hkv] int32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h, d = q.shape
    n_pool, rows, _ = c_pool.shape
    p = page_table.shape[1]
    hkv = rows // (block // stride)
    g_heads = h // hkv
    pp = -(-p // _LANES) * _LANES
    lens = jnp.clip(lens.astype(jnp.int32), 0, p * block)
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pool - 1)
    table = jnp.pad(table, ((0, 0), (0, pp - p)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, hkv, g_heads, d), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, pp), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, pp), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, hkv, width), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, hkv, _LANES), lambda i, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2 * pp * _SUBLANES, d), jnp.float32),
            pltpu.VMEM((_SUBLANES, g_heads, pp), jnp.float32),
            pltpu.VMEM((_SUBLANES, pp), jnp.float32),
            pltpu.VMEM((_SUBLANES, pp), jnp.float32),
            pltpu.VMEM((pp, _SUBLANES), jnp.float32),
            pltpu.VMEM((hkv, pp, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    scores, picked, count = pl.pallas_call(
        functools.partial(
            _kernel, hkv=hkv, stride=stride, kernel=kernel, block=block,
            topk=topk, init_blocks=init_blocks, near_blocks=near_blocks,
            dense_len=dense_len, n_pages=n_pages),
        out_shape=[jax.ShapeDtypeStruct((s, hkv, pp), jnp.float32),
                   jax.ShapeDtypeStruct((s, hkv, width), jnp.int32),
                   jax.ShapeDtypeStruct((s, hkv, _LANES), jnp.int32)],
        grid_spec=grid_spec,
        interpret=interpret,
        name="sparse_select",
        # rows run in order: each starts the next row's pages
        # (no bounds checks: a descriptor a page is what paces the kernel, and
        # two checks are 15 of its 21 bundles; the table is clamped to the
        # store above and a group never passes the buffer's ``pp`` pages)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
    )(lens, (table * rows).reshape(-1), q.reshape(s, hkv, g_heads, d),
      table.astype(jnp.float32).reshape(s, 1, pp),
      _in_hbm(c_pool.reshape(n_pool * rows, d), interpret))
    return scores[:, :, :p], picked, count[:, :, 0]
