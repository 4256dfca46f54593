"""Decode attention of a multi-head latent attention layer over a paged
latent pool, in the absorbed form: every head's query already lies in the
latent's space (``hybrid.mla_absorb``), so one row of ``rank + rope``
values a token is key for all heads, and its first ``rank`` columns are
the value too.

``latent_paged_attention`` is the dispatcher: on a TPU the kernel below,
elsewhere a gather-based oracle. The kernel: one program an attention
row, a loop over the row's own blocks of pages with the blocks to come
(or the next live row's first) in flight while one is computed, the heads
as the rows of the MXU products, online softmax in float32 (running
maximum, sum and accumulator; ``exp`` in float32; probabilities cast to
the pool's dtype for the value product). The scores run over the whole
row, [H, w] x [keys, w]; the value product and the accumulator over the
``rank`` columns only (whole lane tiles: 512 of 640, a tenth of the
kernel's FLOPs and a fifth of the accumulator's traffic); the output is
[S, H, rank] in the pool's dtype, divided in float32 and then cast, which
is what ``hybrid.mla_unabsorb`` reads. The accumulator lives in a VMEM
scratch of its own and the running maximum and sum are the loops' carry.
Blocks whose every key is live take no mask and are one basic block, the
next block's descriptors, the wait and the products; the row's
part-filled last block runs in PIECES: all but the last wholly live, in
the whole block's form in small, a loop iteration each; the last one,
whole or not, under the mask, in the basic block that divides and stores.

Block size, sub-blocks, buffers and the piece are ``_block_plan``'s, a
function of the static shapes: the kernel's FLOPs a byte against the
chip's ridge (240 on v5e). Two regimes, kernel alone at the two cells'
shapes on one v5e (my chip runs, PR 36; the parent: 384 keys a block in
one piece, two buffers, value product over all 640 lanes, float32 out):

- From HALF the ridge up (128 heads: 230 FLOPs a byte; 65 rows over
  505.9k latent rows) the MXU with ``H`` rows a weight tile and the DMAs
  bound it about alike. Parent 1.583 ms (45% of the FLOP roof). Value
  columns, no mask, bf16 out at 384 keys: 1.494. Larger blocks amortise
  the accumulator's rescale and the pipeline's fill and drain, and cut
  in sub-blocks one sub-block's scores run on the MXU beside the softmax
  of the one before: 512 keys 1.303 in one piece and 1.243 in two; 1,024
  keys 1.147, 1.106 in two, 1.126 in four; 2,048 keys 1.092 in two, 1.098
  in four, 1.146 in eight. Kept: 2,048 in two, two buffers. The time
  follows the compiler's bundle count here (2,601 bundles a 1,024 keys
  1.106 ms, 2,704 read 1.155), so a third buffer (1% off the DMAs' side,
  3.5% more bundles for its bookkeeping) does not pay.
- Below half the ridge (32 heads: 58 FLOPs a byte; 129 rows over 542.1k
  latent rows) the DMAs bound it, and what pays is their queue: parent
  1.246 ms (61% of the bytes' roof); value columns and bf16 out alone
  1.238; a third buffer, two blocks in flight: 1.061 at 384 keys, 0.942
  at 512, 0.932 at 768 (82%), 0.931 at 1,024; two buffers at 1,024 keys
  0.996. Kept: a MiB of pages (768 keys of 640 bf16 lanes) in one piece,
  three buffers. Sub-blocks buy nothing here (0.938 against 0.939).

In both, a block's DMAs are started BEFORE the wait for the block at
hand: started after it, the scheduler sinks the descriptors into the
products (156 bundles fewer) and the DMAs start late: 1.169 against 1.106
at 128 heads, 1.565 against 1.238 at 32. A page's descriptor is unrolled
code in a whole block's iteration and a loop everywhere else
(``block_dma``): the loop in the iteration costs 11.6%, the unrolled form
everywhere made every program that holds the kernel lower 0.4-0.8 s
later at set-up.

What a row pays once, at 128 heads (my chip runs, PR 48; the kernel alone
at three offsets into the answer, 564, 1,247 and 1,930 tokens: 505.9k,
549.7k and 593.4k latent rows; the parent of PR 48 reads 1.092, 1.174
and 1.252 ms). Without the DMAs that kernel takes 0.894 ms, without the
products 0.873, with both 1.071; rows cut down to their whole blocks take
0.714, 0.754 and 0.805: a row's last block costs 4.1 us where its
products are 2.8 and its DMAs 1.9, because its iteration also waits for
the next row's first block (3.5 us of DMA, started from a loop of 33
cycles a page beside an idle MXU). So what the last block MULTIPLIES is
the smaller half of what it costs:

- The last block in pieces (the parent masked a 1,024-key sub-block under
  a ``cond`` each): pieces of 512 keys 1.079, 1.162, 1.244 (1.2%, 1.0%,
  0.6% off); of 256 keys 1.098, 1.183, 1.262 (a piece's loop iteration
  carries the state through VMEM: 754 bundles a 256 keys against 2,113 a
  1,024 in a whole block); of 1,024 keys 1.083, 1.164, 1.245; the whole
  block under its mask 1.097, 1.175, 1.262. Kept: 512.
- A whole block's iteration as ONE basic block (the predicate "is there
  a block to start" folded into each page's own, where a branch around
  the 32 descriptors cut the iteration in three): 4,934 -> 4,844 bundles,
  1.070, 1.153, 1.236.
- The accumulator in a VMEM scratch (as a loop's carry its 64 vregs, the
  whole register file, went through the compiler's spill slots at every
  region's edge): a whole block 4,740 bundles (2,067 spills for 2,600), a
  piece 1,221 for 1,327, the divide and store 98 for 175: **1.048, 1.128,
  1.213** (68.3, 68.9, 69.2% of the FLOP roof; 4.1%, 3.9%, 3.1% under the
  parent); without DMAs 0.865. Maximum and sum in scratches too: 1.085.
- Tried on top and dropped: a third buffer 1.061 (again), four sub-blocks
  1.058, 3,072 keys in three 1.108; the products without the look-ahead
  of one sub-block's scores 1.120; a whole block's descriptors split
  around the wait, E pages before it and the rest between the products
  (they keep the program's order against the buffer's loads, so they land
  where they are written): 1.163 at E = 4, 1.101 at 8, 1.074 at 12, 1.070
  at 16 or 20, the DMA queue running dry wherever fewer than a block's
  first half are early; four pages a loop iteration for the next row's
  first block: 27 bundles a page for 28 (the bounds checks serialize);
  one loop over all of a row's blocks with the last under a ``cond``: 283
  bundles more a whole block.

At 32 heads all of these read what the parent reads (0.932, 1.079,
1.227 ms at the three offsets: the DMAs bound it), so Ling's shape keeps
one piece (the block) and gets the same code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from polyrl_tpu.ops import dispatch
from polyrl_tpu.ops.paged_attention import NEG_INF, _sublane_tile


def latent_paged_attention_ref(q, pool, page_table, seq_lens, rank: int,
                               scale: float):
    """Gather-based oracle: ``q`` [S, H, w], ``pool`` [1, N, page, w] ->
    [S, H, rank] float32."""
    s, h, w = q.shape
    ps = pool.shape[2]
    p = page_table.shape[1]
    rows = pool[0][page_table].reshape(s, p * ps, w).astype(jnp.float32)
    logits = jnp.einsum("shw,stw->sht", q.astype(jnp.float32), rows,
                        precision=jax.lax.Precision.HIGHEST) * scale
    ok = jnp.arange(p * ps)[None, :] < jnp.maximum(seq_lens, 1)[:, None]
    probs = jax.nn.softmax(jnp.where(ok[:, None], logits, NEG_INF), axis=-1)
    out = jnp.einsum("sht,str->shr", probs, rows[..., :rank],
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where((seq_lens > 0)[:, None, None], out, 0.0)


# one TPU v5e chip (Google Cloud, "TPU v5e"): bf16 FLOP/s over HBM bytes/s
_RIDGE = 197e12 / 819e9
# below half the ridge: bytes of one block of pages, three of them in VMEM
_DMA_BLOCK_BYTES = 1 << 20
# from half the ridge up: keys a block, the sub-blocks it is cut into, and
# the most keys of a piece of a row's part-filled last block
_MXU_BLOCK_KEYS, _MXU_SUBS, _MXU_PIECE_KEYS = 2048, 2, 512


def _block_plan(h: int, w: int, rank: int, page_size: int, itemsize: int,
                p: int) -> tuple[int, int, int, int]:
    """(pages a block, sub-blocks a block, buffers, keys a piece of a row's
    last block) from the static shapes alone: the kernel's arithmetic
    intensity against the chip's ridge. A key costs ``w * itemsize`` bytes
    of DMA and ``2 * h * (w + rank)`` FLOPs (scores over the whole row,
    values over the rank columns); the module's docstring has the readings
    behind the two regimes. A piece is whole pages and divides the
    sub-block; below half the ridge it is the block."""
    if 2 * h * (w + rank) / (w * itemsize) < _RIDGE / 2:
        b = max(1, min(p, _DMA_BLOCK_BYTES // (page_size * w * itemsize)))
        return b, 1, 3, b * page_size
    b = max(1, min(p, _MXU_BLOCK_KEYS // page_size))
    subs = _MXU_SUBS if b % _MXU_SUBS == 0 else 1
    pages = max(d for d in range(1, b // subs + 1) if (b // subs) % d == 0
                and (d == 1 or d * page_size <= _MXU_PIECE_KEYS))
    return b, subs, 2, pages * page_size


def keys_multiplied(lengths, plan: tuple[int, int, int, int],
                    page_size: int) -> int:
    """Keys the kernel multiplies for rows of these lengths under ``plan``:
    the whole blocks, the last block's wholly live pieces and, where keys
    are left over, one more piece under its mask."""
    b, _subs, _nbuf, piece = plan
    bt = b * page_size
    return sum(t - t % bt + -(-(t % bt) // piece) * piece for t in lengths)


def _kernel(lens_ref, live_from_ref, table_ref,   # scalar prefetch
            q_ref,      # [1, R, w] pre-scaled, pool dtype
            pool_hbm,   # [1, N, page_size, w], left in HBM
            out_ref,    # [1, R, vw] pool dtype
            buf,        # VMEM [nbuf, b * page_size, w]
            sems,       # DMA [nbuf buffers]
            buf_ref,    # SMEM [1]: buffer of the row's first block
            acc_ref,    # VMEM [R, vw] float32: the row's accumulator
            *, pages_per_block: int, subs: int, nbuf: int, piece: int,
            page_size: int, vw: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pages_per_block
    bt = b * page_size
    n_rows = pl.num_programs(0)
    p = table_ref.shape[0] // n_rows
    row = pl.program_id(0)
    length = lens_ref[row]
    n_full = length // bt
    n_blk = (length + bt - 1) // bt

    def page_dma(r, col, j, which, start: bool):
        cp = pltpu.make_async_copy(
            pool_hbm.at[0, table_ref[r * p + col]],
            buf.at[which, pl.ds(pl.multiple_of(j * page_size, page_size),
                                page_size)],
            sems.at[which])
        if start:
            cp.start()
        else:
            cp.wait()

    def block_dma(r, blk, which, start: bool, hot: bool = False,
                  whole: bool = False):
        """Start, or wait for, the copies of block ``blk`` of row ``r``
        into buffer ``which``, one a live page. On the ``hot`` path (a
        whole block's iteration) the descriptors are unrolled, and need no
        predicate where the block is known ``whole``: in a loop over the
        live pages there the kernel reads 1.216 ms against 1.090 at 128
        heads (the descriptors' scalar work then runs beside nothing).
        Off it (a row's first blocks and its last) they are a loop:
        unrolled everywhere, 32 pages on five paths made the kernel four
        times as slow to lower, which every program that holds it pays
        at set-up."""
        keys = lens_ref[jnp.minimum(r, n_rows - 1)]
        n_pg = (keys + page_size - 1) // page_size
        # past the batch's last row there is no block: no branch around
        # the starts, which would cut a whole block's iteration in two
        n_pg = jnp.where(r < n_rows, n_pg - blk * b, 0)
        if not hot:
            jax.lax.fori_loop(
                0, jnp.clip(n_pg, 0, b),
                lambda j, _: page_dma(r, blk * b + j, j, which, start), None)
            return
        for j in range(b):
            if whole:
                page_dma(r, blk * b + j, j, which, start)
            else:
                pl.when(j < n_pg)(functools.partial(
                    page_dma, r, blk * b + j, j, which, start))

    @pl.when(row == 0)
    def _first_program():
        buf_ref[0] = 0
        # 0 x NaN is NaN: masked rows of the value product must be finite
        buf[...] = jnp.zeros_like(buf)

    buf0 = buf_ref[0]

    def after(r, blk):
        """The block after (r, blk) in the order the grid reads them;
        row ``n_rows`` when there is none."""
        n = (lens_ref[jnp.minimum(r, n_rows - 1)] + bt - 1) // bt
        last = blk + 1 >= n
        nxt = live_from_ref[jnp.minimum(r + 1, n_rows)]
        return jnp.where(last, nxt, r), jnp.where(last, 0, blk + 1)

    def ring(which, k):
        """The buffer ``k`` places after ``which``."""
        return jax.lax.rem(which + k, nbuf)

    @pl.when(row == live_from_ref[0])
    def _cold_start():
        r, blk = row, 0
        for k in range(nbuf - 1):
            block_dma(r, blk, ring(buf0, k), start=True)
            r, blk = after(r, blk)

    q = q_ref[0]                                        # [R, w]
    r_pad, _w = q.shape

    def scores(which, lo, n: int):
        return jax.lax.dot_general(
            q, buf[which, pl.ds(lo, n), :], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, n]

    def attend(carry, logits, which, lo):
        """The keys from ``lo`` that ``logits`` scores into the running
        softmax: values are the rows' first ``vw`` columns."""
        m_prev, l_prev = carry
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs.astype(buf.dtype),
            buf[which, pl.ds(lo, logits.shape[1]), pl.ds(0, vw)],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, vw]
        acc_ref[...] = acc_ref[...] * alpha + pv
        return m_new, l_new

    def run(state, which, lo, n: int, cuts: int, left=None):
        """Keys ``lo`` to ``lo + n`` of buffer ``which`` in ``cuts`` parts,
        as one basic block in which the scheduler runs part j + 1's scores
        beside j's softmax; ``left``: how many of the BLOCK's keys are
        live, where not all of these are."""
        part = n // cuts
        nxt = scores(which, lo, part)
        for j in range(cuts):
            logits = nxt
            if j + 1 < cuts:
                nxt = scores(which, lo + (j + 1) * part, part)
            if left is not None:
                pos = lo + j * part + jax.lax.broadcasted_iota(
                    jnp.int32, logits.shape, 1)
                logits = jnp.where(pos < left, logits, NEG_INF)
            state = attend(state, logits, which, lo + j * part)
        return state

    def fetch(i, hot: bool):
        """Start the block ``nbuf - 1`` after ``i`` (of this row or of the
        live rows that follow), then wait for block ``i``: returns its
        buffer. The starts come first: issued after the wait, the
        scheduler sinks them into the block's products and the DMAs,
        which bound the kernel or nearly do, start late. ``hot``: ``i`` is
        a whole block."""
        which = ring(buf0, i)
        last = i + 1 == n_blk    # ``after(row, i)`` without its SMEM reads
        r = jnp.where(last, live_from_ref[row + 1], row)
        blk = jnp.where(last, 0, i + 1)
        for _ in range(nbuf - 2):
            r, blk = after(r, blk)
        block_dma(r, blk, ring(which, nbuf - 1), start=True, hot=hot)
        block_dma(row, i, which, start=False, hot=hot, whole=hot)
        return which

    def whole_block(i, state):
        # every key live: no mask, no predicate on the waits
        return run(state, fetch(i, hot=True), 0, bt, subs)

    # a piece is cut as a block is, where its parts stay whole lane tiles
    cuts = subs if piece % (128 * subs) == 0 else 1

    def last_block(state):
        """The row's part-filled block, ``left`` keys of it live: all but
        the last of its pieces are wholly live and take the whole block's
        form; the last, whole or not, takes the mask."""
        which = fetch(n_full, hot=False)
        left = length - n_full * bt
        lo = 0
        if piece < bt:
            n_whole = (left - 1) // piece
            state = jax.lax.fori_loop(
                0, n_whole,
                lambda k, c: run(c, which, pl.multiple_of(k * piece, piece),
                                 piece, cuts), state)
            lo = pl.multiple_of(n_whole * piece, piece)
        return run(state, which, lo, piece, cuts, left)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    carry = jax.lax.fori_loop(
        0, n_full, whole_block,
        (jnp.full((r_pad, 1), NEG_INF, jnp.float32),
         jnp.zeros((r_pad, 1), jnp.float32)))
    _, l = jax.lax.cond(n_blk > n_full, last_block, lambda c: c, carry)
    acc = acc_ref[...]
    buf_ref[0] = ring(buf0, n_blk)
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "interpret", "plan"))
def latent_paged_attention_pallas(q, pool, page_table, seq_lens, rank: int,
                                  scale: float, interpret: bool = False,
                                  plan: tuple[int, ...] | None = None):
    """The TPU kernel: ``q`` [S, H, w] -> [S, H, rank] in the pool's dtype
    (divided in float32, then cast). A row of length 0 returns zeros.
    ``plan`` is ``_block_plan``'s answer for the shapes unless a test or
    ``tools/bench_latent_attention.py`` hands it another."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h, w = q.shape
    _one, _n, page_size, _w = pool.shape
    p = page_table.shape[1]
    dtype = pool.dtype
    tile = _sublane_tile(dtype)
    r_pad = -(-h // tile) * tile
    vw = min(w, -(-rank // 128) * 128)       # the value columns, whole lane tiles
    b, subs, nbuf, piece = plan or _block_plan(h, w, rank, page_size,
                                               dtype.itemsize, p)
    qr = (q.astype(jnp.float32) * scale).astype(dtype)
    if r_pad != h:
        qr = jnp.pad(qr, ((0, 0), (0, r_pad - h), (0, 0)))
    lens = jnp.clip(seq_lens.astype(jnp.int32), 0, p * page_size)
    live_from = jax.lax.cummin(
        jnp.where(lens > 0, jnp.arange(s, dtype=jnp.int32), s), reverse=True)
    live_from = jnp.concatenate([live_from, jnp.full((1,), s, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, r_pad, w), lambda si, *_: (si, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, r_pad, vw), lambda si, *_: (si, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nbuf, b * page_size, w), dtype),
                        pltpu.SemaphoreType.DMA((nbuf,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((r_pad, vw), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_block=b, subs=subs, nbuf=nbuf,
                          piece=piece, page_size=page_size, vw=vw),
        out_shape=jax.ShapeDtypeStruct((s, r_pad, vw), dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="latent_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lens, live_from, page_table.astype(jnp.int32).reshape(-1), qr, pool)
    return out[:, :h, :rank]    # the whole of it at the cells' shapes


def latent_paged_attention(q, pool, page_table, seq_lens, rank: int,
                           scale: float):
    """The absorbed decode attention, noted in ``ops/dispatch.py`` as
    ``latent_attention``: ``pallas`` on a TPU ([S, H, rank] in the pool's
    dtype), ``ref`` elsewhere (float32)."""
    if jax.default_backend() == "tpu":
        dispatch.note("latent_attention", "pallas")
        return latent_paged_attention_pallas(q, pool, page_table, seq_lens,
                                             rank, scale)
    dispatch.note("latent_attention", "ref")
    return latent_paged_attention_ref(q, pool, page_table, seq_lens, rank,
                                      scale)
