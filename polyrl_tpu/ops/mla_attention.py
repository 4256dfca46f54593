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
is what ``hybrid.mla_unabsorb`` reads. Blocks whose every key is live
take no mask and are one basic block; the row's part-filled last block
skips its dead sub-blocks.

Block size, sub-blocks and buffers are ``_block_plan``'s, a function of
the static shapes: the kernel's FLOPs a byte against the chip's ridge
(240 on v5e). Two regimes, kernel alone at the two cells' shapes on one
v5e (my chip runs, PR 36; the parent: 384 keys a block in one piece, two
buffers, value product over all 640 lanes, float32 out):

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
# from half the ridge up: keys a block, and the sub-blocks it is cut into
_MXU_BLOCK_KEYS, _MXU_SUBS = 2048, 2


def _block_plan(h: int, w: int, rank: int, page_size: int, itemsize: int,
                p: int) -> tuple[int, int, int]:
    """(pages a block, sub-blocks a block, buffers) from the static shapes
    alone: the kernel's arithmetic intensity against the chip's ridge. A
    key costs ``w * itemsize`` bytes of DMA and ``2 * h * (w + rank)``
    FLOPs (scores over the whole row, values over the rank columns); the
    module's docstring has the readings behind the two regimes."""
    if 2 * h * (w + rank) / (w * itemsize) < _RIDGE / 2:
        b = _DMA_BLOCK_BYTES // (page_size * w * itemsize)
        return max(1, min(p, b)), 1, 3
    b = max(1, min(p, _MXU_BLOCK_KEYS // page_size))
    return b, (_MXU_SUBS if b % _MXU_SUBS == 0 else 1), 2


def _kernel(lens_ref, live_from_ref, table_ref,   # scalar prefetch
            q_ref,      # [1, R, w] pre-scaled, pool dtype
            pool_hbm,   # [1, N, page_size, w], left in HBM
            out_ref,    # [1, R, vw] pool dtype
            buf,        # VMEM [nbuf, b * page_size, w]
            sems,       # DMA [nbuf buffers]
            buf_ref,    # SMEM [1]: buffer of the row's first block
            *, pages_per_block: int, subs: int, nbuf: int, page_size: int,
            vw: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pages_per_block
    bt = b * page_size
    sub = bt // subs
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
        n_pg = (lens_ref[r] + page_size - 1) // page_size - blk * b
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

    def start(r, blk, which, hot: bool = False):
        @pl.when(r < n_rows)
        def _():
            block_dma(r, blk, which, start=True, hot=hot)

    def ring(which, k):
        """The buffer ``k`` places after ``which``."""
        return jax.lax.rem(which + k, nbuf)

    @pl.when(row == live_from_ref[0])
    def _cold_start():
        r, blk = row, 0
        for k in range(nbuf - 1):
            start(r, blk, ring(buf0, k))
            r, blk = after(r, blk)

    q = q_ref[0]                                        # [R, w]
    r_pad, _w = q.shape

    def scores(which, j):
        return jax.lax.dot_general(
            q, buf[which, pl.ds(j * sub, sub), :], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, sub]

    def attend(carry, logits, which, j):
        """One sub-block into the running softmax: values are the rows'
        first ``vw`` columns."""
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs.astype(buf.dtype),
            buf[which, pl.ds(j * sub, sub), pl.ds(0, vw)],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, vw]
        return m_new, l_new, acc * alpha + pv

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
        start(r, blk, ring(which, nbuf - 1), hot)
        block_dma(row, i, which, start=False, hot=hot, whole=hot)
        return which

    def whole_block(i, state):
        # every key live: no mask, and one basic block, in which the
        # scheduler runs sub-block j + 1's scores beside j's softmax
        which = fetch(i, hot=True)
        nxt = scores(which, 0)
        for j in range(subs):
            logits = nxt
            if j + 1 < subs:
                nxt = scores(which, j + 1)
            state = attend(state, logits, which, j)
        return state

    def last_block(state):
        # the row's part-filled block: dead sub-blocks are skipped
        which = fetch(n_full, hot=False)
        left = length - n_full * bt
        for j in range(subs):
            def live(state, j=j):
                logits = scores(which, j)
                pos = j * sub + jax.lax.broadcasted_iota(
                    jnp.int32, logits.shape, 1)
                return attend(state, jnp.where(pos < left, logits, NEG_INF),
                              which, j)

            state = jax.lax.cond(j * sub < left, live, lambda c: c, state)
        return state

    carry = jax.lax.fori_loop(
        0, n_full, whole_block,
        (jnp.full((r_pad, 1), NEG_INF, jnp.float32),
         jnp.zeros((r_pad, 1), jnp.float32),
         jnp.zeros((r_pad, vw), jnp.float32)))
    _, l, acc = jax.lax.cond(n_blk > n_full, last_block, lambda c: c, carry)
    buf_ref[0] = ring(buf0, n_blk)
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "interpret", "plan"))
def latent_paged_attention_pallas(q, pool, page_table, seq_lens, rank: int,
                                  scale: float, interpret: bool = False,
                                  plan: tuple[int, int, int] | None = None):
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
    b, subs, nbuf = plan or _block_plan(h, w, rank, page_size,
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
                        pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_block=b, subs=subs, nbuf=nbuf,
                          page_size=page_size, vw=vw),
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
