"""Decode attention of a multi-head latent attention layer over a paged
latent pool, in the absorbed form: every head's query already lies in the
latent's space (``hybrid.mla_absorb``), so one row of ``rank + rope``
values a token is key for all heads, and its first ``rank`` columns are
the value too.

``latent_paged_attention`` is the dispatcher: on a TPU the kernel below,
elsewhere a gather-based oracle. The kernel is ``ops/paged_attention.py``'s
multi-page decode kernel with one pool in place of a K/V pair: one program
an attention row, a loop over the row's own blocks of pages, the next
block (or the next live row's first) in flight while one is computed, the
heads as the rows of one MXU product [H, w] x [block, w], online softmax
in float32. The value product runs over the whole row and the caller
keeps its first ``rank`` columns: a slice at a lane boundary inside the
kernel buys nothing, the rope columns are a ninth of the product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from polyrl_tpu.ops import dispatch
from polyrl_tpu.ops.paged_attention import (NEG_INF, _pages_per_block,
                                            _sublane_tile)


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


def _kernel(lens_ref, live_from_ref, table_ref,   # scalar prefetch
            q_ref,      # [1, R, w] pre-scaled, pool dtype
            pool_hbm,   # [1, N, page_size, w], left in HBM
            out_ref,    # [1, R, w] float32
            buf,        # VMEM [2, b * page_size, w]
            sems,       # DMA [2 buffers]
            buf_ref,    # SMEM [1]: buffer of the row's first block
            *, pages_per_block: int, page_size: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pages_per_block
    bt = b * page_size
    n_rows = pl.num_programs(0)
    p = table_ref.shape[0] // n_rows
    row = pl.program_id(0)
    length = lens_ref[row]
    n_blk = (length + bt - 1) // bt

    def block_dma(r, blk, which, start: bool):
        n_pg = (lens_ref[r] + page_size - 1) // page_size
        for j in range(b):
            col = blk * b + j

            @pl.when(col < n_pg)
            def _():
                cp = pltpu.make_async_copy(
                    pool_hbm.at[0, table_ref[r * p + col]],
                    buf.at[which, pl.ds(j * page_size, page_size)],
                    sems.at[which])
                if start:
                    cp.start()
                else:
                    cp.wait()

    @pl.when(row == 0)
    def _first_program():
        buf_ref[0] = 0
        # 0 x NaN is NaN: masked rows of the value product must be finite
        buf[...] = jnp.zeros_like(buf)

    buf0 = buf_ref[0]

    @pl.when(row == live_from_ref[0])
    def _cold_start():
        block_dma(row, 0, buf0, start=True)

    q = q_ref[0]                                        # [R, w]
    r_pad, w = q.shape

    def body(i, carry):
        m_prev, l_prev, acc = carry
        which = (buf0 + i) & 1
        last = i + 1 == n_blk
        nxt_row = jnp.where(last, live_from_ref[row + 1], row)
        nxt_blk = jnp.where(last, 0, i + 1)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            block_dma(nxt_row, nxt_blk, 1 - which, start=True)

        block_dma(row, i, which, start=False)
        rows = buf[which]                               # [bt, w]
        logits = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, bt]
        pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < length, logits, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)         # [R, w]
        return m_new, l_new, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        0, n_blk, body,
        (jnp.full((r_pad, 1), NEG_INF, jnp.float32),
         jnp.zeros((r_pad, 1), jnp.float32),
         jnp.zeros((r_pad, w), jnp.float32)))
    buf_ref[0] = (buf0 + n_blk) & 1
    out_ref[0] = acc / jnp.maximum(l, 1e-30)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_paged_attention_pallas(q, pool, page_table, seq_lens, rank: int,
                                  scale: float, interpret: bool = False):
    """The TPU kernel: ``q`` [S, H, w] -> [S, H, rank] float32. A row of
    length 0 returns zeros."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h, w = q.shape
    _one, _n, page_size, _w = pool.shape
    p = page_table.shape[1]
    dtype = pool.dtype
    tile = _sublane_tile(dtype)
    r_pad = -(-h // tile) * tile
    b = _pages_per_block(1, page_size, w, dtype.itemsize, p)
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
        out_specs=pl.BlockSpec((1, r_pad, w), lambda si, *_: (si, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, b * page_size, w), dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_block=b, page_size=page_size),
        out_shape=jax.ShapeDtypeStruct((s, r_pad, w), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="latent_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(lens, live_from, page_table.astype(jnp.int32).reshape(-1), qr, pool)
    return out[:, :h, :rank]


def latent_paged_attention(q, pool, page_table, seq_lens, rank: int,
                           scale: float):
    """The absorbed decode attention, noted in ``ops/dispatch.py`` as
    ``latent_attention``: ``pallas`` on a TPU, ``ref`` elsewhere."""
    if jax.default_backend() == "tpu":
        dispatch.note("latent_attention", "pallas")
        return latent_paged_attention_pallas(q, pool, page_table, seq_lens,
                                             rank, scale)
    dispatch.note("latent_attention", "ref")
    return latent_paged_attention_ref(q, pool, page_table, seq_lens, rank,
                                      scale)
