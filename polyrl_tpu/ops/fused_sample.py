"""The decode step's output matmul and its sampler as one kernel: the
vocabulary is walked in tiles, and the ``[rows, vocab]`` float32 logits are
never written.

A grid step multiplies the rows by one tile of the head (operands in the
model's dtype on the MXU, float32 accumulation, as ``quant.unembed``) and
keeps the tile's logits in VMEM. Over them it carries, per row and per
lane (column modulo 128) in float32: the running maximum and the sum of
exponentials of ``z/T`` (an online log-sum-exp), and the best of
``z/T + g`` with its column and the ``z/T`` there, ``g`` standard Gumbel
noise: the Gumbel-max draw ``jax.random.categorical`` makes. The lanes are
folded once, after the last tile. A greedy row (``temps <= 0``) runs with
``T = 1`` and ``g = 0``: the exact first argmax, scored under the raw
logits, as ``sampling.sample_token_vec`` scores it.

The noise is a function of (key, row, column) alone: threefry2x32, JAX's
own default generator, with the row and the column pair as the counter and
both output words used (word 0 for an even 128-column group, word 1 for
the odd one after it), so the tile width does not move a draw and the
interpreter draws what the chip draws. The head is bound by its weights'
DMA; generator and epilogue run on the VPU beside it, eight rows and two
column groups at a time so that a row block's running values stay in
registers.

On a TPU the kernel lowers or raises; there is no jnp path behind it here
(``decoder.samples_in_head`` chooses between this and head + sampler).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8             # a float32 sublane tile: the epilogue's row block
_NEG = -1e30          # a masked column's logit: finite, so m - m is 0
# a head tile (double-buffered) beside the logits tile in the VMEM asked
# for. Timed on a v5e at 2, 4, 8 and 16 MiB (65 rows, both cells' heads):
# all within 1%, the kernel at the matmul's own time (PERF.md section 6)
_TILE_BYTES = 4 * 2**20
_VMEM_LIMIT = 32 * 2**20


def vocab_tile(d: int, vocab: int, itemsize: int) -> int:
    """Vocabulary columns a grid step: the largest multiple of 256 (two
    column groups share a generator call) whose ``[d, tile]`` head block
    fits ``_TILE_BYTES``, and no more than hold the vocabulary."""
    fits = max(256, _TILE_BYTES // (d * itemsize) // 256 * 256)
    return min(fits, -(-vocab // 256) * 256)


def _threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds, on int32 words (two's-complement adds
    wrap as uint32's do): ``jax.extend.random.threefry_2x32`` word for
    word."""
    def rotl(v, r):
        return (v << r) | jax.lax.shift_right_logical(v, 32 - r)

    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in ((13, 15, 26, 6), (17, 29, 16, 24))[i % 2]:
            x0 = x0 + x1
            x1 = x0 ^ rotl(x1, r)
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + (i + 1)
    return x0, x1


def _gumbel(bits):
    """Standard Gumbel noise from 32 random bits: a uniform of 23 bits in
    [tiny, 1) as ``jax.random.uniform`` forms it, then -log(-log(u))."""
    one_to_two = jax.lax.bitcast_convert_type(
        jax.lax.shift_right_logical(bits, 9) | 0x3F800000, jnp.float32)
    u = jnp.maximum(one_to_two - 1.0, jnp.finfo(jnp.float32).tiny)
    return -jnp.log(-jnp.log(u))


def gumbel_noise(key_words, rows, cols):
    """The noise the kernel draws at (``rows``, ``cols``) (int32 arrays of
    one shape) under ``key_words`` (two int32 words)."""
    group = cols // _LANES
    x0, x1 = _threefry2x32(key_words[0], key_words[1], rows,
                           (group // 2) * _LANES + cols % _LANES)
    return _gumbel(jnp.where(group % 2 == 0, x0, x1))


def _kernel(key_ref, x_ref, w_ref, temps_ref, *refs, vocab, tile, tied,
            given_noise):
    """One vocabulary tile: its logits into ``z_ref``, then the running
    values of every row block over the tile's column groups; the last
    tile folds the lanes into the token and its log-probability."""
    noise_ref = refs[0] if given_noise else None
    (tok_ref, logp_ref, z_ref, m_ref, l_ref, b_ref, bz_ref,
     bi_ref) = refs[given_noise:]
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        b_ref[...] = jnp.full_like(b_ref, -jnp.inf)
        bz_ref[...] = jnp.zeros_like(bz_ref)
        bi_ref[...] = jnp.zeros_like(bi_ref)

    # tests/conftest.py sets a process-wide "highest", under which Mosaic
    # refuses bf16 operands: DEFAULT is what a chip run has (PR 26)
    z_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (1 if tied else 0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)

    k0, k1 = key_ref[0], key_ref[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)

    def row_block(r, carry):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        t = temps_ref[rows, :]                               # [8, 1]
        sampled = t > 0.0
        inv_t = jnp.where(sampled, 1.0 / jnp.maximum(t, 1e-6), 1.0)
        m, l, b = m_ref[rows, :], l_ref[rows, :], b_ref[rows, :]
        bz, bi = bz_ref[rows, :], bi_ref[rows, :]
        for p in range(tile // (2 * _LANES)):
            if not given_noise:
                x0, x1 = _threefry2x32(
                    k0, k1, r * _ROWS + sublane,
                    j * (tile // 2) + p * _LANES + lane)
            for h in range(2):
                off = (2 * p + h) * _LANES
                col = j * tile + off + lane
                g = (noise_ref[rows, pl.ds(off, _LANES)] if given_noise
                     else _gumbel(x1 if h else x0))
                z = jnp.where(col < vocab,
                              z_ref[rows, pl.ds(off, _LANES)] * inv_t, _NEG)
                y = z + jnp.where(sampled, g, 0.0)
                m_new = jnp.maximum(m, z)
                l = l * jnp.exp(m - m_new) + jnp.exp(z - m_new)
                m = m_new
                # strict: a lane keeps its first (lowest) best column
                better = y > b
                b = jnp.where(better, y, b)
                bz = jnp.where(better, z, bz)
                bi = jnp.where(better, col, bi)
        m_ref[rows, :], l_ref[rows, :], b_ref[rows, :] = m, l, b
        bz_ref[rows, :], bi_ref[rows, :] = bz, bi
        return carry

    jax.lax.fori_loop(0, z_ref.shape[0] // _ROWS, row_block, None)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        m = m_ref[...]
        row_m = jnp.max(m, axis=1, keepdims=True)
        row_l = jnp.sum(l_ref[...] * jnp.exp(m - row_m), axis=1,
                        keepdims=True)
        b = b_ref[...]
        # the lowest column among the lanes that hold the best value
        cand = jnp.where(b == jnp.max(b, axis=1, keepdims=True),
                         bi_ref[...], jnp.iinfo(jnp.int32).max)
        tok = jnp.min(cand, axis=1, keepdims=True)
        z_tok = jnp.sum(jnp.where(cand == tok, bz_ref[...], 0.0), axis=1,
                        keepdims=True)
        tok_ref[...] = tok
        logp_ref[...] = z_tok - (row_m + jnp.log(row_l))


def key_words(rng) -> jnp.ndarray:
    """Two int32 words of ``rng`` (a typed key or raw key data) for the
    kernel's generator."""
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    return jax.lax.bitcast_convert_type(rng.reshape(-1)[:2], jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("tied", "tile", "interpret"))
def head_sample_pallas(x, head, rng, temps, noise=None, *, tied=False,
                       tile=None, interpret=False):
    """``(token [S] int32, logp [S] float32)`` for rows ``x`` [S, d] (after
    the final norm) under ``head`` ([d, V]; ``tied``: the embedding
    [V, d], contracted on its last axis, never transposed) and ``temps``
    [S] (``<= 0``: greedy): a draw from softmax(x @ head / T) and its
    log-probability there, as ``sampling.sample_token_vec`` without
    filters gives them. ``noise`` [S, V] float32, for tests, takes the
    place of the generator's draw under ``rng``."""
    s, d = x.shape
    vocab = head.shape[0] if tied else head.shape[1]
    tile = tile or vocab_tile(d, vocab, head.dtype.itemsize)
    # whole sublane tiles of x's dtype (16 rows of bf16)
    pad = -s % (_ROWS * 4 // x.dtype.itemsize)
    sp = s + pad
    x = jnp.pad(x, ((0, pad), (0, 0)))
    temps = jnp.pad(temps.astype(jnp.float32), (0, pad)).reshape(sp, 1)
    whole = lambda j, key: (0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((sp, d), whole),
        pl.BlockSpec((tile, d), lambda j, key: (j, 0)) if tied
        else pl.BlockSpec((d, tile), lambda j, key: (0, j)),
        pl.BlockSpec((sp, 1), whole)]
    args = [key_words(rng), x, head, temps]
    if noise is not None:
        in_specs.append(pl.BlockSpec((sp, tile), lambda j, key: (0, j)))
        args.append(jnp.pad(noise, ((0, pad), (0, 0))))
    lanes = lambda dt: pltpu.VMEM((sp, _LANES), dt)  # noqa: E731
    tok, logp = pl.pallas_call(
        functools.partial(_kernel, vocab=vocab, tile=tile, tied=tied,
                          given_noise=noise is not None),
        out_shape=(jax.ShapeDtypeStruct((sp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((sp, 1), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(vocab, tile),),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((sp, 1), whole),
                       pl.BlockSpec((sp, 1), whole)),
            scratch_shapes=[pltpu.VMEM((sp, tile), jnp.float32)]
            + [lanes(jnp.float32)] * 4 + [lanes(jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="head_sample", interpret=interpret,
    )(*args)
    return tok[:s, 0], logp[:s, 0]
