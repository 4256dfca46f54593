"""Grouped matmul for the MoE expert projections: every row multiplies
with its own group's matrix.

The rows come tiled (``tiled_layout``): group g's rows are contiguous and
padded with zero rows to whole tiles of ``tile`` rows, so a tile belongs to
one group and the TPU kernel is a plain matmul a tile, whose weight block
is chosen by a prefetched table of each tile's group. Consecutive tiles of
one group reuse the block already in VMEM, so a group's weights are read
from HBM once a call however many tiles it fills, and a group without rows
is never read. The rows total at most ``N + G*tile`` for N real rows.

Why not ``jax.lax.ragged_dot`` on the TPU: XLA lowers it to a grouped
kernel of its own, which at decode shapes (512 rows over 128 experts of
2048x768) reads the weights at 210 GB/s, against 708 GB/s for the dense
batched einsum over every expert that this block replaces (PERF.md section
6, PR 27). It stays the CPU path and the gradient's (``grouped_matmul`` has
a custom VJP whose cotangents are ``ragged_dot``'s own). So there are two
implementations, not one: the forward is this kernel on a TPU, every
gradient is XLA's. At a trainer's shapes the pair was timed once and
tuned never: a layer's forward and backward over 16,384 tokens take 106 ms,
35 TFLOP/s of useful work (PERF.md section 6, PR 27).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a step's weight blocks (double-buffered) have to leave room in the VMEM
# the kernel asks for (40 MiB of the chip's 128) for row and output blocks
_MAX_WEIGHT_BLOCK_BYTES = 8 * 2**20


def row_tile(n_rows: int, n_groups: int) -> int:
    """Rows a tile: twice the mean rows a group rounded up to a power of
    two (most groups then fill one tile, and the MXU loads a group's
    weights once), between 16 (a packed bf16 sublane tile) and 256. Decode
    (4 rows an expert) pads little; the trainer (thousands) fills the
    MXU."""
    mean = max(1, -(-n_rows // n_groups))
    return int(min(256, max(16, 1 << (2 * mean - 1).bit_length())))


class TiledLayout(NamedTuple):
    """Where group-sorted rows sit once every group is padded to whole
    tiles (``tiled_layout``)."""

    tile_group: jnp.ndarray   # [T] group of each tile
    tiles_used: jnp.ndarray   # [1] tiles that hold rows
    padded_sizes: jnp.ndarray  # [G] rows a group, padded to whole tiles
    src: jnp.ndarray          # [T*tile] sorted row a tiled row holds
    live: jnp.ndarray         # [T*tile] False for pad rows
    shift: jnp.ndarray        # [G] sorted row r of group g -> r + shift[g]

    @property
    def tile(self) -> int:
        """Rows a tile (static: it is in the shapes)."""
        return self.src.shape[0] // self.tile_group.shape[0]


def tiled_layout(sizes: jnp.ndarray, n_rows: int, tile: int) -> TiledLayout:
    """The tiled layout of ``sum(sizes) <= n_rows`` rows sorted by group,
    ``sizes`` [G] rows a group: group g's rows are contiguous from a tile
    boundary, so tile j holds rows of group ``tile_group[j]`` alone.
    T = n_rows // tile + G tiles bound every routing; tiles past
    ``tiles_used`` repeat the last group, so that the kernel fetches
    nothing for them.

    A group's numbers reach its tiles through a [T, G] one-hot and its
    rows by broadcast: a gather of T*tile scalars from a table of G takes
    the TPU 60 us (PERF.md section 6, PR 27), the one-hot sums nothing."""
    g = sizes.shape[0]
    n_tiles = n_rows // tile + g
    tiles = -(-sizes // tile)                                   # [G]
    tile_end = jnp.cumsum(tiles)
    start = jnp.cumsum(sizes) - sizes
    tiled_start = (tile_end - tiles) * tile
    j = jnp.minimum(jnp.arange(n_tiles), tile_end[-1] - 1)
    tile_group = jnp.sum(tile_end[None, :] <= j[:, None], axis=1)
    of_group = tile_group[:, None] == jnp.arange(g)[None, :]     # [T, G]

    def a_tile(table):       # table[tile_group], without a gather
        return jnp.sum(jnp.where(of_group, table[None, :], 0), axis=1)

    # row p of tile j: the (p - tiled_start)-th row of the tile's group
    within = (jnp.arange(n_tiles * tile).reshape(n_tiles, tile)
              - a_tile(tiled_start)[:, None])
    live = (within >= 0) & (within < a_tile(sizes)[:, None])
    src = a_tile(start)[:, None] + within
    i32 = jnp.int32
    return TiledLayout(
        tile_group.astype(i32), tile_end[-1:].astype(i32),
        (tiles * tile).astype(i32), src.reshape(-1).astype(i32),
        live.reshape(-1), (tiled_start - start).astype(i32))


def _out_block(k: int, n: int, itemsize: int) -> int:
    """Output columns a weight block: all ``n`` of them if [k, n] fits
    ``_MAX_WEIGHT_BLOCK_BYTES``, else the largest multiple of 128 that
    divides n and fits."""
    if k * n * itemsize <= _MAX_WEIGHT_BLOCK_BYTES:
        return n
    tn = max(128, _MAX_WEIGHT_BLOCK_BYTES // (k * itemsize) // 128 * 128)
    while n % tn:
        tn -= 128
    return tn


def _kernel(tile_group_ref, tiles_used_ref, x_ref, *refs, n_w, scaled):
    """One used tile: its rows times its group's block of each weight;
    two weights are SwiGLU's gate and up (``silu(x @ w0) * (x @ w1)``,
    formed in float32 before the one rounding)."""
    del tile_group_ref, tiles_used_ref
    ws, rest = refs[:n_w], refs[n_w:]
    scales, o_ref = (rest[:n_w], rest[n_w]) if scaled else ((), rest[0])
    x = x_ref[...]
    ys = [jax.lax.dot_general(
        x, w[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for w in ws]
    ys = [y * s[...] for y, s in zip(ys, scales)] or ys
    y = ys[0] if n_w == 1 else jax.nn.silu(ys[0]) * ys[1]
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_matmul_pallas(x, ws, tile_group, tiles_used, scales=None, *,
                          tile: int, interpret: bool = False):
    """``x`` [T*tile, K] in the tiled layout times ``ws[0]`` [G, K, N]
    (any dtype: a block is cast to x's in VMEM, so int8 weights are read as
    int8), tile j with group ``tile_group[j]``'s matrix; with two weights
    the result is ``silu(x @ ws[0]) * (x @ ws[1])``, one pass over x and
    half the grid steps a weight byte. ``scales`` (one [G, N] float32 a
    weight, or None) multiply a tile's product by its group's row. The
    grid ends at ``tiles_used``: the tiles past it are not visited, fetch
    nothing, and their rows of the result are undefined."""
    m, k = x.shape
    g, _k, n = ws[0].shape
    tn = _out_block(k, n, ws[0].dtype.itemsize * len(ws))
    w_spec = pl.BlockSpec((None, k, tn), lambda b, j, tg, used: (tg[j], 0, b))
    s_spec = pl.BlockSpec((None, 1, tn), lambda b, j, tg, used: (tg[j], 0, b))
    scales = [s.astype(jnp.float32).reshape(g, 1, n) for s in scales or ()]
    return pl.pallas_call(
        functools.partial(_kernel, n_w=len(ws), scaled=bool(scales)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, tiles_used[0]),
            in_specs=[pl.BlockSpec((tile, k), lambda b, j, tg, used: (j, 0))]
            + [w_spec] * len(ws) + [s_spec] * len(scales),
            out_specs=pl.BlockSpec((tile, tn),
                                   lambda b, j, tg, used: (j, b))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # gate and up blocks of 3 MiB each, double-buffered
            vmem_limit_bytes=40 * 2**20),
        name="grouped_matmul", interpret=interpret,
    )(tile_group, tiles_used, x, *ws, *scales)


def _ragged(x, ws, scales, lay: TiledLayout):
    """The same result by ``jax.lax.ragged_dot`` over the padded groups
    (pad rows are zero rows of ``x``); rows past the last group are zero."""
    ys = [jax.lax.ragged_dot(x, w.astype(x.dtype), lay.padded_sizes)
          for w in ws]
    if scales is not None:
        rows = jnp.repeat(lay.tile_group, lay.tile)
        ys = [y.astype(jnp.float32) * s[rows] for y, s in zip(ys, scales)]
    if len(ws) == 2:
        ys = [jax.nn.silu(ys[0].astype(jnp.float32))
              * ys[1].astype(jnp.float32)]
    live = jnp.arange(x.shape[0]) < jnp.sum(lay.padded_sizes)
    return jnp.where(live[:, None], ys[0].astype(x.dtype), 0)


@jax.custom_vjp
def grouped_matmul(x, ws, scales, lay: TiledLayout):
    """``x`` [T*tile, K] in the layout ``lay`` times the stacked matrices
    ``ws`` (a tuple of one [G, K, N], or of SwiGLU's gate and up;
    ``scales`` as ``grouped_matmul_pallas`` takes them): the Pallas kernel
    on a TPU (it lowers or raises), ``ragged_dot`` elsewhere. Rows of tiles
    that hold no row are undefined. Differentiable in ``x`` and ``ws``."""
    if jax.default_backend() == "tpu":
        return grouped_matmul_pallas(x, ws, lay.tile_group, lay.tiles_used,
                                     scales, tile=lay.tile)
    return _ragged(x, ws, scales, lay)


def _fwd(x, ws, scales, lay):
    return grouped_matmul(x, ws, scales, lay), (x, ws, scales, lay)


def _bwd(res, dy):
    x, ws, scales, lay = res
    _y, vjp = jax.vjp(lambda x, ws: _ragged(x, ws, scales, lay), x, ws)
    dx, dws = vjp(dy)
    return dx, dws, None, None


grouped_matmul.defvjp(_fwd, _bwd)
