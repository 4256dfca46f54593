"""Grouped matmul for the MoE expert projections: every row multiplies
with its own group's matrix.

The rows come tiled (``tiled_layout``): group g's rows are contiguous and
padded with zero rows to whole tiles of ``tile`` rows, so a tile belongs to
one group and the TPU kernel is a plain matmul a tile, whose weights are
chosen by a prefetched table of each tile's group. A group without rows is
never read. The rows total at most ``N + G*tile`` for N real rows.

A group's weights reach VMEM as slabs of whole rows along K
(``_slab_plan``): ``tk`` rows of every column, one contiguous run in HBM,
each slab's product summed into a float32 accumulator a weight, the
output block written once a tile. The grid is (tiles, slabs), slabs
innermost, under ``BlockSpec``'s own double-buffered pipeline. Matrices
that fit 8 MiB a step (gate and up together) go in whole, ONE slab, and
then consecutive tiles of one group reuse the block already in VMEM: a
group's weights are read once a call however many tiles it fills. Larger
ones are cut into slabs of about 4 MiB, and a group of several tiles then
reads its weights once a TILE: decode has one tile a group (``row_tile``
is twice the mean); a prefill chunk or the trainer's forward over such
matrices in tiles of 256 rows is bound by the MXU either way (256 FLOPs a
weight byte against the chip's 240). Why slabs and not column blocks: a
block [K, tn] of a row-major matrix is K strided runs, which at
dots.vlm1's widths (runs of 512 B) read 86% of 819 GB/s where a slab
reads 92%, what a whole matrix reads and this chip's ceiling for a
stream; and nothing beyond the pipeline's two buffers is needed for it
(PERF.md section 6, PR 45).

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

# a step's slabs, one a weight (gate and up together): whole matrices
# where they fit the first, else whole rows up to about the second
_WHOLE_BYTES = 8 * 2**20
_SLAB_BYTES = 4 * 2**20
# what the kernel asks of the chip's 128 MiB of VMEM: two slabs in flight,
# a tile's float32 sums, the rows' and the output's blocks take up to 29
# MiB at the shapes the cells run (dots.vlm1's down in tiles of 256 rows)
_VMEM_LIMIT_BYTES = 40 * 2**20


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


def _slab_plan(k: int, n: int, itemsize: int, n_w: int) -> int:
    """Rows a slab: how ``n_w`` weights [k, n] of ``itemsize`` bytes reach
    VMEM. A slab is ``tk`` whole rows of each weight, one contiguous run
    in HBM: all ``k`` where the step's weights fit ``_WHOLE_BYTES`` (or k
    has no multiple of 128 to cut at), else the most rows, a multiple of
    128 that divides k, that fit ``_SLAB_BYTES``."""
    row_bytes = n * itemsize * n_w
    if k * row_bytes <= _WHOLE_BYTES or k % 128:
        return k
    tk = max(128, _SLAB_BYTES // row_bytes // 128 * 128)
    while k % tk:
        tk -= 128
    return tk


def _kernel(tile_group_ref, tiles_used_ref, x_ref, *refs, n_w, scaled,
            n_slabs):
    """One slab of one used tile: the tile's columns of that slab times
    the slab of each weight, summed over the slabs in float32; after the
    last, the scales, SwiGLU of two weights (``silu(x @ w0) * (x @ w1)``)
    in float32 and the ONE rounding."""
    del tile_group_ref, tiles_used_ref
    ws, rest = refs[:n_w], refs[n_w:]
    scales, rest = (rest[:n_w], rest[n_w:]) if scaled else ((), rest)
    o_ref, accs = rest[0], rest[1:]
    x = x_ref[...]
    ys = [jax.lax.dot_general(
        x, w[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for w in ws]

    def finish(ys):
        ys = [y * s[...] for y, s in zip(ys, scales)] or ys
        y = ys[0] if n_w == 1 else jax.nn.silu(ys[0]) * ys[1]
        o_ref[...] = y.astype(o_ref.dtype)

    if n_slabs == 1:
        finish(ys)
        return
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _first():
        for acc, y in zip(accs, ys):
            acc[...] = y

    @pl.when(s > 0)
    def _add():
        for acc, y in zip(accs, ys):
            acc[...] += y

    @pl.when(s == n_slabs - 1)
    def _last():
        finish([acc[...] for acc in accs])


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "slab"))
def grouped_matmul_pallas(x, ws, tile_group, tiles_used, scales=None, *,
                          tile: int, interpret: bool = False,
                          slab: int | None = None):
    """``x`` [T*tile, K] in the tiled layout times ``ws[0]`` [G, K, N]
    (any dtype: a slab is cast to x's in VMEM, so int8 weights are read as
    int8), tile j with group ``tile_group[j]``'s matrix; with two weights
    the result is ``silu(x @ ws[0]) * (x @ ws[1])``, one pass over x and
    half the grid steps a weight byte. ``scales`` (one [G, N] float32 a
    weight, or None) multiply a tile's product by its group's row. The
    grid ends at ``tiles_used``: the tiles past it are not visited, fetch
    nothing, and their rows of the result are undefined. ``slab`` is
    ``_slab_plan``'s rows a slab unless a test or the bench tool hands
    another (a multiple of 128 that divides K)."""
    m, k = x.shape
    g, _k, n = ws[0].shape
    n_w = len(ws)
    tk = slab or _slab_plan(k, n, ws[0].dtype.itemsize, n_w)
    n_slabs = k // tk
    w_spec = pl.BlockSpec((None, tk, n), lambda j, s, tg, used: (tg[j], s, 0))
    s_spec = pl.BlockSpec((None, 1, n), lambda j, s, tg, used: (tg[j], 0, 0))
    scales = [s.astype(jnp.float32).reshape(g, 1, n) for s in scales or ()]
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, scaled=bool(scales),
                          n_slabs=n_slabs),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles_used[0], n_slabs),
            in_specs=[pl.BlockSpec((tile, tk), lambda j, s, tg, used: (j, s))]
            + [w_spec] * n_w + [s_spec] * len(scales),
            out_specs=pl.BlockSpec((tile, n), lambda j, s, tg, used: (j, 0)),
            # a tile's float32 sums over its slabs, one a weight
            scratch_shapes=[pltpu.VMEM((tile, n), jnp.float32)]
            * (n_w if n_slabs > 1 else 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
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
