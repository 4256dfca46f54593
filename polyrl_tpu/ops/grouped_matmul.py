"""Grouped matmul for the MoE expert projections: every row multiplies
with its own group's matrix, in one of two forms chosen by the shapes
alone (``rows_by_table``; on a TPU, ``in_kernel``): a decode step's few
rows an expert are taken from the tokens by a prefetched table inside the
gate/up kernel and summed back into each token by the down kernel
(``expert_rows``, the file's second half: no tiled copy of the rows exists
outside the kernels); a prefill chunk's or the trainer's thousands come
tiled, as follows, and so does everything off a TPU and on a mesh.

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
then consecutive tiles of one group reuse the block already in VMEM.
Larger ones are cut into slabs of about 4 MiB, and a group of several
tiles then reads its weights once a TILE: decode has one tile a group
(``row_tile`` is twice the mean); tiles of 256 rows are bound by the MXU
either way (256 FLOPs a weight byte against the chip's 240). A weight
whose columns are no whole number of lane tiles (Nemotron-3-Nano's 2688 x
1856: 14.5) lies on the chip as its transpose, the rows minor (the device's
own layout for the shape): it is read where it lies, as ``[N, K]`` rows,
whole, and multiplied from the right (``_as_rows``); as ``[K, N]`` the
program copies the whole stack before every dispatch (3.5 GB at 23 layers
of 16 experts). Why slabs
and not column blocks: a block [K, tn] of a row-major matrix is K strided
runs, which at dots.vlm1's widths (runs of 512 B) read 86% of 819 GB/s
where a slab reads 92%, this chip's ceiling (PERF.md section 6, PR 45).

Why not ``jax.lax.ragged_dot`` on the TPU: XLA lowers it to a grouped
kernel of its own, which at decode shapes (512 rows over 128 experts of
2048x768) reads the weights at 210 GB/s, against 708 GB/s for the dense
batched einsum over every expert that this block replaces (PERF.md section
6, PR 27). It stays the CPU path and every gradient's (the custom VJPs'
cotangents are XLA's over ``_ragged``). At a trainer's shapes the pair was
timed once: a layer's forward and backward over 16,384 tokens take 106 ms,
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
_LANES = 128


def _as_rows(ws: tuple) -> tuple[tuple, bool]:
    """(the weights as the kernels read them, whether as ``[G, N, K]``
    rows): a stack ``[G, K, N]`` whose ``N`` is no whole number of lane
    tiles as its transpose, which is where the chip holds it (module
    docstring), so that the view costs nothing there."""
    if ws[0].shape[-1] % _LANES == 0:
        return ws, False
    return tuple(jnp.swapaxes(w, 1, 2) for w in ws), True


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
    128 that divides k, that fit ``_SLAB_BYTES``. All ``k`` too where ``n``
    is no whole number of lane tiles (``_as_rows``)."""
    row_bytes = n * itemsize * n_w
    if k * row_bytes <= _WHOLE_BYTES or k % 128 or n % _LANES:
        return k
    tk = max(128, _SLAB_BYTES // row_bytes // 128 * 128)
    while k % tk:
        tk -= 128
    return tk


def _activate(ys: list, act: str):
    """What a tile's float32 products ``ys`` (one a weight) become before
    the ONE rounding: SwiGLU of two weights (``silu(x @ w0) * (x @ w1)``),
    one weight's product as it is, or with ``act`` ``relu2`` its
    ``relu(x @ w0)^2`` (an expert of two matrices: no gate)."""
    if act == "relu2":
        return jnp.square(jnp.maximum(ys[0], 0.0))
    if act:
        raise ValueError(f"activation {act!r} of a grouped matmul")
    return ys[0] if len(ys) == 1 else jax.nn.silu(ys[0]) * ys[1]


def _kernel(tile_group_ref, tiles_used_ref, x_ref, *refs, n_w, scaled,
            n_slabs, act="", rows=False):
    """One slab of one used tile: the tile's columns of that slab times
    the slab of each weight, summed over the slabs in float32; after the
    last, the scales, the activation (``_activate``) in float32 and the
    ONE rounding. ``rows``: a weight's block is ``[N, K]`` (``_as_rows``)."""
    del tile_group_ref, tiles_used_ref
    ws, rest = refs[:n_w], refs[n_w:]
    scales, rest = (rest[:n_w], rest[n_w:]) if scaled else ((), rest)
    o_ref, accs = rest[0], rest[1:]
    x = x_ref[...]
    ys = [jax.lax.dot_general(
        x, w[...].astype(x.dtype), (((1,), (1 if rows else 0,)), ((), ())),
        preferred_element_type=jnp.float32) for w in ws]

    def finish(ys):
        ys = [y * s[...] for y, s in zip(ys, scales)] or ys
        o_ref[...] = _activate(ys, act).astype(o_ref.dtype)

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


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "slab",
                                             "act"))
def grouped_matmul_pallas(x, ws, tile_group, tiles_used, scales=None, *,
                          tile: int, interpret: bool = False,
                          slab: int | None = None, act: str = ""):
    """``x`` [T*tile, K] in the tiled layout times ``ws[0]`` [G, K, N]
    (any dtype: a slab is cast to x's in VMEM, so int8 weights are read as
    int8), tile j with group ``tile_group[j]``'s matrix; with two weights
    the result is ``silu(x @ ws[0]) * (x @ ws[1])``, one pass over x and
    half the grid steps a weight byte; one weight under ``act`` ``relu2``
    gives ``relu(x @ ws[0])^2``. ``scales`` (one [G, N] float32 a
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
    ws, rows = _as_rows(ws)
    w_specs, scales = _weight_specs(g, n, tk, n_w, scales, rows)
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, scaled=bool(scales),
                          n_slabs=n_slabs, act=act, rows=rows),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles_used[0], n_slabs),
            in_specs=[pl.BlockSpec((tile, tk), lambda j, s, tg, used: (j, s))]
            + w_specs,
            out_specs=pl.BlockSpec((tile, n), lambda j, s, tg, used: (j, 0)),
            # a tile's float32 sums over its slabs, one a weight
            scratch_shapes=[pltpu.VMEM((tile, n), jnp.float32)]
            * (n_w if n_slabs > 1 else 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="grouped_matmul", interpret=interpret,
    )(tile_group, tiles_used, x, *ws, *scales)


def _ragged(x, ws, scales, lay: TiledLayout, act: str = ""):
    """The same result by ``jax.lax.ragged_dot`` over the padded groups
    (pad rows are zero rows of ``x``); rows past the last group are zero."""
    ys = [jax.lax.ragged_dot(x, w.astype(x.dtype), lay.padded_sizes)
          for w in ws]
    if scales is not None:
        rows = jnp.repeat(lay.tile_group, lay.tile)
        ys = [y.astype(jnp.float32) * s[rows] for y, s in zip(ys, scales)]
    if len(ws) == 2 or act:
        ys = [_activate([y.astype(jnp.float32) for y in ys], act)]
    live = jnp.arange(x.shape[0]) < jnp.sum(lay.padded_sizes)
    return jnp.where(live[:, None], ys[0].astype(x.dtype), 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, ws, scales, lay: TiledLayout, act: str = ""):
    """``x`` [T*tile, K] in the layout ``lay`` times the stacked matrices
    ``ws`` (a tuple of one [G, K, N], or of SwiGLU's gate and up, or of one
    under ``act`` ``relu2``; ``scales`` as ``grouped_matmul_pallas`` takes
    them): the Pallas kernel on a TPU (it lowers or raises), ``ragged_dot``
    elsewhere. Rows of tiles that hold no row are undefined.
    Differentiable in ``x`` and ``ws``."""
    if jax.default_backend() == "tpu":
        return grouped_matmul_pallas(x, ws, lay.tile_group, lay.tiles_used,
                                     scales, tile=lay.tile, act=act)
    return _ragged(x, ws, scales, lay, act)


def _fwd(x, ws, scales, lay, act):
    return grouped_matmul(x, ws, scales, lay, act), (x, ws, scales, lay)


def _bwd(act, res, dy):
    x, ws, scales, lay = res
    _y, vjp = jax.vjp(lambda x, ws: _ragged(x, ws, scales, lay, act), x, ws)
    dx, dws = vjp(dy)
    return dx, dws, None, None


grouped_matmul.defvjp(_fwd, _bwd)


# --- a decode step's rows: taken from the tokens by table, summed back ---

# what the tokens' two blocks [n, d] and their float32 result's two may
# take of VMEM beside the slabs (``rows_by_table``)
_TOKENS_BYTES = 8 * 2**20


class RowTables(NamedTuple):
    """Where the rows of the group-sorted (token, group) choices lie in
    whole tiles a group, by tile and not by row (``row_tables``): what
    the two kernels below prefetch."""

    tile_group: jnp.ndarray    # [T] group of each tile
    tiles_run: jnp.ndarray     # [1] tiles the grid visits: max(used, 1)
    tile_start: jnp.ndarray    # [T] sorted row a tile's first row holds
    tile_count: jnp.ndarray    # [T] live rows of a tile
    padded_sizes: jnp.ndarray  # [G] rows a group, padded to whole tiles
    token_of: jnp.ndarray      # [M] token of each sorted row


def rows_by_table(n: int, d: int, itemsize: int, m: int, g: int) -> bool:
    """Whether ``n`` tokens of width ``d`` whose ``m`` choices fall on
    ``g`` groups take the table form (``expert_rows``) and not the tiled
    one: a few rows a group, ``row_tile(m, g) < 256`` (at 256 a tile is
    bound by the MXU and its rows are a prefill chunk's or the trainer's,
    thousands: copying each costs what it multiplies), and the tokens and
    their float32 result stay in VMEM for a whole call: two blocks of each,
    ``2 * n * d * (itemsize + 4)`` bytes, within ``_TOKENS_BYTES``, which
    with two slabs in flight (2 x 8 MiB at most), the gate/up call's
    float32 copy of the tokens (half the result's two blocks) and a tile's
    sums and rows (a tile under 256 rows: under 8 MiB at d = 7168) stays
    under ``_VMEM_LIMIT_BYTES``. In bf16 at d = 2048 that is 341 tokens:
    every decode step of the cells (65 or 129 rows: 1.6 to 5.6 MiB), and
    no 512-token prefill chunk (12 MiB and more) nor trainer batch."""
    return (row_tile(m, g) < 256
            and 2 * n * d * (itemsize + 4) <= _TOKENS_BYTES)


def in_kernel(n: int, d: int, itemsize: int, m: int, g: int) -> bool:
    """Whether the experts of such a call run ``expert_rows`` here: on a
    TPU, at the shapes ``rows_by_table`` takes."""
    return jax.default_backend() == "tpu" and rows_by_table(n, d, itemsize,
                                                            m, g)


def row_tables(sizes: jnp.ndarray, token_of: jnp.ndarray, tile: int,
               first_row=0) -> RowTables:
    """``tiled_layout``'s tiles without its rows: ``sizes`` [G] rows a
    group, ``token_of`` [M] the token of each choice sorted by group, of
    which these groups' rows start at sorted row ``first_row``. Tile j
    holds the ``tile_count[j]`` sorted rows from ``tile_start[j]`` on, all
    of group ``tile_group[j]``; T = M // tile + G tiles bound every
    routing, and a call without a row still visits tile 0, which then
    holds none. A group's numbers reach its tiles through the [T, G]
    one-hot, as in ``tiled_layout``."""
    g = sizes.shape[0]
    n_tiles = token_of.shape[0] // tile + g
    tiles = -(-sizes // tile)                                   # [G]
    tile_end = jnp.cumsum(tiles)
    at = jnp.arange(n_tiles)
    j = jnp.minimum(at, tile_end[-1] - 1)
    tile_group = jnp.sum(tile_end[None, :] <= j[:, None], axis=1)
    of_group = tile_group[:, None] == jnp.arange(g)[None, :]     # [T, G]

    def a_tile(table):       # table[tile_group], without a gather
        return jnp.sum(jnp.where(of_group, table[None, :], 0), axis=1)

    before = (at - a_tile(tile_end - tiles)) * tile   # of the group's rows
    i32 = jnp.int32
    return RowTables(
        tile_group.astype(i32), jnp.maximum(tile_end[-1:], 1).astype(i32),
        (first_row + a_tile(jnp.cumsum(sizes) - sizes) + before).astype(i32),
        jnp.clip(a_tile(sizes) - before, 0, tile).astype(i32),
        (tiles * tile).astype(i32), token_of.astype(i32))


def _gather_kernel(tile_group_ref, tiles_run_ref, start_ref, count_ref,
                   token_ref, x_ref, *refs, n_w, scaled, n_slabs, act="",
                   rows=False):
    """``_kernel`` on a tile whose rows it takes from the tokens itself:
    before a tile's first slab, row r of the tile is token
    ``token_of[start + r]``'s for r under the tile's count, copied from
    the tokens' float32 copy (made once a call), and a zero row past it."""
    rows_ref, rows32_ref, x32_ref = refs[-3:]
    j, s = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (s == 0))
    def _tokens():
        x32_ref[...] = x_ref[...].astype(jnp.float32)

    @pl.when(s == 0)
    def _rows():
        start, count = start_ref[j], count_ref[j]

        def copy(r, carry):
            rows32_ref[pl.ds(r, 1), :] = x32_ref[
                pl.ds(token_ref[start + r], 1), :]
            return carry

        jax.lax.fori_loop(0, count, copy, 0)
        tile, tk = rows_ref.shape[1:]
        live = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < count
        rows = jnp.where(live, rows32_ref[...], 0.0).astype(rows_ref.dtype)
        for i in range(n_slabs):
            rows_ref[i] = rows[:, i * tk:(i + 1) * tk]

    _kernel(tile_group_ref, tiles_run_ref, rows_ref.at[s], *refs[:-3],
            n_w=n_w, scaled=scaled, n_slabs=n_slabs, act=act, rows=rows)


def _scatter_kernel(tile_group_ref, tiles_run_ref, start_ref, count_ref,
                    token_ref, weight_ref, h_ref, *refs, scaled, n_slabs,
                    rows=False):
    """``_kernel`` on a tile of ``hidden`` whose products it hands to
    their tokens itself: after a tile's last slab the products, rounded
    once as the tiled form rounds them, are added row by live row, each
    times its sorted row's weight, into the tokens' float32 result, which is
    zeroed at the call's first step and stays in VMEM to its last. A pad
    row is never read."""
    o_ref, y_ref, y32_ref = refs[1 + scaled], refs[-2], refs[-1]
    j, s = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (s == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    _kernel(tile_group_ref, tiles_run_ref, h_ref, *refs[:1 + scaled], y_ref,
            *refs[2 + scaled:-2], n_w=1, scaled=scaled, n_slabs=n_slabs,
            rows=rows)

    @pl.when(s == n_slabs - 1)
    def _add():
        y32_ref[...] = y_ref[...].astype(jnp.float32)
        start = start_ref[j]

        def add(r, carry):
            o_ref[pl.ds(token_ref[start + r], 1), :] += (
                weight_ref[start + r] * y32_ref[pl.ds(r, 1), :])
            return carry

        jax.lax.fori_loop(0, count_ref[j], add, 0)


def _tables_call(kernel, tab: RowTables, prefetch, operands, in_specs,
                 out_shape, out_spec, scratch, n_slabs, interpret):
    """One of the two kernels over the tiles ``tab`` visits and the slabs
    of each, slabs innermost, ``prefetch`` in SMEM."""
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tab.tiles_run[0], n_slabs), in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="grouped_matmul", interpret=interpret,
    )(*prefetch, *operands)


def _weight_specs(g, n, tk, n_w, scales, rows=False):
    """The slabs' and the scales' blocks, chosen by a tile's group, and
    the scales as the kernels take them. ``rows``: the weights come as
    ``[G, N, K]`` (``_as_rows``), whole."""
    w_spec = (pl.BlockSpec((None, n, tk), lambda j, s, tg, *_: (tg[j], 0, s))
              if rows else
              pl.BlockSpec((None, tk, n), lambda j, s, tg, *_: (tg[j], s, 0)))
    s_spec = pl.BlockSpec((None, 1, n), lambda j, s, tg, *_: (tg[j], 0, 0))
    scales = [s.astype(jnp.float32).reshape(g, 1, n) for s in scales or ()]
    return [w_spec] * n_w + [s_spec] * len(scales), scales


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "slab",
                                             "act"))
def gather_matmul_pallas(x, ws, tab: RowTables, scales=None, *, tile: int,
                         interpret: bool = False, slab: int | None = None,
                         act: str = ""):
    """``grouped_matmul_pallas`` of the tokens ``x`` [n, K] themselves:
    the result [T*tile, N] in the tiled layout, tile j's rows the tokens
    ``tab`` names (zero rows past its count). ``x`` is one block, fetched
    once a call; no tiled copy of it exists outside the kernel."""
    n_tok, k = x.shape
    g, _k, n = ws[0].shape
    n_w = len(ws)
    tk = slab or _slab_plan(k, n, ws[0].dtype.itemsize, n_w)
    n_slabs = k // tk
    ws, rows = _as_rows(ws)
    w_specs, scales = _weight_specs(g, n, tk, n_w, scales, rows)
    prefetch = (tab.tile_group, tab.tiles_run, tab.tile_start,
                tab.tile_count, tab.token_of)
    f32 = jnp.float32
    return _tables_call(
        functools.partial(_gather_kernel, n_w=n_w, scaled=bool(scales),
                          n_slabs=n_slabs, act=act, rows=rows),
        tab, prefetch, (x, *ws, *scales),
        [pl.BlockSpec((n_tok, k), lambda j, s, *_: (0, 0))] + w_specs,
        jax.ShapeDtypeStruct((tab.tile_group.shape[0] * tile, n), x.dtype),
        pl.BlockSpec((tile, n), lambda j, s, *_: (j, 0)),
        [pltpu.VMEM((tile, n), f32)] * (n_w if n_slabs > 1 else 0)
        # a tile's rows a slab, the same in float32, the tokens in float32
        + [pltpu.VMEM((n_slabs, tile, tk), x.dtype),
           pltpu.VMEM((tile, k), f32), pltpu.VMEM((n_tok, k), f32)],
        n_slabs, interpret)


@functools.partial(jax.jit, static_argnames=("n_tokens", "tile", "interpret",
                                             "slab"))
def matmul_scatter_pallas(hidden, ws, tab: RowTables, weights, scales=None,
                          *, n_tokens: int, tile: int,
                          interpret: bool = False, slab: int | None = None):
    """``hidden`` [T*tile, K] in the tiled layout times ``ws[0]`` [G, K,
    N] as ``grouped_matmul_pallas`` multiplies it, and each live row of
    the rounded product, times its weight (``weights`` [M] float32, in
    the sorted rows' order), summed into its token's row: [n_tokens, N]
    float32, ONE block that is written once a call; zero for a token
    without a row, and for every token of a call without one."""
    _m, k = hidden.shape
    g, _k, n = ws[0].shape
    tk = slab or _slab_plan(k, n, ws[0].dtype.itemsize, 1)
    n_slabs = k // tk
    ws, rows = _as_rows(ws)
    w_specs, scales = _weight_specs(g, n, tk, 1, scales, rows)
    prefetch = (tab.tile_group, tab.tiles_run, tab.tile_start,
                tab.tile_count, tab.token_of, weights.astype(jnp.float32))
    f32 = jnp.float32
    return _tables_call(
        functools.partial(_scatter_kernel, scaled=bool(scales),
                          n_slabs=n_slabs, rows=rows),
        tab, prefetch, (hidden, *ws, *scales),
        [pl.BlockSpec((tile, tk), lambda j, s, *_: (j, s))] + w_specs,
        jax.ShapeDtypeStruct((n_tokens, n), f32),
        pl.BlockSpec((n_tokens, n), lambda j, s, *_: (0, 0)),
        [pltpu.VMEM((tile, n), f32)] * (n_slabs > 1)
        # a tile's products as rounded, and the same in float32
        + [pltpu.VMEM((tile, n), hidden.dtype), pltpu.VMEM((tile, n), f32)],
        n_slabs, interpret)


def _rows_plain(x, ws_in, ws_out, scales_in, scales_out, tab: RowTables,
                weights, tile: int, act: str = ""):
    """What the two kernels compute, in XLA's operations: the tokens
    gathered into the tiled layout, ``_ragged`` twice, each live row times
    its weight summed into its token. The gradient's form."""
    m = tab.token_of.shape[0]
    r = jnp.arange(tile)[None, :]
    live = (r < tab.tile_count[:, None]).reshape(-1)
    src = jnp.clip(tab.tile_start[:, None] + r, 0, m - 1).reshape(-1)
    token = tab.token_of[src]
    lay = TiledLayout(tab.tile_group, tab.tiles_run, tab.padded_sizes, src,
                      live, None)
    xs = jnp.where(live[:, None], x[token], 0)
    ys = _ragged(_ragged(xs, ws_in, scales_in, lay, act), ws_out,
                 scales_out, lay)
    w = weights.astype(jnp.float32)[src]
    ys = jnp.where(live[:, None], ys.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((x.shape[0], ys.shape[1]), jnp.float32).at[token].add(ys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def expert_rows(x, ws_in, ws_out, scales_in, scales_out, tab: RowTables,
                weights, tile: int, act: str = ""):
    """Each token's weighted sum over its choices' experts, [n, N] float32:
    ``x`` [n, K] through SwiGLU of ``ws_in`` (gate, up: [G, K, F]; or
    (up,) alone under ``act`` ``relu2``: ``relu(x up)^2``) and
    ``ws_out`` ((down,): [G, F, N]), ``scales_*`` as
    ``grouped_matmul_pallas`` takes them, the choices and their tiles in
    ``tab`` (``row_tables``), ``weights`` [M] float32 a sorted row. The two
    kernels (interpreted off a TPU: tests alone get there; the callers ask
    ``in_kernel``), between them only ``hidden`` in the tiled layout.
    Differentiable in ``x``, the weights' stacks and ``weights``: the
    cotangents are XLA's over ``_rows_plain``."""
    interpret = jax.default_backend() != "tpu"
    hidden = gather_matmul_pallas(x, ws_in, tab, scales_in, tile=tile,
                                  interpret=interpret, act=act)
    return matmul_scatter_pallas(hidden, ws_out, tab, weights, scales_out,
                                 n_tokens=x.shape[0], tile=tile,
                                 interpret=interpret)


def _rows_fwd(x, ws_in, ws_out, scales_in, scales_out, tab, weights, tile,
              act):
    return (expert_rows(x, ws_in, ws_out, scales_in, scales_out, tab, weights,
                        tile, act),
            (x, ws_in, ws_out, scales_in, scales_out, tab, weights))


def _rows_bwd(tile, act, res, dy):
    x, ws_in, ws_out, scales_in, scales_out, tab, weights = res
    _y, vjp = jax.vjp(
        lambda x, ws_in, ws_out, weights: _rows_plain(
            x, ws_in, ws_out, scales_in, scales_out, tab, weights, tile, act),
        x, ws_in, ws_out, weights)
    dx, dws_in, dws_out, dweights = vjp(dy)
    return dx, dws_in, dws_out, None, None, None, dweights


expert_rows.defvjp(_rows_fwd, _rows_bwd)
