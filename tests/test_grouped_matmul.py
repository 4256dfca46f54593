"""The owned grouped-matmul kernel (``ops/grouped_matmul.py``) in interpret
mode against ``jax.lax.ragged_dot`` over the same tiled layout, and the
layout itself against a loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.ops import grouped_matmul as gm


def _case(sizes, n_rows, tile, k=32, n=256, seed=0, dtype=jnp.float32):
    sizes = jnp.asarray(sizes, jnp.int32)
    lay = gm.tiled_layout(sizes, n_rows, tile)
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    rows = jax.random.normal(kx, (n_rows, k), dtype)
    x = jnp.where(lay.live[:, None],
                  rows[jnp.clip(lay.src, 0, n_rows - 1)], 0)
    w = jax.random.normal(kw, (sizes.shape[0], k, n), dtype)
    return sizes, lay, rows, x, w


def _int8(w, seed=3):
    q = jnp.clip(jnp.round(w * 20), -127, 127).astype(jnp.int8)
    scale = jax.random.uniform(jax.random.PRNGKey(seed),
                               (w.shape[0], w.shape[-1])) + 0.5
    return q, scale


@pytest.mark.parametrize("sizes,n_rows", [
    ([3, 0, 17, 4], 24),          # a group over one tile, an empty group
    ([0, 0, 0, 40], 40),          # every row on the last group
    ([40, 0, 0, 0], 48),          # rows past the sum (invalid choices)
    ([0, 0, 0, 0], 16),           # no row at all
])
def test_tiled_layout_puts_each_group_in_whole_tiles(sizes, n_rows):
    tile = 8
    sizes_a, lay, *_ = _case(sizes, n_rows, tile)
    n_tiles = n_rows // tile + len(sizes)
    assert lay.tile == tile and lay.tile_group.shape == (n_tiles,)
    want_src, want_group = [], []
    start = 0
    for g, s in enumerate(sizes):
        tiles = -(-s // tile)
        want_group += [g] * tiles
        want_src += list(range(start, start + s)) + [-1] * (tiles * tile - s)
        start += s
    used = len(want_group)
    assert int(lay.tiles_used[0]) == used
    assert lay.tile_group[:used].tolist() == want_group
    # unused tiles repeat the last used group: nothing new to fetch
    assert set(lay.tile_group[used:].tolist()) <= {want_group[-1] if used
                                                   else 0}
    live = np.asarray(lay.live)
    assert live.sum() == sum(sizes)
    assert np.asarray(lay.src)[live].tolist() == [r for r in want_src
                                                  if r >= 0]
    assert (~live[:len(want_src)]).tolist() == [r < 0 for r in want_src]
    # sorted row r of group g sits at r + shift[g]
    start = 0
    for g, s in enumerate(sizes):
        for r in range(start, start + s):
            assert int(lay.src[r + int(lay.shift[g])]) == r
        start += s
    assert lay.padded_sizes.tolist() == [-(-s // tile) * tile for s in sizes]


@pytest.mark.parametrize("k,slab", [(32, None), (512, 128)],
                         ids=["one slab", "four slabs"])
def test_kernel_matches_ragged_dot_and_a_loop(k, slab):
    sizes, lay, rows, x, w = _case([3, 0, 17, 4, 0, 9], 40, 8, k=k)
    tol = 1e-5 * (k / 32)     # float32 sums of k products, in another order
    got = gm.grouped_matmul_pallas(x, (w,), lay.tile_group, lay.tiles_used,
                                   tile=lay.tile, interpret=True, slab=slab)
    used = int(lay.tiles_used[0]) * lay.tile       # the rest is undefined
    np.testing.assert_allclose(got[:used], gm._ragged(x, (w,), None, lay)[:used],
                               rtol=1e-5, atol=tol)
    start = 0
    for g, s in enumerate(sizes.tolist()):
        at = start + int(lay.shift[g])
        np.testing.assert_allclose(got[at:at + s],
                                   rows[start:start + s] @ w[g],
                                   rtol=1e-5, atol=tol)
        start += s
    assert np.all(np.asarray(got)[:used][~np.asarray(lay.live)[:used]] == 0.0)


# sizes, rows, tile, K, weights, int8: each through slabs of 128 rows
SLAB_CASES = {
    "one weight": ([3, 0, 17, 4, 0, 9], 40, 8, 512, 1, False),
    "gate and up": ([3, 0, 17, 4, 0, 9], 40, 8, 512, 2, False),
    "int8 with scales": ([5, 0, 12], 24, 8, 256, 1, True),
    "int8 gate and up": ([5, 0, 12], 24, 8, 256, 2, True),
    "a group of three tiles": ([0, 20, 0, 0, 2], 24, 8, 384, 2, False),
    "empty groups between full ones": ([8, 0, 0, 8, 0, 8], 24, 8, 256, 1,
                                       False),
    "K of 7168": ([2, 0, 9], 16, 8, 7168, 2, False),
}


@pytest.mark.parametrize("case", SLAB_CASES)
def test_slabs_along_k_sum_to_the_whole_product(case):
    sizes, n_rows, tile, k, n_w, int8 = SLAB_CASES[case]
    sizes, lay, rows, x, w = _case(sizes, n_rows, tile, k=k, n=128)
    ws, scales = (w, jnp.flip(w, axis=1))[:n_w], None
    if int8:
        ws, scales = zip(*(_int8(w, seed) for seed, w in enumerate(ws)))
    got = np.asarray(gm.grouped_matmul_pallas(
        x, ws, lay.tile_group, lay.tiles_used, scales, tile=tile,
        interpret=True, slab=128))
    used = int(lay.tiles_used[0]) * tile
    # float32 sums of k products in another order, then SwiGLU's product
    tol = dict(rtol=1e-4, atol=1e-3 if int8 or n_w == 2 else 1e-4)
    np.testing.assert_allclose(got[:used],
                               gm._ragged(x, ws, scales, lay)[:used], **tol)
    # the plain loop, group by group
    start = 0
    for g, s in enumerate(sizes.tolist()):
        at = start + int(lay.shift[g])
        ys = [rows[start:start + s] @ w[g].astype(jnp.float32)
              * (1.0 if scales is None else scales[i][g])
              for i, w in enumerate(ws)]
        want = ys[0] if n_w == 1 else jax.nn.silu(ys[0]) * ys[1]
        np.testing.assert_allclose(got[at:at + s], want, **tol)
        start += s
    # tiles past tiles_used are not visited (interpret mode leaves NaN)
    assert got[used:].size and np.all(np.isnan(got[used:]))


def test_kernel_reads_int8_groups_and_scales_each_by_its_own():
    sizes, lay, _rows, x, w = _case([5, 0, 12], 24, 8, dtype=jnp.float32)
    q, scale = _int8(w)
    used = int(lay.tiles_used[0]) * lay.tile
    got = gm.grouped_matmul_pallas(x, (q,), lay.tile_group, lay.tiles_used,
                                   (scale,), tile=lay.tile, interpret=True)
    np.testing.assert_allclose(got[:used],
                               gm._ragged(x, (q,), (scale,), lay)[:used],
                               rtol=1e-5, atol=1e-4)
    # SwiGLU of two int8 weights, each with its own scale
    got = gm.grouped_matmul_pallas(x, (q, q[::-1]), lay.tile_group,
                                   lay.tiles_used, (scale, scale + 1),
                                   tile=lay.tile, interpret=True)
    np.testing.assert_allclose(
        got[:used],
        gm._ragged(x, (q, q[::-1]), (scale, scale + 1), lay)[:used],
        rtol=1e-5, atol=1e-3)


def test_two_weights_are_swiglu():
    sizes, lay, rows, x, w = _case([3, 0, 17, 4], 24, 8)
    up = jnp.flip(w, axis=1)
    used = int(lay.tiles_used[0]) * lay.tile
    got = gm.grouped_matmul_pallas(x, (w, up), lay.tile_group,
                                   lay.tiles_used, tile=lay.tile,
                                   interpret=True)
    np.testing.assert_allclose(got[:used],
                               gm._ragged(x, (w, up), None, lay)[:used],
                               rtol=1e-5, atol=1e-5)
    at = int(lay.shift[2]) + 3
    np.testing.assert_allclose(
        got[at:at + 17],
        jax.nn.silu(rows[3:20] @ w[2]) * (rows[3:20] @ up[2]),
        rtol=1e-5, atol=1e-5)


def test_gradient_is_the_ragged_dots():
    _sizes, lay, _rows, x, w = _case([3, 0, 17, 4], 24, 8)
    f = lambda fn: jax.grad(  # noqa: E731
        lambda x, w: jnp.sum(jnp.sin(fn(x, w))), argnums=(0, 1))(x, w)
    got = f(lambda x, w: gm.grouped_matmul(x, (w, w * 2), None, lay))
    want = f(lambda x, w: gm._ragged(x, (w, w * 2), None, lay))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert np.all(np.asarray(got[1][1]) == 0.0)     # the empty group


@pytest.mark.parametrize("rows,groups,tile", [
    (512, 128, 16), (4096, 128, 64), (131072, 128, 256), (24, 4, 16)])
def test_row_tile_follows_the_mean_rows_a_group(rows, groups, tile):
    assert gm.row_tile(rows, groups) == tile


# (K, N, weights, rows a tile) of the two calls a layer of the four MoE
# cells' decode steps, and the rows a slab each gets
CELL_SLABS = {
    "qwen3-30b-a3b gate+up": ((2048, 768, 2, 16), 2048),
    "qwen3-30b-a3b down": ((768, 2048, 1, 16), 768),
    "ling-3.0-flash gate+up": ((2560, 768, 2, 32), 2560),
    "ling-3.0-flash down": ((768, 2560, 1, 32), 768),
    "zaya1-8b gate+up": ((2048, 2048, 2, 32), 512),
    "zaya1-8b down": ((2048, 2048, 1, 32), 2048),
    "dots.vlm1 gate+up": ((7168, 2048, 2, 128), 512),
    "dots.vlm1 down": ((2048, 7168, 1, 128), 256),
}


@pytest.mark.parametrize("call", CELL_SLABS)
def test_slab_plan_at_the_cells_shapes(call):
    (k, n, n_w, tile), want = CELL_SLABS[call]
    tk = gm._slab_plan(k, n, 2, n_w)
    assert tk == want
    # whole rows, so a slab [tk, n] is one contiguous run of a [k, n]
    # matrix, cut where the (8, 128) tiles of both operands allow
    assert k % tk == 0 and (tk == k or tk % 128 == 0)
    slabs = n_w * tk * n * 2
    if tk == k:
        assert slabs <= gm._WHOLE_BYTES
    else:
        assert gm._SLAB_BYTES // 2 < slabs <= gm._SLAB_BYTES
    # what the call keeps in VMEM, in the cell's tiles and in a prefill
    # chunk's or the trainer's 256 rows: two slabs in flight, the float32
    # sums and a slab's products, two blocks each of the rows and the output
    for rows in (tile, 256):
        sums = n_w * rows * n * 4
        need = (2 * slabs + (sums if tk < k else 0) + sums
                + 2 * rows * tk * 2 + 2 * rows * n * 2)
        assert need <= gm._VMEM_LIMIT_BYTES - 8 * 2**20


def test_slab_plan_counts_bytes_and_keeps_an_odd_k_whole():
    assert gm._slab_plan(7168, 2048, 1, 2) == 1024     # the same 4 MiB
    assert gm._slab_plan(7168 + 64, 2048, 2, 2) == 7168 + 64
