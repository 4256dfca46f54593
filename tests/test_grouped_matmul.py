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


@pytest.mark.parametrize("n", [256, 1024])     # one weight block, four
def test_kernel_matches_ragged_dot_and_a_loop(n, monkeypatch):
    monkeypatch.setattr(gm, "_MAX_WEIGHT_BLOCK_BYTES", 32 * 256 * 4)
    sizes, lay, rows, x, w = _case([3, 0, 17, 4, 0, 9], 40, 8, n=n)
    got = gm.grouped_matmul_pallas(x, (w,), lay.tile_group, lay.tiles_used,
                                   tile=lay.tile, interpret=True)
    used = int(lay.tiles_used[0]) * lay.tile       # the rest is undefined
    np.testing.assert_allclose(got[:used], gm._ragged(x, (w,), None, lay)[:used],
                               rtol=1e-5, atol=1e-5)
    start = 0
    for g, s in enumerate(sizes.tolist()):
        at = start + int(lay.shift[g])
        np.testing.assert_allclose(got[at:at + s],
                                   rows[start:start + s] @ w[g],
                                   rtol=1e-5, atol=1e-5)
        start += s
    assert np.all(np.asarray(got)[:used][~np.asarray(lay.live)[:used]] == 0.0)


def test_kernel_reads_int8_groups_and_scales_each_by_its_own():
    sizes, lay, _rows, x, w = _case([5, 0, 12], 24, 8, dtype=jnp.float32)
    q = jnp.clip(jnp.round(w * 20), -127, 127).astype(jnp.int8)
    scale = jax.random.uniform(jax.random.PRNGKey(3), (3, w.shape[-1])) + 0.5
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
