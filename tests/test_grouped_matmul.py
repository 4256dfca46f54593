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


# --- the rows taken by table (``expert_rows``) ---

def _routed(n, k, routed, held, first=0, seed=0, valid=None, all_on=None):
    """A step's routing over ``routed`` experts of which ``held`` from
    ``first`` on are here: (token of each sorted row, weight of each,
    rows of every expert, the choices [n, k], their weights [n, k])."""
    rng = np.random.default_rng(seed)
    choice = np.stack([rng.choice(routed, k, replace=False)
                       for _ in range(n)])
    if all_on is not None:
        choice[:] = all_on
    top_p = rng.random((n, k)).astype(np.float32) + 0.1
    if valid is not None:                  # an invalid row chooses nothing
        choice[~valid] = routed
    flat = choice.reshape(-1)
    order = np.argsort(flat, kind="stable")
    sizes = np.bincount(flat, minlength=routed + 1)[:routed]
    return (jnp.asarray(order // k, jnp.int32),
            jnp.asarray(top_p.reshape(-1)[order]),
            jnp.asarray(sizes, jnp.int32), choice, top_p)


def _weights(d, f, held, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [0.1 * jax.random.normal(key, (held, *shape), dtype)
            for key, shape in zip(keys, [(d, f), (d, f), (f, d)])]


def _plain(x, ws, choice, top_p, first, scales=None):
    """The plain float32 form: token by token, choice by choice."""
    held = ws[0].shape[0]
    gate, up, down = (np.asarray(w, np.float32) * (
        1.0 if scales is None else np.asarray(s)[:, None, :])
        for w, s in zip(ws, scales or (None,) * 3))
    x = np.asarray(x, np.float32)
    out = np.zeros((x.shape[0], down.shape[-1]), np.float32)
    for t, (experts, weights) in enumerate(zip(choice, top_p)):
        for e, w in zip(experts - first, weights):
            if 0 <= e < held:
                h = np.asarray(jax.nn.silu(x[t] @ gate[e])) * (x[t] @ up[e])
                out[t] += w * (h @ down[e])
    return out


def _by_kernels(x, ws, token_of, weight, sizes, first, scales=None,
                slabs=(None, None), spoil=None):
    """The two kernels, interpreted, over the experts ``ws`` holds."""
    held = ws[0].shape[0]
    tile = gm.row_tile(token_of.shape[0], held)
    first_row = int(jnp.sum(sizes[:first]))
    tab = gm.row_tables(sizes[first:first + held], token_of, tile, first_row)
    hidden = gm.gather_matmul_pallas(
        x, tuple(ws[:2]), tab, scales and tuple(scales[:2]), tile=tile,
        interpret=True, slab=slabs[0])
    if spoil is not None:
        hidden = spoil(hidden, tab, tile)
    return gm.matmul_scatter_pallas(
        hidden, (ws[2],), tab, weight, scales and (scales[2],),
        n_tokens=x.shape[0], tile=tile, interpret=True, slab=slabs[1])


# (tokens, d, f, choices a token, experts routed over, held, the first
# held, slabs of gate+up and of down): the five cells' decode steps in
# small, and what a routing can do to them
ROWS_CASES = {
    "qwen3-30b-a3b: all 16 held": (9, 256, 128, 4, 16, 16, 0, (None, None)),
    "ling-3.0-flash: 8 of 32": (17, 256, 128, 4, 32, 8, 0, (None, None)),
    "dots.vlm1: 2 of 32, slabs": (9, 512, 256, 4, 32, 2, 0, (128, 128)),
    "zaya1-8b: one choice, slabs": (17, 256, 256, 1, 4, 4, 0, (128, None)),
    "laguna-xs.2: 4 of 32": (9, 256, 128, 4, 32, 4, 0, (None, None)),
    "a share that starts at expert 8": (17, 128, 128, 4, 16, 4, 8,
                                        (None, 128)),
}


@pytest.mark.parametrize("case", ROWS_CASES)
def test_rows_by_table_are_the_plain_float32_form(case):
    n, d, f, k, routed, held, first, slabs = ROWS_CASES[case]
    token_of, weight, sizes, choice, top_p = _routed(n, k, routed, held,
                                                     first)
    x = jax.random.normal(jax.random.PRNGKey(7), (n, d))
    ws = _weights(d, f, held)
    got = _by_kernels(x, ws, token_of, weight, sizes, first, slabs=slabs)
    want = _plain(x, ws, choice, top_p, first)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    # a token with no choice here is exactly zero
    none_here = ~((choice >= first) & (choice < first + held)).any(axis=1)
    assert np.all(np.asarray(got)[none_here] == 0.0)


ROUTINGS = {
    # every row on one expert: a group of several tiles
    "all rows on one expert": dict(all_on=2),
    # no row on any held expert: zeros, not an unwritten block
    "no row on a held expert": dict(all_on=9),
    # rows without a request choose nothing
    "invalid rows": dict(valid=np.arange(17) % 3 != 1),
}


@pytest.mark.parametrize("routing", ROUTINGS)
def test_rows_by_table_whatever_the_routing(routing):
    n, d, f, k, routed, held = 17, 128, 128, 1, 12, 4
    token_of, weight, sizes, choice, top_p = _routed(
        n, k, routed, held, **ROUTINGS[routing])
    x = jax.random.normal(jax.random.PRNGKey(8), (n, d))
    ws = _weights(d, f, held)
    got = np.asarray(_by_kernels(x, ws, token_of, weight, sizes, 0))
    np.testing.assert_allclose(got, _plain(x, ws, choice, top_p, 0),
                               rtol=1e-4, atol=2e-5)
    if routing == "no row on a held expert":
        assert np.all(got == 0.0)
    if routing == "invalid rows":
        assert np.all(got[~ROUTINGS[routing]["valid"]] == 0.0)
    if routing == "all rows on one expert":
        tile = gm.row_tile(n * k, held)
        assert n > tile             # more than one tile of the one group


def test_pad_rows_of_hidden_may_hold_anything():
    """What the down kernel never reads: a used tile's rows past its
    count, and every tile past the last used (NaN under ``interpret``)."""
    n, d, f, k, routed, held = 17, 128, 128, 2, 8, 8
    token_of, weight, sizes, choice, top_p = _routed(n, k, routed, held)
    x = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    ws = _weights(d, f, held)

    def spoil(hidden, tab, tile):
        rows = hidden.reshape(-1, tile, f)
        pad = jnp.arange(tile)[None, :] >= tab.tile_count[:, None]
        assert bool(pad.any()) and bool(jnp.all(
            jnp.where(pad[:int(tab.tiles_run[0]), :, None],
                      rows[:int(tab.tiles_run[0])], 0) == 0))   # zero rows
        return jnp.where(pad[:, :, None], jnp.nan, rows).reshape(-1, f)

    got = _by_kernels(x, ws, token_of, weight, sizes, 0, spoil=spoil)
    np.testing.assert_allclose(got, _plain(x, ws, choice, top_p, 0),
                               rtol=1e-4, atol=2e-5)


def test_rows_by_table_read_int8_experts_with_their_scales():
    n, d, f, k, routed, held = 9, 256, 128, 2, 8, 4
    token_of, weight, sizes, choice, top_p = _routed(n, k, routed, held)
    x = jax.random.normal(jax.random.PRNGKey(10), (n, d))
    qs, scales = zip(*(_int8(w, seed) for seed, w in
                       enumerate(_weights(d, f, held))))
    scales = [s * 0.05 for s in scales]
    got = _by_kernels(x, qs, token_of, weight, sizes, 0, scales,
                      slabs=(128, None))
    want = _plain(x, qs, choice, top_p, 0, scales)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


def test_expert_rows_gradient_is_the_tiled_paths():
    from polyrl_tpu.models import blocks

    n, d, f, k, held = 9, 64, 32, 2, 4
    token_of, weight, sizes, choice, top_p = _routed(n, k, held, held)
    x = jax.random.normal(jax.random.PRNGKey(11), (n, d))
    experts = dict(zip(blocks.EXPERT_KEYS, _weights(d, f, held)))
    flat = choice.reshape(-1)
    place = jnp.argsort(jnp.argsort(jnp.asarray(flat), stable=True))

    def rows(x, experts, top_p):
        w = top_p.reshape(-1)[jnp.argsort(jnp.asarray(flat), stable=True)]
        return blocks._expert_rows(x, experts, None, token_of, w, sizes)

    def tiled(x, experts, top_p):
        return blocks._expert_mix(x, experts, None, token_of, place,
                                  jnp.asarray(flat), top_p, sizes)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2))(x, experts, jnp.asarray(top_p))

    np.testing.assert_allclose(rows(x, experts, jnp.asarray(top_p)),
                               tiled(x, experts, jnp.asarray(top_p)),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads(rows)),
                    jax.tree_util.tree_leaves(grads(tiled))):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sizes,n_rows,first_row", [
    ([3, 0, 17, 4], 24, 0), ([0, 0, 0, 40], 40, 5), ([40, 0, 0, 0], 48, 0),
    ([0, 0, 0, 0], 16, 7)])
def test_row_tables_are_the_tiled_layouts_tiles(sizes, n_rows, first_row):
    tile = 8
    lay = gm.tiled_layout(jnp.asarray(sizes, jnp.int32), n_rows, tile)
    tab = gm.row_tables(jnp.asarray(sizes, jnp.int32),
                        jnp.arange(n_rows) // 2, tile, first_row)
    used = int(lay.tiles_used[0])
    assert int(tab.tiles_run[0]) == max(used, 1)
    assert tab.tile_group.tolist() == lay.tile_group.tolist()
    assert tab.padded_sizes.tolist() == lay.padded_sizes.tolist()
    live = np.asarray(lay.live).reshape(-1, tile)
    src = np.asarray(lay.src).reshape(-1, tile)
    assert tab.tile_count[:used].tolist() == live[:used].sum(1).tolist()
    assert int(tab.tile_count[used:].sum()) == 0    # tile 0 of a call without
    assert ((tab.tile_start[:used] - first_row).tolist()
            == src[:used, 0].tolist())
    assert tab.token_of.tolist() == (np.arange(n_rows) // 2).tolist()


# (tokens, d, choices a token, experts held) -> the form: the five cells'
# decode steps take their rows by table; a 512-token prefill chunk and the
# trainer's batch keep the tiled form
FORMS = {
    "qwen3-30b-a3b decode": ((65, 2048, 8, 128), True),
    "ling-3.0-flash decode": ((129, 2560, 8, 128), True),
    "dots.vlm1 decode": ((65, 7168, 8, 16), True),
    "zaya1-8b decode": ((129, 2048, 1, 16), True),
    "laguna-xs.2 decode": ((65, 2048, 8, 32), True),
    "qwen3-30b-a3b prefill chunk": ((512, 2048, 8, 128), False),
    "ling-3.0-flash prefill chunk": ((512, 2560, 8, 128), False),
    "zaya1-8b prefill chunk": ((512, 2048, 1, 16), False),
    "dots.vlm1 prefill chunk": ((512, 7168, 8, 16), False),
    "a trainer batch": ((16384, 2048, 8, 128), False),
}
# expert widths of the cells' configurations
CELL_INTER = {2048: 768, 2560: 768, 7168: 2048}


@pytest.mark.parametrize("call", FORMS)
def test_the_form_follows_the_shapes(call, monkeypatch):
    (n, d, k, g), by_table = FORMS[call]
    assert gm.rows_by_table(n, d, 2, n * k, g) is by_table
    assert not gm.in_kernel(n, d, 2, n * k, g)      # no TPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.in_kernel(n, d, 2, n * k, g) is by_table
    if not by_table:
        return
    # what the two calls keep in VMEM (``rows_by_table``'s arithmetic)
    tile, f = gm.row_tile(n * k, g), CELL_INTER[d]
    for (kk, nn, n_w) in ((d, f, 2), (f, d, 1)):
        tk = gm._slab_plan(kk, nn, 2, n_w)
        sums = n_w * tile * nn * 4
        need = 2 * n_w * tk * nn * 2 + (sums if tk < kk else 0) + sums
        if n_w == 2:    # the tokens twice, their float32 copy, a tile's rows
            need += 2 * n * d * 2 + n * d * 4 + tile * d * 6 + 2 * tile * f * 2
        else:           # hidden's block twice, the result twice, the products
            need += 2 * tile * tk * 2 + 2 * n * d * 4 + tile * d * 6
        assert need <= gm._VMEM_LIMIT_BYTES - 8 * 2**20


# --- an expert of two matrices: ``relu(x up)^2 down`` (``act="relu2"``) ---

def _plain_relu2(x, up, down, choice, top_p, first):
    """The plain float32 form of the ungated expert, token by token."""
    held = up.shape[0]
    x, up, down = (np.asarray(a, np.float32) for a in (x, up, down))
    out = np.zeros((x.shape[0], down.shape[-1]), np.float32)
    for t, (experts, weights) in enumerate(zip(choice, top_p)):
        for e, w in zip(experts - first, weights):
            if 0 <= e < held:
                out[t] += w * (np.maximum(x[t] @ up[e], 0.0) ** 2 @ down[e])
    return out


@pytest.mark.parametrize("k,n,slab", [(32, 256, None), (512, 256, 128),
                                      (384, 232, None)],
                         ids=["one slab", "four slabs",
                              "an odd width, read as rows"])
def test_one_weight_under_relu2_is_the_squared_relu(k, n, slab):
    """Width 232 is 1.8 lane tiles, as 1856 is 14.5."""
    sizes, lay, rows, x, w = _case([3, 0, 17, 4], 24, 8, k=k, n=n)
    used = int(lay.tiles_used[0]) * lay.tile
    got = gm.grouped_matmul_pallas(x, (w,), lay.tile_group, lay.tiles_used,
                                   tile=lay.tile, interpret=True, slab=slab,
                                   act="relu2")
    tol = 1e-5 * k
    np.testing.assert_allclose(
        got[:used], gm._ragged(x, (w,), None, lay, "relu2")[:used],
        rtol=1e-5, atol=tol)
    at = int(lay.shift[2]) + 3
    np.testing.assert_allclose(
        got[at:at + 17], jnp.maximum(rows[3:20] @ w[2], 0.0) ** 2,
        rtol=1e-5, atol=tol)
    with pytest.raises(ValueError, match="gelu"):
        gm._ragged(x, (w,), None, lay, "gelu")


RELU2_CASES = {
    # (tokens, d, f, choices, routed, held, first, slabs of up and of down)
    "nemotron: 4 of 32 at an odd width": (9, 256, 232, 3, 32, 4, 0,
                                          (None, None)),
    "a share that starts at expert 4": (17, 384, 232, 3, 16, 4, 4,
                                        (None, None)),
}


@pytest.mark.parametrize("case", RELU2_CASES)
def test_rows_by_table_under_relu2_are_the_plain_float32_form(case):
    n, d, f, k, routed, held, first, slabs = RELU2_CASES[case]
    token_of, weight, sizes, choice, top_p = _routed(n, k, routed, held,
                                                     first)
    x = jax.random.normal(jax.random.PRNGKey(7), (n, d))
    _gate, up, down = _weights(d, f, held)
    tile = gm.row_tile(token_of.shape[0], held)
    tab = gm.row_tables(sizes[first:first + held], token_of, tile,
                        int(jnp.sum(sizes[:first])))
    hidden = gm.gather_matmul_pallas(x, (up,), tab, None, tile=tile,
                                     interpret=True, slab=slabs[0],
                                     act="relu2")
    got = gm.matmul_scatter_pallas(hidden, (down,), tab, weight, None,
                                   n_tokens=n, tile=tile, interpret=True,
                                   slab=slabs[1])
    want = _plain_relu2(x, up, down, choice, top_p, first)
    # sums of 232 squares reach 20: float32's ulps of that
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-5)
    # the dispatcher's own form off a TPU, and the gradient's
    both = gm.expert_rows(x, (up,), (down,), None, None, tab, weight, tile,
                          "relu2")
    np.testing.assert_allclose(both, want, rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(
        gm._rows_plain(x, (up,), (down,), None, None, tab, weight, tile,
                       "relu2"), want, rtol=1e-5, atol=5e-5)


def test_relu2_gradients_of_both_forms_are_jax_grads_of_the_plain_form():
    """``jax.grad`` of ``_expert_rows`` (the kernels' custom VJP) and of
    ``_expert_mix`` (``grouped_matmul``'s) for an expert of two matrices at
    an odd width, against ``jax.grad`` of the plain form written out."""
    from polyrl_tpu.models import blocks

    n, d, f, k, held = 9, 64, 29, 2, 4
    token_of, weight, sizes, choice, top_p = _routed(n, k, held, held)
    x = jax.random.normal(jax.random.PRNGKey(11), (n, d))
    _gate, up, down = _weights(d, f, held)
    experts = {"we_up": up, "we_down": down}
    flat = jnp.asarray(choice.reshape(-1))
    order = jnp.argsort(flat, stable=True)
    place = jnp.argsort(order)

    def rows(x, experts, top_p):
        return blocks._expert_rows(x, experts, None, token_of,
                                   top_p.reshape(-1)[order], sizes)

    def tiled(x, experts, top_p):
        return blocks._expert_mix(x, experts, None, token_of, place, flat,
                                  top_p, sizes)

    def plain(x, experts, top_p):
        hidden = jnp.maximum(jnp.einsum(
            "nd,nkdf->nkf", x, experts["we_up"][jnp.asarray(choice)]),
            0.0) ** 2
        return jnp.einsum("nkf,nkfd,nk->nd", hidden,
                          experts["we_down"][jnp.asarray(choice)], top_p)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2))(x, experts, jnp.asarray(top_p))

    want = grads(plain)
    for fn in (rows, tiled):
        np.testing.assert_allclose(fn(x, experts, jnp.asarray(top_p)),
                                   plain(x, experts, jnp.asarray(top_p)),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(grads(fn)),
                        jax.tree_util.tree_leaves(want)):
            assert np.abs(np.asarray(b)).max() > 0
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_slab_plan_at_nemotrons_widths():
    """2688 -> 1856: 9.98 MB a matrix passes the one-slab rule, but 1856
    columns are 14.5 lane tiles: the chip holds the matrix as its transpose
    and it is read as ``[1856, 2688]`` rows, whole (``_as_rows``); 1856 ->
    2688: 1856 rows cannot be cut at a lane tile either, so that matrix
    goes in whole too, two of them in flight under the kernels' VMEM
    limit."""
    assert gm._slab_plan(2688, 1856, 2, 1) == 2688
    assert gm._slab_plan(1856, 2688, 2, 1) == 1856
    w = jnp.zeros((3, 2688, 1856), jnp.bfloat16)
    assert gm._as_rows((w,))[1] and gm._as_rows((w,))[0][0].shape == (
        3, 1856, 2688)
    assert not gm._as_rows((jnp.swapaxes(w, 1, 2),))[1]
    tile = gm.row_tile(64 * 6, 16)
    assert tile == 64
    whole = 1856 * 2688 * 2
    need = (2 * whole + tile * 2688 * 4 + 2 * tile * 1856 * 2
            + 2 * 65 * 2688 * 4 + tile * 2688 * (2 + 4))
    assert need <= gm._VMEM_LIMIT_BYTES - 8 * 2**20
