"""The kernel that chooses a sparse layer's blocks for a decode step
(``ops/sparse_select.py``), interpreted, against ``mixers/sparse.py``'s jnp
form of ``selected_table`` (``block_scores`` and ``choose`` over the rows'
pooled keys gathered at the table's full width): the blocks' scores to
1e-5, the chosen pages' table, the keys it holds and the count EXACTLY. The
pool's pages are shuffled, so a kernel that reads page ``i`` for table
entry ``i`` fails, and the null page holds NaN, so one that scores what a
row does not own fails too. The cases are one test, so each counts."""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.models.mixers import sparse
from polyrl_tpu.ops import sparse_select

# the published sizes (pooled keys of 32 tokens every 16, blocks of 64, 2
# K/V heads of 128) with a short table: 4 of a row's blocks, the first and
# the last 2 forced, every block up to 256 keys
SMALL = dict(sparse_topk=4, sparse_window_size=128, sparse_dense_len=256)
BLOCK = 64


def _cfg(heads=16, **sizes):
    return decoder.get_config("minicpm-sala", num_heads=heads,
                              **{**SMALL, **sizes})


def _rows(cfg, lens, width, dtype, seed, live=None, q=None):
    """Queries, a pooled store whose null page is NaN, and the rows' page
    table over a shuffled pool."""
    rng = np.random.default_rng(seed)
    s = len(lens)
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    pages = [-(-n // BLOCK) for n in lens]
    n_pool = 1 + sum(pages) + 3
    if q is None:
        q = rng.normal(size=(s, h, d)) * 1.5
    store = rng.normal(size=(n_pool, 4 * hkv, d)).astype(np.float32)
    store[0] = np.nan
    shuffled = rng.permutation(np.arange(1, n_pool))
    table, at = np.zeros((s, width), np.int32), 0
    for i, m in enumerate(pages):
        table[i, :m] = shuffled[at:at + m]
        at += m
    ctx = SimpleNamespace(
        page_table=jnp.asarray(table), attn_lens=jnp.asarray(lens, jnp.int32),
        live=jnp.asarray([True] * s if live is None else live))
    return jnp.asarray(q, dtype), jnp.asarray(store), ctx, n_pool


def _kernel(cfg, q, store, ctx, n_pool):
    stride, kernel, block, _r = sparse.geometry(cfg)
    assert sparse_select.accepts(store.shape, store.dtype, cfg.head_dim_,
                                 cfg.num_heads // cfg.num_kv_heads)
    return sparse_select.sparse_select_pallas(
        q, store, ctx.page_table, jnp.where(ctx.live, ctx.attn_lens, 0),
        stride=stride, kernel=kernel, block=block, topk=cfg.sparse_topk,
        init_blocks=cfg.sparse_init_blocks,
        near_blocks=cfg.sparse_window_size // block,
        dense_len=cfg.sparse_dense_len,
        width=min(ctx.page_table.shape[1], sparse.table_width(cfg)),
        n_pages=n_pool, interpret=True)


def _oracle_scores(cfg, q, store, ctx):
    s, width = ctx.page_table.shape
    pooled = store[ctx.page_table].reshape(s, width * 4, cfg.num_kv_heads,
                                           cfg.head_dim_)
    n = jnp.maximum(ctx.attn_lens, 1)[:, None]
    return sparse.block_scores(cfg, q[:, None], pooled, n)[:, :, 0]


def _held(cfg, q, store, ctx, n_pool):
    """The kernel's scores, table and count against the jnp form's; returns
    the count."""
    table, lens, count = sparse.selected_table(cfg, q, store, ctx, n_pool)
    scores, picked, took = _kernel(cfg, q, store, ctx, n_pool)
    live = np.asarray(ctx.live)
    np.testing.assert_allclose(
        np.asarray(scores)[live],
        np.asarray(_oracle_scores(cfg, q, store, ctx))[live], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(took), np.asarray(count))
    np.testing.assert_array_equal(
        np.asarray(picked).reshape(table.shape), np.asarray(table))
    assert np.isfinite(np.asarray(scores)).all()
    return np.asarray(count), np.asarray(table).reshape(len(live), 2, -1)


def _cell():
    """The cell's shapes: 96 rows of 8.2k-28k keys and the engine's spare
    row, 2 K/V heads of 16 queries, a 448-wide table, bf16 queries."""
    cfg = decoder.get_config("minicpm-sala")
    rng = np.random.default_rng(5)
    lens = rng.integers(8193, 28673, 96).tolist() + [0]
    lens[:4] = [8193, 28672, 16384, 16385]      # tile edges among them
    got = _rows(cfg, lens, 448, jnp.bfloat16, 5, live=[True] * 96 + [False])
    count, _ = _held(cfg, *got)
    assert (count[:96] == 64).all() and (count[96] == 0).all()


def _tiny():
    cfg = _cfg()
    count, _ = _held(cfg, *_rows(cfg, [1000, 700, 513, 300], 16, jnp.float32,
                                 1))
    assert (count == 4).all()


def _one_past_dense():
    cfg = _cfg()
    count, table = _held(cfg, *_rows(cfg, [257, 257 + 64], 16, jnp.float32,
                                     2))
    assert (count == 4).all()       # 5 and 6 blocks, the best 4


def _at_dense():
    cfg = _cfg()
    count, _ = _held(cfg, *_rows(cfg, [256, 255, 64, 1], 16, jnp.float32, 3))
    assert count[:, 0].tolist() == [4, 4, 1, 1]      # every block


def _dead_row():
    cfg = _cfg()
    q, store, ctx, n_pool = _rows(cfg, [900, 900, 900], 16, jnp.float32, 4,
                                  live=[True, False, True])
    count, table = _held(cfg, q, store, ctx, n_pool)
    assert count[:, 0].tolist() == [4, 0, 4] and not table[1].any()
    # a dead row's length may be anything: the engine's spare row
    ctx.attn_lens = jnp.asarray([900, 0, 900], jnp.int32)
    _held(cfg, q, store, ctx, n_pool)


def _part_filled_last_page():
    """The last page holds 1 to 64 of the row's keys: none to three of the
    pooled keys that start in it are complete, and the one that starts a
    stride before it may not be."""
    cfg = _cfg()
    lens = [512 + k for k in (1, 15, 16, 31, 32, 47, 48, 63, 64)]
    count, _ = _held(cfg, *_rows(cfg, lens, 16, jnp.float32, 6))
    assert (count == 4).all()


def _completes_this_step(monkeypatch):
    """A row whose token completes a pooled key this very step: the store
    the kernel reads is ``_complete_pooled``'s result, in one program."""
    cfg = _cfg()
    lens = [512 + 48, 512 + 32, 512 + 17]       # two rows complete a key
    q, store, ctx, n_pool = _rows(cfg, lens, 16, jnp.float32, 7)
    rng = np.random.default_rng(7)
    # the keys the completed pooled key is the mean of lie along the
    # queries: a softmax that misses it is another softmax
    along = 3 * np.sign(np.asarray(q).mean((0, 1)))
    k_pool = jnp.asarray(rng.normal(size=(2, n_pool, BLOCK, 128)) + along,
                         jnp.float32)
    store = jnp.where(jnp.isnan(store), 0.0, store)

    def step(k_pool, store):
        store = sparse._complete_pooled(cfg, k_pool, store, ctx)
        return store, sparse.selected_table(cfg, q, store, ctx, n_pool)

    written, want = jax.jit(step)(k_pool, store)
    assert not np.array_equal(np.asarray(written), np.asarray(store))
    monkeypatch.setattr(sparse_select, "in_kernel", sparse_select.accepts)
    monkeypatch.setattr(
        sparse_select, "sparse_select_pallas", functools.partial(
            sparse_select.sparse_select_pallas, interpret=True))
    _, got = jax.jit(step)(k_pool, store)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    monkeypatch.undo()
    scores = _kernel(cfg, q, written, ctx, n_pool)[0]
    stale = _oracle_scores(cfg, q, store, ctx)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(
        _oracle_scores(cfg, q, written, ctx)), atol=1e-5)
    assert np.abs(np.asarray(scores) - np.asarray(stale))[:2].max() > 1e-3


def _null_page():
    """A table whose entries past the row's pages are the null page, which
    holds NaN, and a row of one page: nothing of it reaches a score."""
    cfg = _cfg()
    q, store, ctx, n_pool = _rows(cfg, [64 * 5, 40, 64 * 9 + 1], 16,
                                  jnp.float32, 8)
    assert np.isnan(np.asarray(store[0])).all()
    assert (np.asarray(ctx.page_table)[0, 5:] == 0).all()
    _held(cfg, q, store, ctx, n_pool)


def _equal_scores():
    """Queries of zero: every pooled key a row sees weighs the same, the
    choice is the forced blocks and then the LOWEST."""
    cfg = _cfg()
    q, store, ctx, n_pool = _rows(cfg, [1000, 640], 16, jnp.float32, 9,
                                  q=np.zeros((2, 16, 128)))
    count, table = _held(cfg, q, store, ctx, n_pool)
    pages = np.asarray(ctx.page_table)
    # 16 and 10 blocks: the first, the second (the lowest free), the last 2
    assert table[0, 0, :4].tolist() == pages[0, [0, 1, 14, 15]].tolist()
    assert table[1, 1, :4].tolist() == (
        pages[1, [0, 1, 8, 9]] + n_pool).tolist()


def _fewer_than_topk():
    """Past ``dense_len`` with fewer blocks than ``topk``: all of them."""
    cfg = _cfg(sparse_topk=8, sparse_dense_len=128)
    count, _ = _held(cfg, *_rows(cfg, [300, 129, 500], 16, jnp.float32, 10))
    assert count[:, 0].tolist() == [5, 3, 8]


CASES = {
    "cell": _cell, "tiny": _tiny, "one_past_dense": _one_past_dense,
    "at_dense": _at_dense, "dead_row": _dead_row,
    "part_filled_last_page": _part_filled_last_page,
    "completes_this_step": _completes_this_step, "null_page": _null_page,
    "equal_scores": _equal_scores, "fewer_than_topk": _fewer_than_topk}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_jnp_form(case, monkeypatch):
    fn = CASES[case]
    if fn is _completes_this_step:
        fn(monkeypatch)
    else:
        fn()


def test_which_stores_the_kernel_takes(monkeypatch):
    """One float32 tile a page, heads of 128, whole sublane tiles of
    queries a group, on a TPU: the published sizes; not the tiny preset's."""
    full, tiny = (decoder.get_config(n)
                  for n in ("minicpm-sala", "minicpm-sala-tiny"))
    assert sparse_select.accepts((22978, 8, 128), jnp.float32, 128, 16)
    assert not sparse_select.accepts((22978, 8, 128), jnp.bfloat16, 128, 16)
    assert not sparse_select.accepts((90, 4, 16), jnp.float32, 16, 2)
    assert not sparse_select.accepts((90, 4, 128), jnp.float32, 128, 16)
    assert not sparse.in_kernel(full)                # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sparse.in_kernel(full) and not sparse.in_kernel(tiny)
