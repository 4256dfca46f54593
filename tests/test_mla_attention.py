"""The absorbed latent-attention decode kernel (``ops/mla_attention.py``)
in interpret mode against its gather-based oracle, at the row width the
cells run (640 lanes, rank 512), and the rule that sizes its blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.ops import mla_attention as mla
from polyrl_tpu.ops.paged_attention import _block_plan

W, RANK, PAGE = 640, 512, 16
SCALE = 192 ** -0.5


def _case(heads, dtype, lengths, width, seed=0):
    """q, pool, table, lens: each live row's pages its own, scattered
    over the pool; the table's unused entries name the null page 0."""
    rng = np.random.default_rng(seed)
    pages = [-(-t // PAGE) for t in lengths]
    order = rng.permutation(np.arange(1, 1 + sum(pages)))
    table = np.zeros((len(lengths), width), np.int32)
    at = 0
    for r, n in enumerate(pages):
        table[r, :n] = order[at:at + n]
        at += n
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    pool = jax.random.normal(k1, (1, 1 + sum(pages), PAGE, W), jnp.float32)
    pool = pool.at[..., RANK + 64:].set(0).astype(dtype)
    q = jax.random.normal(k2, (len(lengths), heads, W), jnp.float32)
    return (q.astype(dtype), pool, jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


# (pages a block, sub-blocks, buffers): what the rule returns for 32 and
# for 128 heads at these shapes, and others it could: one piece against
# sub-blocks, two buffers against three, a block of one page
PLANS = [None, (4, 1, 2), (4, 2, 2), (8, 4, 3), (2, 1, 3), (1, 1, 2)]


@pytest.mark.parametrize("plan", PLANS, ids=lambda p: "rule" if p is None
                         else "x".join(map(str, p)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [32, 128])
def test_kernel_is_the_oracle(heads, dtype, plan):
    """Lengths that end inside the first sub-block, on a sub-block's and
    on a block's edge, one key past it and several blocks on; a dead row
    first, between and after the live ones; the output [S, H, rank] in
    the pool's dtype."""
    width = 512
    b, subs, _nbuf = plan or mla._block_plan(heads, W, RANK, PAGE,
                                             jnp.dtype(dtype).itemsize, width)
    bt = b * PAGE
    sub = bt // subs
    lengths = [0, 5, sub, bt, 0, bt + 1, 3 * bt + sub + 7, 2 * bt, 1, 0]
    assert max(lengths) <= width * PAGE
    q, pool, table, lens = _case(heads, dtype, lengths, width)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True, plan=plan)
    assert got.shape == (len(lengths), heads, RANK) and got.dtype == dtype
    want = mla.latent_paged_attention_ref(q, pool, table, lens, RANK, SCALE)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = jnp.abs(got.astype(jnp.float32) - want).max(axis=(1, 2))
    assert float(err.max()) < tol, err
    for r, t in enumerate(lengths):
        if t == 0:
            assert not bool(jnp.any(got[r])), r


def test_every_row_dead_and_one_row_alone():
    q, pool, table, lens = _case(32, jnp.float32, [0, 0, 0], 8)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True, plan=(2, 2, 3))
    assert not bool(jnp.any(got))
    q, pool, table, lens = _case(32, jnp.float32, [0, 0, 70], 8)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True, plan=(2, 2, 3))
    want = mla.latent_paged_attention_ref(q, pool, table, lens, RANK, SCALE)
    assert float(jnp.abs(got - want).max()) < 2e-5


# -- the shape rule -------------------------------------------------------------


def test_block_plan_follows_the_intensity_not_a_name():
    """128 heads over rows of 640 bf16 lanes are 230 FLOPs a byte, on the
    compute side of half the ridge: 2048 keys in two sub-blocks, two
    buffers; 32 heads are 58, on the DMA side: a MiB in one piece, three
    buffers."""
    ridge = mla._RIDGE
    assert 239 < ridge < 242

    def intensity(h):
        return 2 * h * (W + RANK) / (W * 2)

    assert intensity(128) > ridge / 2 > intensity(32)
    few = mla._block_plan(32, W, RANK, 64, 2, 192)       # Ling's cell
    many = mla._block_plan(128, W, RANK, 64, 2, 320)     # dots.vlm1's
    assert few == (12, 1, 3) and many == (32, 2, 2)
    # the boundary is the arithmetic, wherever a model's heads fall
    assert mla._block_plan(64, W, RANK, 64, 2, 320) == few
    assert mla._block_plan(72, W, RANK, 64, 2, 320) == many
    # float32 rows halve the intensity and the pages a MiB holds
    assert mla._block_plan(128, W, RANK, 64, 4, 320) == (6, 1, 3)
    # a block is whole pages, at most the table; sub-blocks divide it
    for heads in (32, 128):
        for p in (1, 3, 5, 7, 320):
            b, subs, nbuf = mla._block_plan(heads, W, RANK, 64, 2, p)
            assert 1 <= b <= p and b % subs == 0 and nbuf in (2, 3)


@pytest.mark.parametrize("hkv,page,d,itemsize,p,want", [
    (4, 64, 128, 2, 192, (8, 2, 3)),   # qwen2.5-7b, rollout-long and -short
    (4, 64, 128, 2, 64, (8, 2, 3)),    # qwen3-30b-a3b.rollout-wide
    (4, 64, 128, 4, 192, (4, 2, 3)),   # a float32 pool
    (1, 64, 128, 2, 192, (32, 2, 3)),  # a tp shard left with one KV head
    (8, 64, 128, 2, 32, (4, 2, 3)),    # chip_smoke's model
    (4, 64, 128, 2, 5, (5, 1, 3)),     # a table narrower than the block
    (2, 64, 128, 2, 192, (16, 2, 3)),  # zaya1-8b.rollout-wide-cca
    (10, 64, 128, 2, 320, (3, 1, 3)),  # phi-4-mini-flash's shared pool
    (10, 64, 128, 2, 8, (3, 1, 3)),    # and its window layers' rings
])
def test_gqa_pages_per_block_is_pinned(hkv, page, d, itemsize, p, want):
    """The GQA decode kernel's plan (pages a block, sub-blocks of a row's
    last block, buffers a pool) at the five GQA cells' shapes (and their
    neighbours): an edit of it moves those cells' programs."""
    assert _block_plan(hkv, page, d, itemsize, p) == want
