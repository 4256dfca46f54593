"""The absorbed latent-attention decode kernel (``ops/mla_attention.py``)
in interpret mode against its gather-based oracle, at the row width the
cells run (640 lanes, rank 512), the rule that sizes its blocks and the
pieces of a row's last block, and the keys a plan multiplies."""

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


# (pages a block, sub-blocks, buffers, keys a piece of the last block):
# what the rule returns for 32 and for 128 heads at these shapes, and
# others it could: one piece against sub-blocks, two buffers against
# three, a block of one page; a piece that is the block (the last block
# under its mask alone), the sub-block, half of it, one page, and one
# large enough to be cut in two parts as a block is
PLANS = [None, (4, 1, 2, 64), (4, 2, 2, 32), (8, 4, 3, 16), (2, 1, 3, 16),
         (1, 1, 2, 16), (16, 2, 2, 64), (32, 2, 2, 256)]


@pytest.mark.parametrize("plan", PLANS, ids=lambda p: "rule" if p is None
                         else "x".join(map(str, p)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [32, 128])
def test_kernel_is_the_oracle(heads, dtype, plan):
    """Lengths that end inside the first sub-block, on a sub-block's and
    on a block's edge, one key past it and several blocks on, a last
    block of whole pieces and a key more; a dead row first, between and
    after the live ones; the output [S, H, rank] in the pool's dtype."""
    width = 512
    b, subs, _nbuf, piece = plan or mla._block_plan(
        heads, W, RANK, PAGE, jnp.dtype(dtype).itemsize, width)
    bt = b * PAGE
    sub = bt // subs
    lengths = [0, 5, sub, bt, 0, bt + 1, 3 * bt + sub + 7, 2 * bt, 1, 0,
               bt + min(2 * piece, bt - PAGE) + 1]
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


def _tail(kind: str, bt: int, piece: int) -> int:
    """Keys in a row's last block, by what the kernel's pieces make of
    them (a piece that is the block has no edge inside it)."""
    edge = piece if piece < bt else bt // 2
    return {"one_key": 1, "under_edge": edge - 1, "on_edge": edge,
            "over_edge": edge + 1,
            "whole_pieces": max(piece, bt - piece) if piece < bt else PAGE,
            "short_of_block": bt - 1}[kind]


@pytest.mark.parametrize("kind", ["one_key", "under_edge", "on_edge",
                                  "over_edge", "whole_pieces",
                                  "short_of_block"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [32, 128])
def test_a_rows_last_block(heads, dtype, kind):
    """Under the rule's own plan, a row's part-filled last block of one
    key, one key under, on and over a piece's edge, of whole pieces
    (nothing of the last piece masked) and one key short of the block:
    behind a whole block and as a row that is nothing else, a dead row
    before, between and after."""
    width = 256
    plan = mla._block_plan(heads, W, RANK, PAGE, jnp.dtype(dtype).itemsize,
                           width)
    b, _subs, _nbuf, piece = plan
    bt = b * PAGE
    tail = _tail(kind, bt, piece)
    lengths = [0, bt + tail, 0, tail, 0]
    assert mla.keys_multiplied(lengths, plan, PAGE) == (
        bt + 2 * -(-tail // piece) * piece)
    q, pool, table, lens = _case(heads, dtype, lengths, width, seed=1)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True)
    want = mla.latent_paged_attention_ref(q, pool, table, lens, RANK, SCALE)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = jnp.abs(got.astype(jnp.float32) - want).max(axis=(1, 2))
    assert float(err.max()) < tol, err
    assert not bool(jnp.any(got[0])) and not bool(jnp.any(got[4]))


def test_every_row_dead_and_one_row_alone():
    q, pool, table, lens = _case(32, jnp.float32, [0, 0, 0], 8)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True, plan=(2, 2, 3, 16))
    assert not bool(jnp.any(got))
    q, pool, table, lens = _case(32, jnp.float32, [0, 0, 70], 8)
    got = mla.latent_paged_attention_pallas(q, pool, table, lens, RANK, SCALE,
                                            interpret=True, plan=(2, 2, 3, 16))
    want = mla.latent_paged_attention_ref(q, pool, table, lens, RANK, SCALE)
    assert float(jnp.abs(got - want).max()) < 2e-5


# -- the shape rule -------------------------------------------------------------


def test_block_plan_follows_the_intensity_not_a_name():
    """128 heads over rows of 640 bf16 lanes are 230 FLOPs a byte, on the
    compute side of half the ridge: 2048 keys in two sub-blocks, two
    buffers, the last block in pieces of 512 keys; 32 heads are 58, on the
    DMA side: a MiB in one piece, three buffers, the last block whole
    under its mask."""
    ridge = mla._RIDGE
    assert 239 < ridge < 242

    def intensity(h):
        return 2 * h * (W + RANK) / (W * 2)

    assert intensity(128) > ridge / 2 > intensity(32)
    few = mla._block_plan(32, W, RANK, 64, 2, 192)       # Ling's cell
    many = mla._block_plan(128, W, RANK, 64, 2, 320)     # dots.vlm1's
    assert few == (12, 1, 3, 768) and many == (32, 2, 2, 512)
    # the boundary is the arithmetic, wherever a model's heads fall
    assert mla._block_plan(64, W, RANK, 64, 2, 320) == few
    assert mla._block_plan(72, W, RANK, 64, 2, 320) == many
    # float32 rows halve the intensity and the pages a MiB holds
    assert mla._block_plan(128, W, RANK, 64, 4, 320) == (6, 1, 3, 384)
    # a block is whole pages, at most the table; sub-blocks divide it; a
    # piece is whole pages and divides the sub-block
    for heads in (32, 128):
        for page in (16, 64, 256):
            for p in (1, 3, 5, 7, 320):
                b, subs, nbuf, piece = mla._block_plan(heads, W, RANK, page,
                                                       2, p)
                assert 1 <= b <= p and b % subs == 0 and nbuf in (2, 3)
                assert piece % page == 0
                assert (b * page // subs) % piece == 0
                assert piece == b * page or piece <= max(512, page)


@pytest.mark.parametrize("lengths,plan,page,want", [
    # dots.vlm1's plan: 2,048 keys a block, pieces of 512
    ([1], (32, 2, 2, 512), 64, 512),
    ([512], (32, 2, 2, 512), 64, 512),
    ([513], (32, 2, 2, 512), 64, 1024),
    ([2047], (32, 2, 2, 512), 64, 2048),
    ([2048], (32, 2, 2, 512), 64, 2048),
    ([2049, 0, 4096 + 1025], (32, 2, 2, 512), 64, 2560 + 4096 + 1536),
    # the same rows by sub-blocks of 1,024 (the parent's count) and under
    # a piece that is the block
    ([2049, 0, 4096 + 1025], (32, 2, 2, 1024), 64, 3072 + 4096 + 2048),
    ([2049, 0, 4096 + 1025], (32, 2, 2, 2048), 64, 4096 + 4096 + 2048),
    # Ling's plan: the last block whole
    ([1, 768, 769], (12, 1, 3, 768), 64, 768 + 768 + 1536),
    ([0, 0], (12, 1, 3, 768), 64, 0),
])
def test_keys_multiplied_against_hand_counts(lengths, plan, page, want):
    assert mla.keys_multiplied(lengths, plan, page) == want


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
