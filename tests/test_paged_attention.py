"""Paged decode attention: oracle vs dense attention, Pallas(interpret) vs oracle."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.ops import paged_attention as pa
from polyrl_tpu.ops.attention import attention
from polyrl_tpu.ops.paged_attention import (
    paged_attention_pallas,
    paged_attention_ref,
)

PAGE = 8


def _make_case(rng, s=3, hq=4, hkv=2, d=16, n_pool=32, max_pages=4,
               lens=(5, 17, 1), page=PAGE):
    """Random pool (head-major [Hkv, N, page, D]) + scattered page tables +
    a dense mirror of the same KV."""
    assert len(lens) == s
    k_pool = rng.standard_normal((hkv, n_pool, page, d)).astype(np.float32)
    v_pool = rng.standard_normal((hkv, n_pool, page, d)).astype(np.float32)
    q = rng.standard_normal((s, hq, d)).astype(np.float32)

    free = list(range(1, n_pool))
    rng.shuffle(free)
    table = np.zeros((s, max_pages), np.int32)
    t_max = max_pages * page
    k_dense = np.zeros((s, t_max, hkv, d), np.float32)
    v_dense = np.zeros((s, t_max, hkv, d), np.float32)
    for i, ln in enumerate(lens):
        n_pages = (ln + page - 1) // page
        pages = [free.pop() for _ in range(n_pages)]
        table[i, :n_pages] = pages
        for j, pg in enumerate(pages):
            k_dense[i, j * page:(j + 1) * page] = k_pool[:, pg].transpose(1, 0, 2)
            v_dense[i, j * page:(j + 1) * page] = v_pool[:, pg].transpose(1, 0, 2)
    return q, k_pool, v_pool, table, np.asarray(lens, np.int32), k_dense, v_dense


def test_ref_matches_dense_attention():
    rng = np.random.default_rng(0)
    q, kp, vp, table, lens, kd, vd = _make_case(rng)
    out = paged_attention_ref(q, kp, vp, table, lens)

    # dense oracle row by row (each row has its own length)
    for i in range(q.shape[0]):
        ln = int(lens[i])
        dense = attention(
            q[None, i:i + 1].transpose(0, 1, 2, 3).reshape(1, 1, *q.shape[1:]),
            kd[None, i, :ln], vd[None, i, :ln])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(dense[0, 0]),
                                   rtol=2e-5, atol=2e-5)


def _edge_lens(block_tokens, width_tokens):
    """The lengths that matter to a kernel that walks a row in blocks:
    nothing, one token, one short of a block, a block, a block and one,
    the table's full width; a row without a request first, between two
    live rows and last."""
    return (0, 1, block_tokens - 1, 0, block_tokens, block_tokens + 1,
            width_tokens, 0)


# (hq, hkv, d, dtype, page_size, table width in pages)
_KERNEL_CASES = [
    (4, 2, 16, np.float32, 8, 4),
    (8, 8, 32, np.float32, 8, 4),
    (8, 2, 128, np.float32, 8, 4),
    # the shapes that run on the chip: bf16 q and pools, 64-token pages,
    # several pages a block (8, 4, 8, 16, 3)
    (28, 4, 128, jnp.bfloat16, 64, 20),   # qwen2.5-7b; 8 does not divide 20
    (16, 8, 128, jnp.bfloat16, 64, 9),    # qwen3-1.7b; 4 does not divide 9
    (32, 4, 128, jnp.bfloat16, 64, 16),   # qwen3-30b-a3b; two whole blocks
    (8, 2, 128, jnp.bfloat16, 64, 40),    # zaya1-8b; 16 does not divide 40
    (40, 10, 128, jnp.bfloat16, 64, 8),   # phi-4-mini-flash's paired heads
                                          # on a ring: blocks of 3, 3, 2
]


def _check_against_ref(q, kp, vp, table, lens, tol, interpret=True, **kw):
    ref = paged_attention_ref(q, kp, vp, table, lens)
    pal = paged_attention_pallas(q, kp, vp, table, lens, interpret=interpret,
                                 **kw)
    assert pal.dtype == q.dtype and pal.shape == q.shape
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(pal, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=tol, atol=tol)
    # a row without a request is zeros (the oracle attends one token of
    # the null page there: nothing reads either)
    assert not np.asarray(pal, np.float32)[~live].any()


@pytest.mark.parametrize("hq,hkv,d,dtype,page,width", _KERNEL_CASES)
def test_pallas_interpret_matches_ref(hq, hkv, d, dtype, page, width):
    rng = np.random.default_rng(1)
    b, _subs, _nbuf = pa._block_plan(hkv, page, d,
                                     jnp.dtype(dtype).itemsize, width)
    if page == PAGE:
        lens = (5, 17, 1, 32)
    else:
        assert 1 < b < width  # a row is several blocks of several pages
        lens = _edge_lens(b * page, width * page)
    q, kp, vp, table, lens, _, _ = _make_case(
        rng, s=len(lens), hq=hq, hkv=hkv, d=d, lens=lens, page=page,
        max_pages=width, n_pool=1 + sum(-(-ln // page) for ln in lens))
    q, kp, vp = (jnp.asarray(a, dtype) for a in (q, kp, vp))
    # bf16: q is rounded once more after scaling and the probabilities go
    # to the MXU in bf16; both stay under a bf16 ulp of the output
    _check_against_ref(q, kp, vp, table, lens,
                       2e-5 if dtype == np.float32 else 1e-2)


def test_pallas_interpret_small_blocks_exact(monkeypatch):
    """float32 at a tight tolerance with the block cut to two 8-token
    pages, so that the online softmax runs over several blocks, the
    table's width (7) is not a multiple of the block and rows hand their
    successors their first blocks across rows without a request."""
    monkeypatch.setattr(pa, "_KV_BLOCK_BYTES", 2 * 2 * PAGE * 24 * 4)
    assert pa._block_plan(2, PAGE, 24, 4, 7) == (2, 1, 3)
    rng = np.random.default_rng(5)
    lens = (0, 0) + _edge_lens(2 * PAGE, 7 * PAGE) + (3,)
    q, kp, vp, table, lens, _, _ = _make_case(
        rng, s=len(lens), hq=6, hkv=2, d=24, lens=lens, max_pages=7,
        n_pool=40)
    _check_against_ref(q, kp, vp, table, lens, 2e-5)


def test_interpret_takes_the_pools_unconstrained():
    """For the chip the wrapper pins both pools to HBM (``pa._in_hbm``);
    interpreted it hands them on as they came, because the HLO interpreter
    cannot slice an aval that carries a memory space (``TypeError`` in its
    ``_dynamic_slice``): the constraint is in the traced program without
    ``interpret`` and not with it, and the interpreted kernel agrees with
    the oracle."""
    rng = np.random.default_rng(11)
    q, kp, vp, table, lens, _, _ = _make_case(rng)

    def traced(interpret):
        return str(jax.make_jaxpr(
            lambda *a: paged_attention_pallas(*a, interpret=interpret))(
                q, kp, vp, table, lens))

    assert traced(False).count("with_memory_space_constraint") == 2
    assert "with_memory_space_constraint" not in traced(True)
    _check_against_ref(q, kp, vp, table, lens, 2e-5)


def _ring_lens(pattern, bt, sub, nbuf):
    """Row lengths, in keys, that walk the ring of ``nbuf`` buffers over
    row boundaries: the look-ahead is ``nbuf - 1`` blocks of the BATCH."""
    return {
        # rows of 0, 1, nbuf - 1, nbuf and nbuf + 1 blocks, whole and not
        "blocks_around_the_ring": (
            0, bt, (nbuf - 1) * bt, nbuf * bt, (nbuf + 1) * bt, 0,
            bt - 1, (nbuf - 1) * bt - 1, nbuf * bt + 1, (nbuf + 1) * bt - 1),
        # rows without a request first, between and last: the look-ahead
        # steps over them, however many lie together
        "empty_rows_first_between_last": (
            0, 0, 5, 0, 0, 0, bt + 3, 0, 2 * bt, 0, 0),
        # the look-ahead spans more rows than one: every row is one
        # part-filled block
        "every_row_one_part_filled_block": (
            3, bt - 1, 1, sub, sub + 1, bt - sub, 7, bt - 1, 2),
        # a last block whose first sub-block alone is live, after 0, 1 and
        # 2 whole blocks, filled to its end, by one key and by one less
        "last_block_one_live_sub_block": (
            sub, bt + 1, 2 * bt + sub, sub - 1, bt + sub - 1),
        # every row dead but the last: the cold start falls on it
        "one_live_row_last": (0, 0, 0, nbuf * bt + sub + 1),
    }[pattern]


# (pages a block, sub-blocks of a last block, buffers): two and three
# blocks in flight, one block (the parent's depth), one page a block
_RING_PLANS = [(2, 2, 3), (4, 4, 3), (4, 2, 4), (2, 1, 2), (1, 1, 3)]


@pytest.mark.parametrize("plan", _RING_PLANS,
                         ids=lambda p: "x".join(map(str, p)))
@pytest.mark.parametrize("pattern", [
    "blocks_around_the_ring", "empty_rows_first_between_last",
    "every_row_one_part_filled_block", "last_block_one_live_sub_block",
    "one_live_row_last"])
def test_ring_runs_across_rows(pattern, plan):
    """float32 at a tight tolerance under a handed plan: the ring's
    position is carried from program to program, and blocks of later rows
    are in flight while a row ends."""
    b, subs, nbuf = plan
    lens = _ring_lens(pattern, b * PAGE, b * PAGE // subs, nbuf)
    width = -(-max(lens) // PAGE) + 1
    rng = np.random.default_rng(9)
    q, kp, vp, table, lens, _, _ = _make_case(
        rng, s=len(lens), hq=6, hkv=2, d=24, lens=lens, max_pages=width,
        n_pool=1 + sum(-(-ln // PAGE) for ln in lens))
    _check_against_ref(q, kp, vp, table, lens, 2e-5, plan=plan)


def _sparse_call(rng, parts, width, counts, hkv=2, d=16, rep=16, page=PAGE,
                 dtype=np.float32):
    """What a block-sparse layer hands the kernel (``mixers/sparse.py``) in
    small: a row a (request, K/V head) of ONE head under ``rep`` query
    rows, the pools seen as one head's, a table of the pages that head
    chose in no order (head ``g``'s numbers offset by ``g * N``) and the
    keys they hold: ``count - 1`` whole pages and ``part`` keys of the
    request's own, last; ``count`` 0 is a request that is not there."""
    n_pages = 1 + width * len(parts)
    table = np.zeros((len(parts) * hkv, width), np.int32)
    lens = np.zeros(len(table), np.int32)
    for r, (part, count) in enumerate(zip(parts, counts)):
        for g in range(hkv):
            table[r * hkv + g, :count] = g * n_pages + rng.permutation(
                np.arange(1, n_pages))[:count]
            lens[r * hkv + g] = max(count - 1, 0) * page + (count > 0) * part
    pools = [jnp.asarray(rng.standard_normal((1, hkv * n_pages, page, d)),
                         dtype) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((len(table), rep, d)), dtype)
    return q, pools[0], pools[1], table, lens


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_sparse_layers_call_in_small(dtype, tol):
    """The call MiniCPM-SALA's cell makes 582 times a step, in small: 1
    K/V head under 16 query rows, every row two blocks (4 pages each of a
    table of 8) of which the second is the row's last with EVERY page
    there and the newest part-filled: the path that waits for the block's
    bytes at once. Beside them a request under ``topk`` pages, one whose
    newest page is full (two whole blocks) and one that is not there."""
    rng = np.random.default_rng(21)
    q, kp, vp, table, lens = _sparse_call(
        rng, parts=(1, 5, PAGE - 1, 3, PAGE, 2), width=8,
        counts=(8, 8, 8, 3, 8, 0), dtype=dtype)
    assert pa._block_plan(1, PAGE, 16, 4, 8)[0] == 8   # so a plan is handed
    _check_against_ref(q, kp, vp, table, lens, tol, plan=(4, 2, 3))


# (pages a block, sub-blocks, buffers): a block of a power of two, and
# Phi's ring of three pages (a whole block, then 2 + 1)
_LAST_BLOCK_PLANS = [(8, 2, 3), (3, 1, 3)]


def _last_block_case(b, counts, seed):
    """Rows whose last block owns ``n_pg`` pages with ``tail`` keys in the
    newest, for each (n_pg, tail) of ``counts``: after 0, 1 and 2 whole
    blocks of ``b`` pages, a row of length 0 between live rows."""
    lens = ()
    for n_pg, tail in counts:
        keys = (n_pg - 1) * PAGE + tail
        lens += (keys, 0, b * PAGE + keys, 2 * b * PAGE + keys)
    return _make_case(
        np.random.default_rng(seed), s=len(lens), hq=6, hkv=2, d=24,
        lens=lens, max_pages=3 * b + 1,
        n_pool=1 + sum(-(-ln // PAGE) for ln in lens))[:5]


@pytest.mark.parametrize("tail", [1, PAGE], ids=["one_key", "page_full"])
@pytest.mark.parametrize("plan,n_pg", [
    (plan, n) for plan in _LAST_BLOCK_PLANS for n in range(1, plan[0] + 1)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"{v}pg")
def test_last_block_of_every_page_count(plan, n_pg, tail):
    """A row's last block waits for the pages it owns by their BYTES, in
    power-of-two pieces (the block whole when all are there): every count
    from one page to the block, the newest page holding one key and
    full (with all ``b`` pages that is a whole block), with a row of
    length 0 between live rows."""
    _check_against_ref(*_last_block_case(plan[0], [(n_pg, tail)], 13), 2e-5,
                       plan=plan)


_BALANCE = """
import sys
from jax.experimental.pallas import tpu as pltpu
sys.path.insert(0, {tests!r})
import test_paged_attention as t
plan = {plan!r}
case = t._last_block_case(plan[0], [(n, tail) for n in range(1, plan[0] + 1)
                                    for tail in (1, t.PAGE)], 17)
t._check_against_ref(*case, 2e-5, plan=plan,
                     interpret=pltpu.InterpretParams())
print("ran to its end")
"""


@pytest.mark.parametrize("plan", _LAST_BLOCK_PLANS,
                         ids=lambda p: "x".join(map(str, p)))
def test_waits_balance_the_starts(plan):
    """The HLO interpreter copies at a start and waits for nothing, so a
    wait for too few or too many bytes passes there and stalls or races
    on the chip. Mosaic's own interpreter keeps the semaphores: a copy
    runs when its bytes are WAITED for (a wait short of the starts reads
    stale keys and leaves a count it reports at the kernel's end), and a
    wait beyond them never returns, hence a child with a time limit. Every
    page count of a last block, after 0, 1 and 2 whole blocks, in one
    call."""
    done = subprocess.run(
        [sys.executable, "-c", _BALANCE.format(
            tests=os.path.dirname(os.path.abspath(__file__)), plan=plan)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    assert "ran to its end" in done.stdout
    assert "non-zero count" not in done.stdout + done.stderr


def test_table_outside_the_pool_is_clipped():
    """The kernel's descriptors carry no bounds checks (they paced a
    program of small pages); the wrapper clips the table to the pool in
    their place. A table holding -1, the pool's size and 2**30 among a
    row's LIVE pages returns exactly what the clipped table returns."""
    rng = np.random.default_rng(19)
    lens = (5 * PAGE + 3, 0, 2 * PAGE, 7 * PAGE)
    q, kp, vp, table, lens, _, _ = _make_case(
        rng, s=len(lens), hq=4, hkv=2, d=16, lens=lens, max_pages=7,
        n_pool=20)
    n_pool = kp.shape[1]
    wild = table.copy()
    wild[0, 1], wild[0, 4], wild[2, 0], wild[3, 6] = -1, n_pool, 2**30, -2**31
    wild[1, :] = 2**30            # a row without a request: never fetched
    clipped = np.clip(wild, 0, n_pool - 1)
    assert (clipped != wild).sum() == 4 + 7

    def run(tab):
        return np.asarray(paged_attention_pallas(
            q, kp, vp, tab, lens, interpret=True, plan=(2, 2, 3)))

    got = run(wild)
    np.testing.assert_array_equal(got, run(clipped))
    np.testing.assert_allclose(
        got[0], np.asarray(paged_attention_ref(q, kp, vp, clipped, lens))[0],
        rtol=2e-5, atol=2e-5)


def test_every_row_dead():
    rng = np.random.default_rng(6)
    q, kp, vp, table, lens, _, _ = _make_case(rng, lens=(0, 0, 0))
    pal = paged_attention_pallas(q, kp, vp, table, lens, interpret=True)
    assert not np.asarray(pal).any()


def test_empty_row_is_finite():
    rng = np.random.default_rng(2)
    q, kp, vp, table, lens, _, _ = _make_case(rng, lens=(5, 0, 3))
    out = paged_attention_ref(q, kp, vp, table, lens)
    assert np.isfinite(np.asarray(out)).all()
    pal = paged_attention_pallas(q, kp, vp, table, lens, interpret=True)
    assert np.isfinite(np.asarray(pal)).all()
    assert not np.asarray(pal)[1].any()


@pytest.mark.parametrize("with_active", [True, False])
def test_decode_gives_rows_without_a_request_length_zero(with_active):
    """``forward_paged_decode`` hands the attention function length 0 for
    rows whose ``active`` is false (the TPU kernel then does no work for
    them) and ``seq_lens + 1`` for the rest; the active rows' logits are
    bitwise what they were when every row attended ``seq_lens + 1``.
    Without ``active`` every row attends ``seq_lens + 1``."""
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("tiny", dtype=jnp.float32, vocab_size=64)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    s, n_pages, width = 5, 12, 2
    rng = np.random.default_rng(4)
    pools = tuple(
        tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in side)
        for side in decoder.make_paged_pools(cfg, n_pages, PAGE))
    table = jnp.asarray(1 + np.arange(s * width).reshape(s, width), jnp.int32)
    seq_lens = jnp.asarray([3, 9, 0, 15, 7], jnp.int32)
    tokens = jnp.asarray([5, 6, 7, 8, 9], jnp.int32)
    active = (jnp.asarray([True, False, False, True, True])
              if with_active else None)

    def run(lens_seen, before):
        def attn(q, kp, vp, pt, lens):
            lens_seen.append(np.asarray(lens))
            return paged_attention_ref(
                q, kp, vp, pt, seq_lens + 1 if before else lens)

        logits, _, _ = decoder.forward_paged_decode(
            params, cfg, tokens, seq_lens, pools, table, seq_lens,
            attn_fn=attn, active=active)
        return np.asarray(logits)

    seen, ignored = [], []
    now, was = run(seen, before=False), run(ignored, before=True)
    want = np.asarray(seq_lens) + 1
    if with_active:
        want = np.where(np.asarray(active), want, 0)
    assert len(seen) == cfg.num_layers
    for lens in seen:
        np.testing.assert_array_equal(lens, want)
    rows = np.asarray(active) if with_active else slice(None)
    np.testing.assert_array_equal(now[rows], was[rows])
    assert np.isfinite(now).all()


def test_bf16_pools():
    rng = np.random.default_rng(3)
    q, kp, vp, table, lens, _, _ = _make_case(rng)
    out16 = paged_attention_ref(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
        jnp.asarray(vp, jnp.bfloat16), table, lens)
    out32 = paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out16, np.float32), np.asarray(out32),
                               rtol=0.1, atol=0.1)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kv_write_pallas_matches_scatter(dtype):
    """The fused K+V Pallas write (interpret mode here; the TPU decode hot
    path) must be element-exact vs the XLA row-scatter oracle, including
    multiple inactive slots all routed to the null page 0."""
    from polyrl_tpu.models.decoder import _scatter_token_kv
    from polyrl_tpu.ops.paged_attention import paged_kv_write_pallas

    rng = np.random.default_rng(7)
    hkv, n_pool, d, s = 2, 16, 32, 5
    k_pool = jnp.asarray(rng.standard_normal((hkv, n_pool, PAGE, d)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((hkv, n_pool, PAGE, d)), dtype)
    k_upd_np = rng.standard_normal((s, hkv, d))
    v_upd_np = rng.standard_normal((s, hkv, d))
    # slots 3+4 inactive -> caller routes both to (page 0, off 0). XLA
    # scatter's duplicate-index ordering is formally UNDEFINED, so give the
    # two null-routed slots identical payloads — otherwise exact equality
    # vs the kernel's sequential grid could flake on a backend change.
    k_upd_np[4] = k_upd_np[3]
    v_upd_np[4] = v_upd_np[3]
    k_upd = jnp.asarray(k_upd_np, dtype)
    v_upd = jnp.asarray(v_upd_np, dtype)
    page = jnp.asarray([3, 9, 3, 0, 0], jnp.int32)
    off = jnp.asarray([0, 7, 5, 0, 0], jnp.int32)

    ko, vo = paged_kv_write_pallas(k_pool, v_pool, page, off, k_upd, v_upd,
                                   interpret=True)
    k_ref = _scatter_token_kv(k_pool, page, off, k_upd)
    v_ref = _scatter_token_kv(v_pool, page, off, v_upd)
    np.testing.assert_array_equal(np.asarray(ko, np.float32),
                                  np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(vo, np.float32),
                                  np.asarray(v_ref, np.float32))


def test_kv_write_tp_shard_map_matches_scatter():
    """TP wrapper: pools + updates sharded over tp on the KV-head dim must
    produce the identical pool contents (CPU mesh, scatter impl inside the
    shard_map via POLYRL_KV_WRITE passthrough default on cpu)."""
    from jax.sharding import Mesh

    from polyrl_tpu.models.decoder import _scatter_token_kv
    from polyrl_tpu.ops.paged_attention import make_tp_paged_kv_write

    rng = np.random.default_rng(11)
    hkv, n_pool, d, s = 4, 8, 16, 3
    k_pool = jnp.asarray(rng.standard_normal((hkv, n_pool, PAGE, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((hkv, n_pool, PAGE, d)),
                         jnp.float32)
    k_upd = jnp.asarray(rng.standard_normal((s, hkv, d)), jnp.float32)
    v_upd = jnp.asarray(rng.standard_normal((s, hkv, d)), jnp.float32)
    page = jnp.asarray([2, 5, 0], jnp.int32)
    off = jnp.asarray([1, 7, 0], jnp.int32)

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2),
                ("dp", "fsdp", "tp"))
    fn = make_tp_paged_kv_write(mesh)
    ko, vo = jax.jit(fn)(k_pool, v_pool, page, off, k_upd, v_upd)
    np.testing.assert_allclose(
        np.asarray(ko), np.asarray(_scatter_token_kv(k_pool, page, off,
                                                     k_upd)), atol=0)
    np.testing.assert_allclose(
        np.asarray(vo), np.asarray(_scatter_token_kv(v_pool, page, off,
                                                     v_upd)), atol=0)
