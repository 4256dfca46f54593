"""MiniCPM-SALA's decoder (``models/hybrid.py`` with ``mixers/sparse.py``
and ``mixers/lightning.py``: block-sparse softmax attention whose pages
carry pooled keys, the choice of a row's blocks a K/V head, linear
attention with a float32 state in the slot, muP's scalings) at the
``minicpm-sala-tiny`` preset on the CPU, in float32, against the
benchmark's plain reference (``benchmark/references/sala_sparse_linear.py``:
whole sequences, a mask built per query with a stable sort, the linear
layers token by token).

The limits are float32's: the program and the reference compute the same
sums in another order (pooled keys as two half sums against a mean of 32,
a running softmax over blocks of keys against a whole row, the chunked
linear form against the recurrence, pages against a whole sequence), each
a few ulps of a value of order 1, through 6 layers: 5e-6 on logits of at
most 0.2 in magnitude (the head reads x / 4). The q/k norms' vectors are
drawn at 3 (``params``), so that a head's scores spread over e^+-3 and a
block that is chosen wrongly moves a logit by 1e-4 or more, as a wrong
position, page, pooled row, state row or scale does."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import cache_spec, decoder, hybrid
from polyrl_tpu.models.mixers import lightning, sparse
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6
CHUNK, PAGE = 16, 8
DENSE = 32          # the tiny preset's dense_len


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    kept = cfg.kept_layers or range(cfg.num_layers)
    return {
        "num_hidden_layers": cfg.num_layers,
        "published_num_hidden_layers": len(cfg.mixer_types),
        "mixer_types": [cfg.mixer_types[i] for i in kept],
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "lightning_nh": cfg.lightning_heads,
        "lightning_head_dim": cfg.lightning_head_dim,
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
        "dim_model_base": cfg.dim_model_base,
        "sparse_config": {
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "block_size": cfg.sparse_block_size, "topk": cfg.sparse_topk,
            "init_blocks": cfg.sparse_init_blocks,
            "window_size": cfg.sparse_window_size,
            "dense_len": cfg.sparse_dense_len}}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "sala_sparse_linear")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("minicpm-sala-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """The preset's weights with the norms' vectors drawn (they are one as
    initialised, and a vector that is left out must show), the q/k norms'
    around 3: a head's scores then spread, and the choice of blocks is no
    near tie."""
    tree = decoder.init_params(jax.random.PRNGKey(0), cfg)

    def drawn(path, a):
        name = path[-1].key
        if name in ("q_norm", "k_norm", "o_norm"):
            key = jax.random.PRNGKey(sum(map(ord, name)))
            mean = 1.0 if name == "o_norm" else 3.0
            return mean + 0.2 * jax.random.normal(key, a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(drawn, tree)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, page_size=PAGE, max_seq_len=160,
                prompt_buckets=(16, 128), num_pages=90, prefill_chunk=CHUNK,
                steps_per_dispatch=4, kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def test_the_two_kinds_follow_from_the_published_keys(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [p.mixer for p in plan] == ["sparse", "lightning", "lightning",
                                       "sparse", "sparse", "lightning"]
    assert [p.published for p in plan] == [1, 2, 3, 4, 5, 6]
    assert cache_spec.published_depth(cfg) == 8
    assert not cache_spec.is_uniform(cfg) and cache_spec.is_stateful(cfg)
    # a K/V pair and a float32 pooled row every 4 tokens a head, 3 layers
    assert cache_spec.paged_bytes_per_token(cfg) == 3 * (2 * 2 * 16 * 4
                                                         + 2 * 16 * 4 // 4)
    # 3 states a slot, and 3 sparse layers' tables of the pages a step
    # attended: 4 pages and the keys they hold, a K/V head
    assert cache_spec.slot_bytes(cfg) == 3 * 4 * 16 * 16 * 4 + 3 * 2 * 5 * 4
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("lightning",
                                                           "sparse")
    full = decoder.get_config("minicpm-sala")
    kinds = [p.mixer for p in cache_spec.layer_plan(full)]
    assert "".join(k[0].upper() for k in kinds) == "SLLLLLLSSLLL"
    assert cache_spec.published_depth(full) == 32
    # 3 layers x (1,024 B of K/V + 64 B of float32 pooled keys) a token,
    # 9 states of 2 MiB a slot and 3 tables of 128 pages a K/V head
    assert cache_spec.paged_bytes_per_token(full) == 3 * (1024 + 64)
    assert cache_spec.slot_bytes(full) == 9 * 2 * 2**20 + 3 * 2 * 129 * 4
    whole = decoder.get_config("minicpm-sala", num_layers=32,
                               kept_layers=None)
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), whole))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # the published 9B: 9,476,833,280 in matrices, the rest norms and slopes
    assert count == 9_476_833_280 + 65 * 4096 + 8 * 256 + 24 * (384 + 32)


def test_make_pools_gives_a_sparse_layer_its_pooled_store(cfg):
    paged, state = cache_spec.make_pools(cfg, 11, PAGE, slots=3,
                                         dtype=jnp.float32)
    assert len(paged) == 3 and len(state) == 6
    for k, v, pooled in paged:
        assert k.shape == v.shape == (2, 11, PAGE, 16)
        assert pooled.shape == (11, 2 * (PAGE // 4), 16)
        assert pooled.dtype == jnp.float32
    # S L L S S L: a sparse layer's slot keeps its last step's table
    for (a,), kind in zip(state, "SLLSSL"):
        assert (a.shape, a.dtype) == (
            ((3, 2, 5), jnp.int32) if kind == "S"
            else ((3, 4, 16, 16), jnp.float32))
    with pytest.raises(ValueError, match="pooled row"):
        cache_spec.make_pools(cfg, 11, 6, slots=3)


def test_the_slopes_are_the_familys_at_the_published_layer(cfg, params):
    got = np.asarray(params["layers"]["lightning"]["slopes"])
    assert got.shape == (3, 4)
    for row, l in zip(got, (2, 3, 6)):
        want = [2.0 ** (-8 * (h + 1) / 4) * (1 - l / 7 + 1e-5)
                for h in range(4)]
        np.testing.assert_allclose(row, want, rtol=1e-6)


@pytest.mark.parametrize("length", [5, DENSE, DENSE + 1, 48, 104])
def test_whole_sequence_forward_agrees_with_the_reference(ref, cfg, params,
                                                          length):
    """Under, at and over ``dense_len``; 104 tokens are 13 blocks of which a
    token chooses 4."""
    ids = np.asarray(_prompts([length], seed=length)[0], np.int32)
    pad = -length % PAGE
    row = np.pad(ids, (0, pad))
    mask = (np.arange(length + pad) < length).astype(np.float32)
    got = decoder.forward(params, cfg, jnp.asarray(row)[None],
                          jnp.arange(length + pad)[None],
                          jnp.asarray(mask)[None])
    got = got[0] if isinstance(got, tuple) else got
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(np.asarray(got)[0, :length], want,
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("n_prompt", [3, CHUNK, DENSE - 1, 2 * CHUNK + 1,
                                      5 * CHUNK + PAGE + 3])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(
        ref, cfg, params, n_prompt):
    """Prompts under ``dense_len``, of one whole chunk, and past several (a
    page's edge among them): the prefill calls after the first start from
    the slot's state and the pages' keys and pooled keys; then 19 decode
    steps (across a page's edge, four pooled keys' last tokens and, for
    the shorter prompts, ``dense_len``) through state, pages and pooled
    store, each step's logits against the reference's full forward of the
    whole sequence; the step's counters; at the end the first lightning
    layer's state and the first sparse layer's pooled store are the
    reference's."""
    n_new = 19
    ids = np.asarray(_prompts([n_prompt + n_new], seed=n_prompt)[0], np.int32)
    c = file_keys(cfg)
    want = np.asarray(ref.logits(params, c, ids))
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    # what the slot's and the pages' last request left must not be read
    pools = jax.tree_util.tree_map(lambda a: (a + 7).astype(a.dtype), pools)
    pages = np.arange(1, 17, dtype=np.int32)     # the row's pages in order
    slot = jnp.array([1])
    per = CHUNK // PAGE
    for at in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - at)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :n] = ids[at:at + n]
        done = at // PAGE
        pools, logits = hybrid.prefill(
            params, cfg, jnp.asarray(chunk), jnp.array([n]), jnp.int32(at),
            pools, jnp.asarray(pages[None, :done]),
            jnp.asarray(pages[None, done:done + per]), slot)
        np.testing.assert_allclose(np.asarray(logits[0]), want[at + n - 1],
                                   atol=LOGIT_TOL, rtol=0)
    table = np.zeros((2, 16), np.int32)
    table[1] = pages
    live = jnp.array([False, True])
    before = [np.asarray(a[0][0]) for a in pools[1][:2]]
    for t in range(n_prompt, n_prompt + n_new):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.array([0, ids[t]]), jnp.array([0, t]), pools,
            jnp.asarray(table), jnp.array([0, t]), active=live)
        np.testing.assert_allclose(np.asarray(logits[1]), want[t],
                                   atol=LOGIT_TOL, rtol=0)
        n = t + 1
        blocks = -(-n // PAGE)
        over = n > DENSE
        assert load.tolist() == [
            3 * 2 * (min(blocks, 4) if over else blocks),
            3 * ((n - 8) // 4 + 1) if over else 0, 0 if over else 3, 3]
    # a row without a request left its table and its state as they were
    for a, was in zip(pools[1][:2], before):
        np.testing.assert_array_equal(np.asarray(a[0][0]), was)
    n = n_prompt + n_new
    tr = ref.trace(params, c, ids.tolist(), n_prompt, n_new)
    held = hybrid.held_state(cfg, pools[1], 1)
    assert len(held) == 6 and held[1].shape == (4, 16, 16)
    # a state's entries reach 20 where k is 3 wide: relative too
    np.testing.assert_allclose(held[1], tr["state"], atol=LOGIT_TOL,
                               rtol=1e-5)
    # what the last step attended, read back from the slot through the
    # row's pages as the benchmark's plane reads it: the reference's choice
    assert held[0].shape == (2, 5) and held[0].dtype == np.int32
    plane = harness.load_named("planes", "rollout_sala")
    took = plane.step_choice(c, held[0], pages[:-(-n // PAGE)], n, 24)
    np.testing.assert_array_equal(took, tr["chosen"])
    assert took.sum(1).tolist() == [min(-(-n // PAGE), 4)] * 2
    store = np.asarray(pools[0][0][2])[pages]       # [16, 2 rows x 2, 16]
    mine = store.reshape(32, 2, 16)[:len(tr["pooled"])]
    np.testing.assert_allclose(mine, tr["pooled"], atol=LOGIT_TOL)


@pytest.fixture(scope="module")
def wide(params):
    """The tiny model at the sizes the selection kernel takes
    (``ops/sparse_select.py``): 16 query heads of 128 over 2 K/V heads,
    pooled keys of 4 tokens every 2, so that a page of 8 tokens holds one
    float32 tile of them; weights drawn like ``params``."""
    cfg = decoder.get_config(
        "minicpm-sala-tiny", dtype=jnp.float32, num_heads=16, head_dim=128,
        sparse_kernel_size=4, sparse_kernel_stride=2)
    tree = decoder.init_params(jax.random.PRNGKey(1), cfg)
    norms = {k: v for k, v in params["layers"]["sparse"].items()
             if k in ("q_norm", "k_norm")}
    tree["layers"]["sparse"].update({
        k: jnp.tile(v, (1, 128 // v.shape[1])) for k, v in norms.items()})
    return cfg, tree


@pytest.mark.parametrize("fault, least, kernel", [
    ("", 0.0, False), ("first_pages", 0.25, False),
    ("no_head_offset", 0.5, False), ("", 0.0, True),
    ("first_pages", 0.25, True)])
def test_the_steps_own_table_is_held_to_the_references_choice(
        ref, cfg, params, wide, monkeypatch, fault, least, kernel):
    """48 decode steps of a row from its first token, compiled once; then
    the table its last step left in the slot, read back through the row's
    pages as the benchmark's plane reads it (``step_choice``), against the
    reference's choice for that token. With a fault planted in the step's
    table (``benchmark/tests/control_sala_on_chip.py::plant``: the first
    pages in place of the chosen ones; a head's offset dropped) the share
    of the reference's blocks it lacks says so, whatever the logits do.
    ``kernel``: the step chooses in the selection kernel, interpreted, at
    the sizes it takes (``wide``); the row beside it has no request, and
    its slot's table stays as it was."""
    import functools
    import importlib

    from polyrl_tpu.ops import sparse_select

    plane = harness.load_named("planes", "rollout_sala")
    control = importlib.import_module("benchmark.tests.control_sala_on_chip")
    if kernel:
        cfg, params = wide
        monkeypatch.setattr(sparse_select, "in_kernel", sparse_select.accepts)
        monkeypatch.setattr(
            sparse_select, "sparse_select_pallas", functools.partial(
                sparse_select.sparse_select_pallas, interpret=True))
        assert sparse.in_kernel(cfg)
    n = 48
    ids = np.asarray(_prompts([n], seed=11)[0], np.int32)
    c = file_keys(cfg)
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    pages = np.arange(3, 3 + n // PAGE, dtype=np.int32)
    table = np.zeros((2, 16), np.int32)
    table[1, :len(pages)] = pages
    undo = control.plant(fault) if fault else lambda: None
    try:
        step = jax.jit(lambda tok, t, pools: decoder.forward_paged_decode(
            params, cfg, jnp.stack([tok, tok]), jnp.stack([t, t]), pools,
            jnp.asarray(table), jnp.stack([t, t]),
            active=jnp.array([False, True]))[1])
        for t in range(n):
            pools = step(jnp.int32(ids[t]), jnp.int32(t), pools)
    finally:
        undo()
    picked = hybrid.held_state(cfg, pools[1], 1)[0]
    assert not hybrid.held_state(cfg, pools[1], 0)[0].any()
    tr = ref.trace(params, c, ids.tolist(), n - 1, 1)
    assert tr["chosen"].sum(1).tolist() == [4, 4]
    diff = plane.set_diff(plane.step_choice(c, picked, pages, n, 24),
                          tr["chosen"])
    assert diff >= least if fault else diff == 0.0, (picked, tr["chosen"])


def test_the_choice_alone_is_the_references_with_ties(ref, cfg):
    """``sparse.choose(sparse.block_scores)`` against the reference's
    ``_choose`` on drawn queries and pooled keys, for every length of a
    row's last 24 tokens; and on scores that are ALL equal, where the
    choice is the forced blocks and then the lowest."""
    z = ref._sizes(file_keys(cfg))
    rng = np.random.default_rng(3)
    t = 120
    q = jnp.asarray(rng.normal(size=(t, 4, 16)) * 2, jnp.float32)
    k = jnp.asarray(rng.normal(size=(t, 2, 16)), jnp.float32)
    pooled = sparse.pooled_keys(cfg, k[None])[0]              # [30, 2, 16]
    np.testing.assert_allclose(np.asarray(pooled)[:29],
                               np.asarray(ref.pooled_keys(k, z)), atol=1e-6)
    n = jnp.arange(1, t + 1)
    mine = sparse.chosen_blocks(cfg, q[None], pooled[None], n[None])[0]
    with jax.default_matmul_precision("highest"):
        theirs = ref._choose(q, ref.pooled_keys(k, z), n, t // PAGE, z)
    np.testing.assert_array_equal(np.asarray(mine).transpose(1, 0, 2),
                                  np.asarray(theirs))
    took = np.asarray(mine).sum(-1)                            # [Hkv, T]
    assert (took[:, DENSE:] == 4).all() and took[0, DENSE - 1] == 4
    flat = jnp.zeros((1, 2, 1, 15))
    tie = np.asarray(sparse.choose(cfg, flat, jnp.array([[t]])))[0, :, 0]
    assert (np.flatnonzero(tie[0]) == [0, 1, 13, 14]).all()
    with jax.default_matmul_precision("highest"):
        same = ref._choose(jnp.zeros((1, 4, 16)), pooled * 0, jnp.array([t]),
                           15, z)
    np.testing.assert_array_equal(np.asarray(same)[0], tie)


def test_the_chunked_linear_form_is_the_recurrence(cfg):
    """``lightning_chunked`` over 37 positions in steps of 8, 5 of them
    padding, against ``lightning_recurrent_step`` a position at a time."""
    rng = np.random.default_rng(5)
    b, t, h, d = 2, 40, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
               for _ in range(3))
    slope = jnp.asarray(lightning.slopes(cfg, 2))
    valid = jnp.asarray(np.arange(t)[None] < np.array([[37], [40]]),
                        jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    state, o = lightning.lightning_chunked(s0, q, k, v, slope, valid, 8)
    s, outs = s0, []
    for i in range(t):
        new, oi = lightning.lightning_recurrent_step(
            s, q[:, i], k[:, i], v[:, i], jnp.exp(-slope))
        s = jnp.where(valid[:, i, None, None, None] > 0, new, s)
        outs.append(oi)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s), atol=2e-5,
                               rtol=2e-6)
    want = np.stack([np.asarray(x) for x in outs], 1)
    np.testing.assert_allclose(np.asarray(o)[0, :37], want[0, :37], atol=2e-5,
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(o)[1], want[1], atol=2e-5, rtol=2e-6)


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts under and over ``dense_len``, longer than a chunk and across
    a page's edge through ``CBEngine`` (chunked prefill from and to state,
    pages and pooled store, the fused multi-step decode dispatch): every
    sampled token's log-probability against the reference's score of the
    same sequence; the profiler's counters against the client's count."""
    eng = _engine(cfg, params)
    assert eng.stateful and eng.prefix_cache is None
    prompts = _prompts([5, 17, 41, 100], seed=7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=12, stop_token_ids=())
    try:
        outs = eng.generate(prompts, sp)
        counted = eng.profiler.counters()
    finally:
        eng.stop()
    assert eng.chunk_dispatches > 0
    for prompt, out in zip(prompts, outs):
        toks, lps = out["token_ids"], out["logprobs"]
        assert len(toks) == 12
        want, _ent = ref.score(params, file_keys(cfg), prompt + toks, 12)
        np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
    assert eng.moe_info() == {}
    # a request's decode steps: its 2nd to 12th token (the first is the
    # prefill's), each over the keys before it and itself
    steps = [(len(p) + i + 1) for p in prompts for i in range(11)]
    over = [n for n in steps if n > DENSE]
    assert counted["lightning_state_rows"] == 3 * len(steps)
    assert counted["sparse_dense_rows"] == 3 * (len(steps) - len(over))
    assert counted["sparse_pooled_scored"] == 3 * sum((n - 8) // 4 + 1
                                                      for n in over)
    assert counted["sparse_pages_read"] == 3 * 2 * (
        4 * len(over) + sum(-(-n // PAGE) for n in steps if n <= DENSE))
    assert counted["lightning_kernel_steps"] == 0      # the oracle, off a TPU


def test_a_reused_slot_starts_from_a_zero_state(ref, cfg, params):
    """One slot, two requests after each other: the second's
    log-probabilities are the reference's, whatever the first left in the
    slot's state rows and in the pages' pooled rows."""
    eng = _engine(cfg, params, max_slots=1)
    sp = SamplingParams(temperature=1.0, max_new_tokens=6, stop_token_ids=())
    first, second = _prompts([45, 38], seed=11)
    try:
        eng.generate([first], sp)
        left = [np.asarray(rows[0][0]).copy() for rows in eng._pools[1]]
        out = eng.generate([second], sp)[0]
    finally:
        eng.stop()
    assert all(np.abs(a).max() > 0 for a in left)
    want, _ = ref.score(params, file_keys(cfg), second + out["token_ids"], 6)
    np.testing.assert_allclose(out["logprobs"], want, atol=LOGP_TOL, rtol=0)


def test_recurrent_state_reads_a_running_requests_slot(ref, cfg, params):
    """``CBEngine.recurrent_state``: the lightning layers' states ``[H, D,
    D]`` of a request that is decoding, in layer order, the first against
    the reference after the tokens it has consumed."""
    eng = _engine(cfg, params)
    sp = SamplingParams(temperature=1.0, max_new_tokens=40, stop_token_ids=())
    prompt = _prompts([19], seed=5)[0]
    done = threading.Event()
    box = {}

    def run():
        box["out"] = eng.generate([prompt], sp)
        done.set()

    t = threading.Thread(target=run)
    t.start()
    try:
        got = None
        while got is None and not done.is_set():
            got = eng.recurrent_state("gen-0")
        t.join()
    finally:
        eng.stop()
    assert got is not None
    consumed, rows = got
    toks = box["out"][0]["token_ids"]
    seq = prompt + toks[:consumed - len(prompt)]
    tr = ref.trace(params, file_keys(cfg), seq, len(prompt),
                   consumed - len(prompt))
    assert len(rows) == 6 and rows[1].shape == (4, 16, 16)
    np.testing.assert_allclose(rows[1], tr["state"], atol=LOGIT_TOL,
                               rtol=1e-5)
    # the first sparse layer's table of that step: its own block last
    assert rows[0].shape == (2, 5)
    assert rows[0][:, -1].tolist() == [
        consumed if consumed <= DENSE else 3 * PAGE + (consumed - 1) % PAGE
        + 1] * 2


@pytest.mark.parametrize("control, moves", [
    ("state_bf16", "state"), ("no_decay", "state"),
    ("first_blocks", "chosen"), ("pooled_unwritten", "pooled")])
def test_each_control_moves_what_its_limit_watches(ref, cfg, params, control,
                                                   moves):
    """The reference under each control of ``correct`` against itself: the
    part the control's limit watches moves, at the tiny size."""
    ids = _prompts([120], seed=9)[0]
    c = file_keys(cfg)
    sound = ref.trace(params, c, ids, 100, 20)
    low = ref.trace(params, c, ids, 100, 20, control)
    if moves == "state":
        d = np.linalg.norm(low["state"] - sound["state"]) / np.linalg.norm(
            sound["state"])
        assert d > (1e-3 if control == "state_bf16" else 0.5)
    elif moves == "chosen":
        assert (low["chosen"] != sound["chosen"]).any()
        np.testing.assert_array_equal(
            np.flatnonzero(low["chosen"][0]), [0, 1, 2, 3])
    else:
        # the store as the choice read it has ONE page of zeros: 2 pooled
        # keys a head at the tiny size
        gone = np.flatnonzero(np.abs(low["pooled"]).sum((1, 2)) == 0)
        assert len(gone) == 2 and gone[0] % 2 == 0
        assert np.abs(sound["pooled"]).sum((1, 2)).min() > 0
    with pytest.raises(ValueError, match="control"):
        ref.trace(params, c, ids, 100, 20, "nothing")
