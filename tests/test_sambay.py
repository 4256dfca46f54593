"""The SambaY decoder (``models/hybrid.py``: Mamba-1 scans with their state
in the slot, window attention over a ring of slot-owned pages, ONE paged
K/V layer that the cross layers read, gated memory units fed inside the
step, differential attention through the paired-128 layout, LayerNorm) at
the ``sambay-tiny`` preset on the CPU, in float32, against the benchmark's
plain reference (``benchmark/references/sambay_diff.py``: whole sequences,
the scan token by token, shifted copies for the convolution, a blocked
softmax over 64-wide heads).

The limits are float32's: the program and the reference compute the same
sums in another order (a ring against a masked whole sequence, the paged
kernel's oracle over 128-wide rows that are half zero against 64-wide
heads, a convolution window against shifted copies), each a few ulps of a
value of order 1, through 12 layers: 5e-6 on logits of at most 0.7 in
magnitude; readings are 2e-7 to 6e-7. A wrong position, mask, page, ring
row, state row or carried ``m`` moves a logit by 1e-2 or more."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import cache_spec, decoder, hybrid, mixers
from polyrl_tpu.models.mixers import diff, ssm
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6
CHUNK, PAGE, WINDOW = 16, 4, 8


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "sliding_window": cfg.sliding_window,
        "mb_per_layer": cfg.mb_per_layer,
        "layer_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "sambay_diff")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("sambay-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """The preset's weights with every bias and skip drawn too (they are
    zero or one as initialised, and a bias that is left out must show)."""
    tree = decoder.init_params(jax.random.PRNGKey(0), cfg)

    def drawn(path, a):
        name = path[-1].key
        if name.endswith("bias") or name in ("bqkv", "bq", "bo", "d_skip",
                                             "sub_norm"):
            key = jax.random.PRNGKey(sum(map(ord, name)))
            return a + 0.1 * jax.random.normal(key, a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(drawn, tree)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, page_size=PAGE, max_seq_len=128,
                prompt_buckets=(16, 64), num_pages=120, prefill_chunk=CHUNK,
                steps_per_dispatch=4, kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def test_the_six_kinds_follow_from_the_published_keys(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [p.mixer for p in plan] == [
        "ssm", "swa", "ssm", "swa", "ssm", "swa", "ssm_mem", "diff",
        "gmu", "cross", "gmu", "cross"]
    assert {p.mlp for p in plan} == {"dense"}
    assert cache_spec.is_stateful(cfg) and not cache_spec.is_uniform(cfg)
    spec = cache_spec.cache_spec(cfg)
    scan = cache_spec.Slot((("state", (4, 128), jnp.float32),
                            ("conv", (3, 128), jnp.float32)))
    ring = cache_spec.Ring(2, 16, 8, jnp.float32)
    assert spec == (scan, ring, scan, ring, scan, ring, scan,
                    cache_spec.Paged(2, 2, 16), None, cache_spec.Reads(7),
                    None, cache_spec.Reads(7))
    # a layer that reads another layer's pages is handed that layer's pool
    assert cache_spec.pool_index(cfg)[7:] == (
        (0, None), (None, None), (0, None), (None, None), (0, None))
    # ONE layer's K/V a token, whatever the depth
    assert cache_spec.paged_bytes_per_token(cfg) == 2 * 2 * 16 * 4
    assert cache_spec.slot_bytes(cfg) == \
        4 * (4 * 128 * 4 + 3 * 128 * 4) + 3 * 2 * 2 * 16 * 8 * 4
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == (
            "cross", "diff", "gmu", "ssm", "ssm_mem", "swa")
    # the published model: 9 scans, 8 windows of 512, layer 17's K/V read
    # by the 7 cross layers, 7 gated memory units
    full = decoder.get_config("phi-4-mini-flash-reasoning")
    kinds = [p.mixer for p in cache_spec.layer_plan(full)]
    assert kinds[:16] == ["ssm", "swa"] * 8
    assert kinds[16:18] == ["ssm_mem", "diff"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert cache_spec.paged_bytes_per_token(full) == 5120
    assert cache_spec.slot_bytes(full) == \
        8 * 5120 * 512 + 9 * (327_680 + 30_720)
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), full))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == 3_852_562_944
    with pytest.raises(ValueError, match="depth cut"):
        cache_spec.layer_plan(decoder.get_config(
            "sambay-tiny", num_layers=2, kept_layers=(0, 1)))


def test_make_pools_gives_a_window_layer_pages_of_the_slots_own(cfg):
    paged, state = cache_spec.make_pools(cfg, 10, PAGE, slots=5,
                                         dtype=jnp.float32)
    assert len(paged) == 1 and len(state) == 7
    assert paged[0][0].shape == paged[0][1].shape == (2, 10, PAGE, 16)
    for l, rows in enumerate(state):
        if l % 2 == 0:
            assert [a.shape for a in rows] == [(5, 4, 128), (5, 3, 128)]
            assert rows[0].dtype == jnp.float32
        else:
            # the null page and two pages of 4 a slot
            assert [a.shape for a in rows] == [(2, 11, PAGE, 16)] * 2
    with pytest.raises(ValueError, match="window"):
        cache_spec.make_pools(cfg, 10, 3, slots=5)


def test_the_ledger_counts_one_layers_bytes_a_page(cfg, params, caplog):
    with caplog.at_level(logging.INFO):
        eng = _engine(cfg, params, num_pages=40, decode_group_share=True,
                      kv_spill=True)
    eng._accounted_bytes()
    assert eng.kvledger.page_bytes == 2 * 2 * 16 * 4 * PAGE
    assert eng.stateful and eng.prefix_cache is None
    assert eng._step_counters[False] == ()
    said = [r.getMessage() for r in caplog.records]
    for feature in ("decode_group_share", "kv_spill"):
        assert any(m.startswith(f"{feature} is off") and "ssm" in m
                   and "swa" in m for m in said)
    with pytest.raises(ValueError, match="spec_tokens.*ssm.*swa"):
        _engine(cfg, params, spec_tokens=2)


@pytest.mark.parametrize("length", [5, WINDOW, 37, 64])
def test_whole_sequence_forward_agrees_with_the_reference(ref, cfg, params,
                                                          length):
    ids = np.asarray(_prompts([length], seed=length)[0])
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids)[None],
                             jnp.arange(length)[None], jnp.ones((1, length)))
    want = ref.logits(params, file_keys(cfg), ids)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


def test_padding_on_the_right_leaves_the_real_positions_alone(cfg, params):
    ids = jnp.asarray(_prompts([24])[0])[None]
    pos = jnp.arange(24)[None]
    whole, _ = decoder.forward(params, cfg, ids, pos, jnp.ones((1, 24)))
    mask = (jnp.arange(24) < 17).astype(jnp.float32)[None]
    cut, _ = decoder.forward(params, cfg, ids.at[:, 17:].set(0), pos, mask)
    np.testing.assert_allclose(np.asarray(cut[0, :17]),
                               np.asarray(whole[0, :17]), atol=LOGIT_TOL)


def _ring_rows(cfg, ring, consumed: int):
    """The rows of a held ring ``ring`` [window, pairs, 2 * 2D] that hold a
    token after ``consumed`` tokens, oldest first."""
    w = cfg.sliding_window
    return np.stack([ring[t % w] for t in range(max(0, consumed - w),
                                                consumed)])


@pytest.mark.parametrize("n_prompt", [3, WINDOW - 1, CHUNK, 2 * CHUNK + 1,
                                      2 * CHUNK + PAGE])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(
        ref, cfg, params, n_prompt):
    """Prompts under the window, of one whole chunk, and past two chunks
    (a page boundary among them): the prefill calls after the first start
    from the slot's state, its rings and the shared pool's pages; then 11
    decode steps (across the ring's wrap and a page boundary) through
    state, rings and pages, each step's logits against the reference's
    full forward of the whole sequence; at the end the slot's state and
    each ring, as a set, are the reference's."""
    n_new = 11
    ids = np.asarray(_prompts([n_prompt + n_new], seed=n_prompt)[0], np.int32)
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    # what the slot's last request left behind must not be read
    pools = jax.tree_util.tree_map(lambda a: a + 7.0, pools)
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
    before = [np.asarray(a[0]) for a in pools[1][0]]
    for t in range(n_prompt, n_prompt + n_new):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.array([0, ids[t]]), jnp.array([0, t]), pools,
            jnp.asarray(table), jnp.array([0, t]), active=live)
        np.testing.assert_allclose(np.asarray(logits[1]), want[t],
                                   atol=LOGIT_TOL, rtol=0)
        # (Mamba layers' rows, keys of the shared pool over its 3 readers,
        # keys of the 3 rings)
        assert load.tolist() == [4, 3 * (t + 1), 3 * min(t + 1, WINDOW)]
    # a row without a request left its state as it was
    for a, b in zip(pools[1][0], before):
        np.testing.assert_array_equal(np.asarray(a[0]), b)
    n = n_prompt + n_new
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), n_prompt, n_new)
    held = hybrid.held_state(cfg, pools[1], 1)
    for mine, theirs in zip(held[0::2], tr["states"]):
        assert mine.shape == theirs.shape == (128, 4)
        np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL)
    for mine, (theirs, first) in zip(held[1::2], tr["rings"]):
        assert first == n - WINDOW and mine.shape == (WINDOW, 2, 32)
        np.testing.assert_allclose(_ring_rows(cfg, mine, n), theirs,
                                   atol=LOGIT_TOL)


@pytest.mark.parametrize("length", [WINDOW - 3, WINDOW, WINDOW + 5])
def test_the_ring_as_a_set_is_the_last_window_tokens(ref, cfg, params,
                                                     length):
    """One prefill call of a sequence shorter than, as long as and longer
    than the window: the rows of a window layer's ring that hold a token
    are the reference's K and V of the last ``window`` tokens, and a row
    that holds none is what it was."""
    ids = np.asarray(_prompts([length], seed=40 + length)[0], np.int32)
    pools = decoder.make_paged_pools(cfg, 12, PAGE, dtype=jnp.float32,
                                     slots=3)
    pools = jax.tree_util.tree_map(lambda a: a + 3.0, pools)
    chunk = np.zeros((1, CHUNK), np.int32)
    chunk[0, :length] = ids
    pools, _ = hybrid.prefill(
        params, cfg, jnp.asarray(chunk), jnp.array([length]), jnp.int32(0),
        pools, jnp.zeros((1, 0), jnp.int32),
        jnp.arange(1, 1 + CHUNK // PAGE, dtype=jnp.int32)[None],
        jnp.array([2]))
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), length - 1, 1)
    held = [hybrid.held_state(cfg, pools[1], slot)[1::2] for slot in range(3)]
    for l, (theirs, first) in enumerate(tr["rings"]):
        mine = held[2][l]
        assert first == max(0, length - WINDOW)
        np.testing.assert_allclose(_ring_rows(cfg, mine, length), theirs,
                                   atol=LOGIT_TOL)
        untouched = [r for r in range(WINDOW) if r >= length]
        assert (mine[untouched] == 3.0).all()
        # and no other slot's pages were written
        for other in (0, 1):
            assert (held[other][l] == 3.0).all()


def test_the_paired_layout_is_the_64_wide_form_bit_for_bit(cfg):
    """Differential heads through one softmax a row of 2D: a query ``(q0 |
    0)`` against a pair's ``[k0 | k1]`` scores ``q0 k0`` and returns ``a1
    [v0 | v1]``: bit for bit, in float32, what the same attention gives
    over heads of D (each K head on its own, the two value halves one
    after the other)."""
    from polyrl_tpu.ops.paged_attention import paged_attention_ref

    hd, pairs, width = cache_spec.diff_dims(cfg)
    d, s = width // 2, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (s, hd, 2, d))
    k = jax.random.normal(keys[1], (pairs, 4, PAGE, width))
    v = jax.random.normal(keys[2], (pairs, 4, PAGE, width))
    table = jnp.asarray([[1, 2, 3], [2, 3, 1], [3, 1, 2]], jnp.int32)
    lens = jnp.asarray([11, 5, 1], jnp.int32)
    got = paged_attention_ref(diff.paired_queries(q), k, v, table, lens,
                              d ** -0.5).reshape(s, hd, 2, width)
    # the 2 x D form: K head (g, c) on its own; query (j, c) belongs to it
    g = hd // pairs
    k64 = k.reshape(pairs, 4, PAGE, 2, d).transpose(0, 3, 1, 2, 4).reshape(
        2 * pairs, 4, PAGE, d)
    q64 = q.reshape(s, pairs, g, 2, d).swapaxes(2, 3).reshape(s, 2 * hd, d)
    halves = []
    for half in range(2):
        v64 = jnp.repeat(v[..., half * d:(half + 1) * d], 2, axis=0)
        o = paged_attention_ref(q64, k64, v64, table, lens, d ** -0.5)
        halves.append(o.reshape(s, pairs, 2, g, d).swapaxes(2, 3))
    want = jnp.concatenate(halves, axis=-1).reshape(s, hd, 2, width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got[0, :, 0] - got[0, :, 1])).min() > 0


def test_a_gated_memory_unit_reads_the_same_tokens_scan_output(cfg, params,
                                                               monkeypatch):
    """Every gated memory unit of a call is handed the ``ssm_mem`` layer's
    ``m`` of that call, row for row, in a whole-sequence forward and in a
    decode step alike."""
    made, read = [], []

    def spy_scan(hand):
        def run(cfg, p, lp, h_in, ctx):
            out, kept = ssm.sequence(cfg, p, lp, h_in, ctx, hand=True)
            made.append(np.asarray(kept.hands["m"]))
            return out, kept if hand else kept._replace(hands={})
        return run

    def spy_gmu(cfg, p, lp, h_in, ctx):
        read.append(np.asarray(ctx.hands["m"]))
        return ssm.gmu(cfg, p, lp, h_in, ctx)

    for name, form in (("ssm", spy_scan(False)), ("ssm_mem", spy_scan(True)),
                       ("gmu", spy_gmu)):
        monkeypatch.setitem(mixers.MIXERS, name, dataclasses.replace(
            mixers.MIXERS[name], sequence=form))
    ids = jnp.asarray(_prompts([13])[0])[None]
    hybrid.forward(params, cfg, ids, jnp.arange(13)[None], jnp.ones((1, 13)))
    assert len(made) == 4 and len(read) == 2
    for m in read:
        np.testing.assert_array_equal(m, made[3])
    assert np.abs(made[3][0, 0] - made[3][0, 1]).max() > 1e-3
    assert np.abs(made[3] - made[2]).max() > 1e-3


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts shorter than the window, longer than a chunk and across a
    page boundary through ``CBEngine`` (chunked prefill from and to state,
    rings and pages, the fused multi-step decode dispatch): every sampled
    token's log-probability against the reference's score of the same
    sequence; the profiler's three counters against the client's count."""
    eng = _engine(cfg, params)
    prompts = _prompts([5, 17, 33, 47], seed=7)
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
    assert counted["ssm_state_rows"] == 4 * len(steps)
    assert counted["shared_kv_rows_read"] == 3 * sum(steps)
    assert counted["window_rows_read"] == 3 * sum(min(n, WINDOW)
                                                  for n in steps)


def test_a_reused_slot_starts_from_a_zero_state_and_an_empty_ring(ref, cfg,
                                                                  params):
    """One slot, two requests after each other: the second's
    log-probabilities are the reference's, whatever the first left in the
    slot's state rows and rings."""
    eng = _engine(cfg, params, max_slots=1)
    sp = SamplingParams(temperature=1.0, max_new_tokens=6, stop_token_ids=())
    first, second = _prompts([21, 6], seed=11)
    try:
        eng.generate([first], sp)
        left = [np.asarray(rows[0]).copy() for rows in eng._pools[1]]
        out = eng.generate([second], sp)[0]
    finally:
        eng.stop()
    assert all(np.abs(a).max() > 0 for a in left)
    want, _ = ref.score(params, file_keys(cfg), second + out["token_ids"], 6)
    np.testing.assert_allclose(out["logprobs"], want, atol=LOGP_TOL, rtol=0)


def test_recurrent_state_reads_a_running_requests_slot(ref, cfg, params):
    """``CBEngine.recurrent_state``: the Mamba layers' states ``[I, N]``
    and the window layers' rings of a request that is decoding, in layer
    order, against the reference after the tokens it has consumed."""
    import threading

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
    assert len(rows) == 7
    for mine, theirs in zip(rows[0::2], tr["states"]):
        np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL)
    for mine, (theirs, _first) in zip(rows[1::2], tr["rings"]):
        np.testing.assert_allclose(_ring_rows(cfg, mine, consumed), theirs,
                                   atol=LOGIT_TOL)
