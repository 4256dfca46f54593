"""A looped language model (``models/hybrid.py``: ONE stack of ``gqa``
layers with a norm after each sublayer too, run ``ut_steps`` times a token
with the final norm between passes, each pass keeping keys and values of
its own in the same logical pages; ``cache_spec.passes``) at the
``ouro-tiny`` preset on the CPU, in float32, against the benchmark's plain
reference (``benchmark/references/looped_gqa.py``: whole sequences, the
passes a Python loop over a Python loop of layers, K/V as ``[T][L]``
lists).

The limits are float32's: the program and the reference compute the same
sums in another order (pages against a whole sequence, the paged kernel's
oracle against a masked softmax), each a few ulps of a value of order 1,
through 3 x 3 layers: 5e-6 on logits of at most 0.5 in magnitude. A pass
that reads another pass's pages moves a logit by 1e-3 or more
(``test_a_crossed_table_fails``)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import costs_looped, harness
from polyrl_tpu.models import cache_spec, decoder, hf_loader, hybrid
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6
CHUNK, PAGE = 16, 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    return {
        "model_type": "ouro", "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "total_ut_steps": cfg.ut_steps, "early_exit_threshold": 1.0}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "looped_gqa")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("ouro-tiny", dtype=jnp.float32)


def _uneven(cfg, rng):
    """The preset's weights with every norm's drawn about 1 (at 1 a norm
    left out, or one layer's in another's place, would not show) and the
    matrices four times as wide (at 0.02 attention is uniform)."""
    tree = decoder.init_params(rng, cfg)

    def drawn(path, a):
        name = path[-1].key
        if "norm" in name:
            key = jax.random.fold_in(rng, sum(map(ord, name)))
            return a * (1.0 + 0.3 * jax.random.normal(key, a.shape, a.dtype))
        return a * 4.0

    return jax.tree_util.tree_map_with_path(drawn, tree)


@pytest.fixture(scope="module")
def params(cfg):
    return _uneven(cfg, jax.random.PRNGKey(0))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, page_size=PAGE, max_seq_len=128,
                prompt_buckets=(16, 64), num_pages=120, prefill_chunk=CHUNK,
                steps_per_dispatch=4, kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def test_a_layer_of_the_plan_is_a_layer_of_weights(cfg):
    """Three entries in the plan, one stack of three, and in pages three
    passes' worth a layer; the engine's two questions."""
    plan = cache_spec.layer_plan(cfg)
    assert [(p.mixer, p.mlp) for p in plan] == [("gqa", "dense")] * 3
    assert hybrid.kind_index(cfg) == [(0, 0), (1, 1), (2, 2)]
    assert cache_spec.passes(cfg) == 3
    assert cache_spec.cache_spec(cfg) == (cache_spec.Paged(2, 4, 16, 3),) * 3
    assert not cache_spec.is_uniform(cfg) and not cache_spec.is_stateful(cfg)
    once = dataclasses.replace(cfg, ut_steps=1)
    assert cache_spec.paged_bytes_per_token(cfg) \
        == 3 * cache_spec.paged_bytes_per_token(once) == 3 * 3 * 2 * 4 * 16 * 4
    paged, state = decoder.make_paged_pools(cfg, 10, PAGE, slots=3)
    assert state == () and len(paged) == 3
    assert {a.shape for pair in paged for a in pair} == {(4, 30, PAGE, 16)}
    assert cache_spec.pass_offset(cfg, paged[0][0], 2) == 20
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("gqa",)
    assert hybrid.load_names(cfg) == ("paged_rows_read", *hybrid.UT_LOAD)
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    assert tree["exit_gate"]["w"].shape == (64, 1)
    assert tree["exit_gate"]["b"].shape == (1,)
    assert tree["layers"]["gqa"]["wqkv"].shape == (3, 64, 3 * 4 * 16)
    assert {k for k in tree["layers"] if "norm" in k} == {
        "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"}
    with pytest.raises(NotImplementedError, match="run 3 times a token"):
        cache_spec.cache_spec(dataclasses.replace(cfg, cca_time0=2,
                                                  cca_time1=2))


def test_the_published_tree_counts_2_667_974_657_parameters():
    """From shapes alone: 2,667,577,344 in matrices, 393,216 in the
    layers' norms, 2,048 in the final norm, 2,049 in the gate."""
    tree = jax.eval_shape(lambda: decoder.init_params(
        jax.random.PRNGKey(0), decoder.get_config("ouro-2.6b")))
    sizes = {jax.tree_util.keystr(path): math.prod(a.shape) for path, a
             in jax.tree_util.tree_leaves_with_path(tree)}
    norms = sum(n for k, n in sizes.items() if "norm" in k)
    gate = sum(n for k, n in sizes.items() if "exit_gate" in k)
    assert sum(sizes.values()) == 2_667_974_657
    assert (norms, gate) == (393_216 + 2_048, 2_049)
    assert sum(sizes.values()) - norms - gate == 2_667_577_344
    assert cache_spec.paged_bytes_per_token(
        decoder.get_config("ouro-2.6b")) == 1_572_864


def test_the_preset_is_the_loader_of_the_benchmark_files_keys():
    """``hf_loader.ouro_config`` of the published keys, as the benchmark's
    configuration file holds them, is the preset, key for key; what is not
    built is refused in words."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        keys = json.load(f)
    assert hf_loader.ouro_config(keys) == decoder.get_config("ouro-2.6b")
    with pytest.raises(NotImplementedError, match="leave the loop"):
        hf_loader.ouro_config({**keys, "early_exit_threshold": 0.9})
    with pytest.raises(ValueError, match="at least once"):
        hf_loader.ouro_config({**keys, "total_ut_steps": 0})
    with pytest.raises(NotImplementedError, match="window layers"):
        hf_loader.ouro_config({**keys, "sliding_window": 4096})
    with pytest.raises(NotImplementedError, match="ouro"):
        hf_loader.load_hf_params("/nowhere", cfg=decoder.get_config(
            "ouro-tiny"))


@pytest.mark.parametrize("length", [5, 16, 37, 64])
def test_whole_sequence_forward_agrees_with_the_reference(ref, cfg, params,
                                                          length):
    """``hybrid.run_sequence`` (the trainer's forward): the passes a scan
    over the plan."""
    ids = np.asarray(_prompts([length], seed=length)[0], np.int32)
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    pos = jnp.arange(length, dtype=jnp.int32)[None]
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids)[None], pos,
                             jnp.ones((1, length), jnp.int32))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=LOGIT_TOL,
                               rtol=0)
    x, states, kept = hybrid.run_sequence(
        params, cfg, params["embed"][jnp.asarray(ids)][None], pos,
        jnp.ones((1, length), bool))
    # what a chunk keeps comes stacked, a pass a leading row
    assert states == [] and len(kept) == 3
    assert kept[0][0].shape == (3, 1, length, 4, 16)
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), 1, length - 1)
    for t, (first, last) in enumerate(tr["pass_kv"]):
        for (k, v), theirs in ((kept[0], first), (kept[2], last)):
            mine = np.concatenate([np.asarray(k[t, 0]), np.asarray(v[t, 0])],
                                  axis=-1)
            np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL)


def test_one_pass_is_the_plain_path_with_the_sandwich_norms(ref, cfg, params):
    """``ut_steps`` 1 at the same sizes: no loop, no norm between passes,
    the plain decoder's equations with four norms a layer."""
    once = dataclasses.replace(cfg, ut_steps=1)
    tree = {k: v for k, v in params.items() if k != "exit_gate"}
    assert jax.tree_util.tree_structure(tree) == jax.tree_util.tree_structure(
        jax.eval_shape(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                   once)))
    ids = np.asarray(_prompts([29], seed=3)[0], np.int32)
    want = np.asarray(ref.logits(tree, file_keys(once), ids))
    got, _ = decoder.forward(tree, once, jnp.asarray(ids)[None],
                             jnp.arange(29, dtype=jnp.int32)[None],
                             jnp.ones((1, 29), jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=LOGIT_TOL,
                               rtol=0)
    # and it is another model than three passes of the same weights
    thrice = np.asarray(ref.logits(params, file_keys(cfg), ids))
    assert np.abs(thrice - want).max() > 1e-2
    text = jax.jit(lambda p, i: decoder.forward(
        p, once, i, jnp.arange(29, dtype=jnp.int32)[None],
        jnp.ones((1, 29), jnp.int32))).lower(
            tree, jnp.asarray(ids)[None]).as_text(debug_info=True)
    assert "ut_pass" not in text and "ut_norm" not in text


def test_padding_on_the_right_leaves_the_real_positions_alone(cfg, params):
    ids = np.asarray(_prompts([21], seed=1)[0], np.int32)
    padded = np.concatenate([ids, np.zeros(11, np.int32)])
    pos = jnp.arange(32, dtype=jnp.int32)[None]
    whole, _ = decoder.forward(params, cfg, jnp.asarray(ids)[None],
                               pos[:, :21], jnp.ones((1, 21), jnp.int32))
    mask = (jnp.arange(32) < 21).astype(jnp.int32)[None]
    got, _ = decoder.forward(params, cfg, jnp.asarray(padded)[None], pos,
                             mask)
    np.testing.assert_allclose(np.asarray(got[0, :21]), np.asarray(whole[0]),
                               atol=LOGIT_TOL, rtol=0)


def _prefill(cfg, params, pools, ids, n_prompt, pages):
    """``ids[:n_prompt]`` through ``hybrid.prefill`` in chunks of ``CHUNK``
    into the pages ``pages``: (pools, each chunk's (last position,
    last-token logits))."""
    per, seen = CHUNK // PAGE, []
    for at in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - at)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :n] = ids[at:at + n]
        done = at // PAGE
        pools, logits = hybrid.prefill(
            params, cfg, jnp.asarray(chunk), jnp.array([n]), jnp.int32(at),
            pools, jnp.asarray(pages[None, :done]),
            jnp.asarray(pages[None, done:done + per]), jnp.array([0]))
        seen.append((at + n - 1, np.asarray(logits[0])))
    return pools, seen


def _decode(cfg, params, pools, ids, start, stop, pages):
    """Tokens ``ids[start:stop]`` one a step through the row 1 of two (row
    0 has no request), by the function the engine's step calls: (pools,
    each step's (logits of row 1, load))."""
    table = np.zeros((2, len(pages)), np.int32)
    table[1] = pages
    live = jnp.array([False, True])
    seen = []
    for t in range(start, stop):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.array([0, ids[t]]), jnp.array([0, t]), pools,
            jnp.asarray(table), jnp.array([0, t]), active=live)
        seen.append((np.asarray(logits[1]), dict(zip(
            hybrid.load_names(cfg), load.tolist()))))
    return pools, seen


def _pass_rows(cfg, pool, pages, t: int, n: int):
    """What pass ``t``'s pages of the logical pages ``pages`` hold of a
    sequence's first ``n`` tokens in one layer's K/V pair ``pool``: the
    rows ``[k | v]`` [n, H, 2D]."""
    at = np.asarray(pages) + int(cache_spec.pass_offset(cfg, pool[0], t))
    k, v = (np.asarray(a)[:, at].transpose(1, 2, 0, 3).reshape(
        -1, a.shape[0], a.shape[3])[:n] for a in pool)
    return np.concatenate([k, v], axis=-1)


N_NEW = 24


@pytest.fixture(scope="module")
def served(ref, cfg, params):
    """A prompt of two chunks and a token prefilled over its prefix in
    pages, then 24 decode steps: (ids, the reference's logits, what each
    chunk and each step gave, the pools at the end, the pages)."""
    n_prompt = 2 * CHUNK + 1
    ids = np.asarray(_prompts([n_prompt + N_NEW], seed=5)[0], np.int32)
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    # whatever the pages' last owner left behind must not be read
    pools = jax.tree_util.tree_map(lambda a: a + 7.0, pools)
    pages = np.arange(1, 17, dtype=np.int32)[::-1].copy()
    pools, chunks = _prefill(cfg, params, pools, ids, n_prompt, pages)
    pools, steps = _decode(cfg, params, pools, ids, n_prompt,
                           n_prompt + N_NEW, pages)
    return ids, want, chunks, steps, pools, pages, n_prompt


def test_chunked_prefill_over_a_prefix_then_decode_agrees_with_the_full_forward(
        served):
    """Three prefill calls (the second and third gather each pass's prefix
    from that pass's pages) and 24 decode steps across six page
    boundaries: every call's logits against the reference's full forward
    of the whole sequence."""
    ids, want, chunks, steps, _pools, _pages, n_prompt = served
    assert [at for at, _ in chunks] == [15, 31, 32]
    for at, logits in chunks:
        np.testing.assert_allclose(logits, want[at], atol=LOGIT_TOL, rtol=0)
    for t, (logits, load) in zip(range(n_prompt, n_prompt + N_NEW), steps):
        np.testing.assert_allclose(logits, want[t], atol=LOGIT_TOL, rtol=0)
        # a live row, three passes; each over the t + 1 keys of its own,
        # in three layers
        assert load == {"ut_passes": 3, "kv_pass_rows_read": 3 * (t + 1),
                        "paged_rows_read": 3 * 3 * (t + 1)}


def test_the_pages_of_a_pass_hold_that_passs_keys(ref, cfg, params, served):
    """Every pass's pages of the first and the last layer against the
    reference's rotated keys and values of that pass; and no pass's are
    another's."""
    ids, _want, _chunks, _steps, pools, pages, n_prompt = served
    n = n_prompt + N_NEW
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), n_prompt, N_NEW)
    for t, (first, last) in enumerate(tr["pass_kv"]):
        for pool, theirs in ((pools[0][0], first), (pools[0][2], last)):
            mine = _pass_rows(cfg, pool, pages, t, n)
            np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL)
            other = _pass_rows(cfg, pool, pages, (t + 1) % 3, n)
            assert np.abs(other - theirs).max() > 1e-2
    # the row without a request wrote nowhere but to the passes' null pages
    for k, v in pools[0]:
        rest = np.setdiff1d(np.arange(24), [0, *pages])
        for t in range(3):
            at = rest + int(cache_spec.pass_offset(cfg, k, t))
            assert (np.asarray(k)[:, at] == 7.0).all()
            assert (np.asarray(v)[:, at] == 7.0).all()


def test_a_crossed_table_fails(monkeypatch, cfg, params, served):
    """Every pass sent to pass 0's pages (the shared-cache shortcut): the
    decode step's logits leave the reference's by far more than float32's
    rounding."""
    ids, want, _chunks, _steps, pools, pages, n_prompt = served
    monkeypatch.setattr(cache_spec, "pass_offset",
                        lambda cfg, pool, t: t * 0)
    n = n_prompt + N_NEW
    _pools, steps = _decode(cfg, params, pools, ids, n - 1, n, pages)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(steps[0][0], want[n - 1], atol=LOGIT_TOL,
                                   rtol=0)
    assert np.abs(steps[0][0] - want[n - 1]).max() > 1e-3


def test_the_three_counts_of_a_pages_bytes_agree(cfg, params):
    """``paged_bytes_per_token``, the ledger's ``page_bytes`` and the
    benchmark's pages from ``kv_pool_bytes``: each three passes' worth of
    the one-pass figure."""
    once = 3 * 2 * 4 * 16 * 4        # layers x (K, V) x heads x width x 4 B
    assert cache_spec.paged_bytes_per_token(cfg, jnp.float32) == 3 * once
    keys = file_keys(cfg)
    assert costs_looped.paged_bytes_per_token(keys, itemsize=4) == 3 * once
    eng = _engine(cfg, params, num_pages=40)
    try:
        eng.kv_memory_info()
        assert eng.kvledger.page_bytes == 3 * once * PAGE
        paged = jax.tree_util.tree_leaves(eng._pools[0])
        assert sum(a.nbytes for a in paged) == 40 * 3 * once * PAGE
    finally:
        eng.stop()
    pool = 39 * 3 * once * PAGE + 5
    assert pool // (costs_looped.paged_bytes_per_token(keys, itemsize=4)
                    * PAGE) + 1 == 40


def _logprobs_of(eng, prompt, n, **kw):
    q = eng.submit(kw.pop("rid", f"r{id(prompt)}{n}{len(kw)}"), prompt,
                   SamplingParams(temperature=0.0, max_new_tokens=n,
                                  stop_token_ids=()), **kw)
    toks, lps = [], []
    while True:
        item = q.get(timeout=120)
        if not isinstance(item, dict):
            return toks, lps
        toks += item["token_ids"]
        lps += item["logprobs"]


def test_what_acts_on_pages_runs_at_three_passes_a_page(ref, cfg, params):
    """Pages only, so everything that acts on pages stays on: the prefix
    cache hits and publishes, a group's siblings attach to their prompt's
    pages, each with the log-probabilities of a cold prefill; what needs a
    GQA kernel that knows nothing of passes is refused or off."""
    with pytest.raises(ValueError, match=r"spec_tokens.*gqa layers"):
        _engine(cfg, params, spec_tokens=2)
    eng = _engine(cfg, params, kv_spill=True).start()
    try:
        assert not eng.stateful and eng.prefix_cache is not None
        assert eng.kvspill is None
        prompt = _prompts([40], seed=7)[0]
        cold = _logprobs_of(eng, prompt, 6, rid="cold")
        assert eng.chunk_dispatches == 2
        assert (eng.prefix_cache.req_hits, eng.prefix_cache.req_misses) == \
            (0, 1)
        warm = _logprobs_of(eng, prompt, 6, rid="warm")
        assert eng.prefix_cache.req_hits == 1
        assert eng.chunk_dispatches == 2          # nothing prefilled again
        assert warm[0] == cold[0]
        want, _ = ref.score(params, file_keys(cfg), prompt + cold[0], 6)
        for toks, lps in (cold, warm):
            np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
        # a GRPO group of 3 of a new prompt: the leader prefills, the
        # siblings attach to its published pages in one wave
        other = _prompts([41], seed=8)[0]
        before = eng.chunk_dispatches
        qs = [eng.submit(f"g{i}", other, SamplingParams(
            temperature=0.0, max_new_tokens=6, stop_token_ids=()),
            group_id="g", group_size=3) for i in range(3)]
        outs = []
        for q in qs:
            toks, lps = [], []
            while True:
                item = q.get(timeout=120)
                if not isinstance(item, dict):
                    break
                toks += item["token_ids"]
                lps += item["logprobs"]
            outs.append((toks, lps))
        assert eng.group_forked_requests == 2
        assert eng.sibling_attach_dispatches >= 1
        assert eng.chunk_dispatches - before == 2     # the leader's alone
        want, _ = ref.score(params, file_keys(cfg), other + outs[0][0], 6)
        for toks, lps in outs:
            assert toks == outs[0][0]
            np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
        assert eng.recoveries == 0
    finally:
        eng.stop()


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts shorter than a chunk, longer than one and across a page
    boundary through ``CBEngine`` (chunked prefill, the fused multi-step
    decode dispatch): every sampled token's log-probability against the
    reference's score of the same sequence; the profiler's counters
    against the client's count."""
    eng = _engine(cfg, params)
    prompts = _prompts([5, 17, 33, 47], seed=7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=12, stop_token_ids=())
    try:
        outs = eng.generate(prompts, sp)
        counted = eng.profiler.counters()
    finally:
        eng.stop()
    assert eng.chunk_dispatches > 0 and eng.moe_info() == {}
    for prompt, out in zip(prompts, outs):
        toks, lps = out["token_ids"], out["logprobs"]
        assert len(toks) == 12
        want, _ent = ref.score(params, file_keys(cfg), prompt + toks, 12)
        np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
    # a request's decode steps: its 2nd to 12th token (the first is the
    # prefill's), each over the keys before it and itself
    steps = [(len(p) + i + 1) for p in prompts for i in range(11)]
    assert counted["ut_passes"] == 3 * len(steps)
    assert counted["kv_pass_rows_read"] == 3 * sum(steps)
    assert counted["paged_rows_read"] == 3 * 3 * sum(steps)


def test_the_passes_are_a_loop_of_the_program(cfg, params):
    """The decode step's and the prefill chunk's lowered text hold the
    layers' bodies once, inside a ``while`` over the passes, under the
    scopes the per-layer metrics read."""
    pools = jax.eval_shape(lambda: decoder.make_paged_pools(
        cfg, 24, PAGE, dtype=jnp.float32, slots=3))

    def step(params, pools):
        return decoder.forward_paged_decode(
            params, cfg, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), pools, jnp.zeros((2, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32))

    def chunk(params, pools):
        return hybrid.prefill(
            params, cfg, jnp.zeros((1, CHUNK), jnp.int32), jnp.array([CHUNK]),
            jnp.int32(CHUNK), pools, jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, 4), jnp.int32), jnp.array([0]))

    for fn in (step, chunk):
        text = jax.jit(fn).lower(params, pools).as_text(debug_info=True)
        assert text.count("stablehlo.while") == 1 if fn is step else True
        # a layer's three MLP products and two attention products, once a
        # layer of the stack whatever the passes
        assert text.count("stablehlo.dot_general") < 2 * 3 * (2 + 3 + 2)
        for scope in ("ut_pass", "ut_norm", "ut_pass/attn_qkv",
                      "ut_pass/attn_core", "ut_pass/attn_out", "ut_pass/mlp",
                      "head"):
            assert scope in text, scope
