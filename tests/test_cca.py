"""ZAYA1's decoder (``models/hybrid.py``: compressed convolutional
attention, a K/V pair in pages AND convolution tails in the slot of the
same layer, a top-1 routed MLP behind a router MLP whose latent is carried
from layer to layer, scaled residuals, a tied head) at the ``cca-tiny``
preset on the CPU, in float32, against the benchmark's plain reference
(``benchmark/references/cca_moe.py``: whole sequences, shifted copies for
the convolutions, blocked softmax, every expert on every position).

The limits are float32's: the program and the reference compute the same
sums in another order (a window of tails against shifted copies, a paged
kernel's oracle against a blocked softmax, a sorted grouped matmul against
a loop over the experts), each a few ulps of a value of order 1, through 3
layers: 5e-6 on logits of at most 0.6 in magnitude; readings are 2e-7 to
4e-7. A wrong position, mask, page, tail row, carry or expert moves a
logit by 1e-2 or more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import blocks, cache_spec, decoder, hybrid
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "cca_time0": cfg.cca_time0, "cca_time1": cfg.cca_time1,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_parameters": {"hybrid": {
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta, "rope_type": "default"}},
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "router_hidden_size": cfg.router_hidden_size,
        "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "cca_moe")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("cca-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return decoder.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, page_size=8, max_seq_len=128,
                prompt_buckets=(16, 64), num_pages=80, prefill_chunk=16,
                steps_per_dispatch=4, kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def test_the_tiny_preset_is_three_cca_layers_with_pages_and_a_slot(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [(p.mixer, p.mlp) for p in plan] == [("cca", "moe")] * 3
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (4, 1)
    assert cache_spec.is_stateful(cfg) and not cache_spec.is_uniform(cfg)
    # one layer keeps BOTH: a K/V pair of 2 heads of 16 in pages, and in
    # the slot one row of the 96 mixed channels before and after the first
    # convolution and the 16 of the shifted value half
    spec = cache_spec.cache_spec(cfg)
    assert spec == (cache_spec.PagedAndSlot(
        cache_spec.Paged(2, 2, 16),
        cache_spec.Slot((("latent", (1, 96), jnp.float32),
                         ("mixed", (1, 96), jnp.float32),
                         ("value", (16,), jnp.float32)))),) * 3
    assert cache_spec.pool_index(cfg) == ((0, 0), (1, 1), (2, 2))
    assert cache_spec.paged_bytes_per_token(cfg) == 3 * 2 * 2 * 16 * 4
    assert cache_spec.slot_bytes(cfg) == 3 * (96 + 96 + 16) * 4
    # the engine's features that re-enter a sequence have no kernel for it
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("cca",)
    # the published preset, cut in depth only
    full = decoder.get_config("zaya1-8b-depth12")
    assert cache_spec.paged_bytes_per_token(full) == 12 * 1024
    assert cache_spec.slot_bytes(full) == 12 * 5376
    assert {p.published for p in cache_spec.layer_plan(full)} == set(range(12))


def test_make_pools_gives_a_layer_pages_and_a_slot(cfg):
    paged, state = cache_spec.make_pools(cfg, 10, 8, slots=5,
                                         dtype=jnp.float32)
    assert len(paged) == len(state) == 3
    for (k, v), tails in zip(paged, state):
        assert k.shape == v.shape == (2, 10, 8, 16)
        assert [a.shape for a in tails] == [(5, 1, 96), (5, 1, 96), (5, 16)]


def test_the_ledger_counts_a_page_by_its_pages_not_its_slot(cfg, params):
    eng = _engine(cfg, params, num_pages=40)
    eng._accounted_bytes()
    assert eng.kvledger.page_bytes == 3 * 2 * 2 * 16 * 4 * 8
    assert eng.stateful and eng.prefix_cache is None
    assert eng._step_counters[False] == ()
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(cfg, params, spec_tokens=2)


@pytest.mark.parametrize("length", [5, 37, 64])
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


CHUNK, PAGE = 16, 8


@pytest.mark.parametrize("past", [1, 2, CHUNK - 1])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(
        ref, cfg, params, past):
    """A prompt of two whole chunks and ``past`` tokens: three prefill
    calls, the later ones from the slot's tails and the pages of the ones
    before, then 9 decode steps through pages and tails, each step's
    logits against the reference's full forward of the whole sequence."""
    n_prompt, n_new = 2 * CHUNK + past, 9
    ids = np.asarray(_prompts([n_prompt + n_new], seed=past)[0], np.int32)
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    pages = np.arange(1, 9, dtype=np.int32)      # the row's pages in order
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
    table = np.zeros((2, 8), np.int32)
    table[1] = pages
    live = jnp.array([False, True])
    before = [np.asarray(a[0]) for a in pools[1][0]]
    for t in range(n_prompt, n_prompt + n_new):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.array([0, ids[t]]), jnp.array([0, t]), pools,
            jnp.asarray(table), jnp.array([0, t]), active=live)
        np.testing.assert_allclose(np.asarray(logits[1]), want[t],
                                   atol=LOGIT_TOL, rtol=0)
        # (routed, experts hit, busiest, choices, kda, mla, cca tail rows)
        assert load.tolist() == [3, 3, 3, 3, 0, 0, 3]
    # a row without a request left its tails as they were
    for a, b in zip(pools[1][0], before):
        np.testing.assert_array_equal(np.asarray(a[0]), b)
    # the slot's tails after the last token are the reference's
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), n_prompt, n_new)
    for mine, theirs in zip(hybrid.held_state(cfg, pools[1], 1),
                            tr["states"]):
        np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL)


def test_each_layers_router_is_handed_the_layer_befores_latent_of_its_token(
        cfg, params, monkeypatch):
    """Fails if a layer reads its own latent, another layer's, or another
    token's: every call of the router is recorded, and layer l's carried
    rows must be layer l-1's latents row for row (zeros at layer 0)."""
    calls = []
    real = blocks._latent_route

    def spy(cfg_, x, lp, carried):
        out = real(cfg_, x, lp, carried)
        calls.append((np.asarray(carried), np.asarray(out[2]),
                      np.asarray(x), np.asarray(lp["router_down"]),
                      float(lp["router_gamma"])))
        return out

    monkeypatch.setattr(hybrid, "_latent_route", spy)
    ids = jnp.asarray(_prompts([11])[0])[None]
    hybrid.forward(params, cfg, ids, jnp.arange(11)[None], jnp.ones((1, 11)))
    assert len(calls) == cfg.num_layers
    assert not calls[0][0].any()
    for (carried, _s, _x, _w, _g), (_c, before, *_r) in zip(calls[1:], calls):
        np.testing.assert_array_equal(carried, before)
    for carried, latent, x, w_down, gamma in calls:
        # a token's latent is its own row's projection plus ITS carry
        assert gamma == hybrid.ROUTER_GAMMA
        np.testing.assert_allclose(latent, x @ w_down + gamma * carried,
                                   atol=1e-6)
    # the rows differ from token to token, so a mixed-up row would show
    assert np.abs(calls[1][0][0] - calls[1][0][1]).max() > 1e-3


def test_the_carry_reaches_the_logits(ref, cfg, params):
    """With the carry cut (gamma 0) the logits move, in the program and in
    the reference alike."""
    cut = jax.tree_util.tree_map(lambda a: a, params)
    cut["layers"] = dict(cut["layers"], moe=dict(
        cut["layers"]["moe"],
        router_gamma=jnp.zeros_like(params["layers"]["moe"]["router_gamma"])))
    ids = np.asarray(_prompts([33], seed=3)[0])
    args = (jnp.asarray(ids)[None], jnp.arange(33)[None], jnp.ones((1, 33)))
    with_carry, _ = decoder.forward(params, cfg, *args)
    without, _ = decoder.forward(cut, cfg, *args)
    assert np.abs(np.asarray(with_carry - without)).max() > 1e-3
    np.testing.assert_allclose(
        np.asarray(without[0]), np.asarray(ref.logits(cut, file_keys(cfg), ids)),
        atol=LOGIT_TOL, rtol=0)


def test_top_1_with_the_bias_on_the_choice_only(cfg, params):
    lp = hybrid._layer_params(cfg, params["layers"], 1)[1]
    x = jax.random.normal(jax.random.PRNGKey(5), (9, cfg.hidden_size))
    carried = jax.random.normal(jax.random.PRNGKey(6),
                                (9, cfg.router_hidden_size))
    p0, i0, s0 = blocks._latent_route(cfg, x, lp, carried)
    assert p0.shape == i0.shape == (9, 1)
    # a bias that dwarfs every probability moves every choice to expert 2
    # and leaves the weights the plain probabilities of expert 2
    forced = dict(lp, router_bias=jnp.zeros((4,)).at[2].set(10.0))
    p1, i1, s1 = blocks._latent_route(cfg, x, forced, carried)
    assert (np.asarray(i1) == 2).all()
    assert (np.asarray(p1) > 0).all() and (np.asarray(p1) < 1).all()
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    flat = dict(lp, router_bias=jnp.zeros((4,)))
    p2, i2, _ = blocks._latent_route(cfg, x, flat, carried)
    was_2 = np.asarray(i2)[:, 0] == 2
    assert was_2.any() and not was_2.all()
    np.testing.assert_allclose(np.asarray(p1)[was_2], np.asarray(p2)[was_2])
    # the block's output is the chosen expert's, times that probability
    out, load = blocks._moe_mlp(cfg, x, forced, None, 1,
                                route=(p1, i1))
    w = {k: params["layers"]["moe"][k][1, 2] for k in blocks.EXPERT_KEYS}
    want = p1 * ((jax.nn.silu(x @ w["we_gate"]) * (x @ w["we_up"]))
                 @ w["we_down"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    assert load.tolist() == [9, 1, 9]


@pytest.mark.parametrize("keep,lengths,last", [
    (jnp.float32, [64] * 8, 0), (jnp.bfloat16, [64] * 8, 0),
    (jnp.bfloat16, [40, 64, 90, 64, 40, 90, 64, 90], 24)],
    ids=["float32", "bfloat16", "ragged"])
def test_an_evened_router_bias_evens_the_loads(ref, cfg, params, keep,
                                               lengths, last):
    """The benchmark's ``even_router_bias`` (the reference's own router,
    a sequence at a time): with random weights one expert of four is most
    rows' choice; under the bias it finds, the PROGRAM's router gives every
    expert of every layer the mean load within a tenth on the rows that
    counted (from position 16 on; ``ragged``: each sequence's last 24),
    also when the residual stream is kept in bfloat16 between the
    sublayers (the few rows whose choice that rounding moves stay inside
    the tenth) and for sequences of any lengths."""
    rng = np.random.default_rng(9)
    seqs = [rng.integers(1, 512, size=n) for n in lengths]
    counted = [np.arange(n) >= max(16, n - last if last else 0)
               for n in lengths]
    bias = ref.even_router_bias(params, file_keys(cfg), seqs, 16, last,
                                keep=keep)
    assert bias.shape == (cfg.num_layers, cfg.num_experts)
    assert bias.dtype == jnp.float32

    def loads(tree):
        chosen, route = [], hybrid._latent_route

        def spy(c, x, lp, carried):
            w, i, s = route(c, x, lp, carried)
            chosen.append(np.asarray(i)[:, 0])
            return w, i, s

        hybrid._latent_route = spy
        try:
            for seq in seqs:
                n = len(seq)
                hybrid.run_sequence(tree, cfg, tree["embed"][seq][None],
                                    jnp.arange(n)[None],
                                    jnp.ones((1, n), bool))
        finally:
            hybrid._latent_route = route
        # calls: sequence by sequence, a layer each
        layers = cfg.num_layers
        return [np.bincount(np.concatenate(
            [chosen[j * layers + l][rows] for j, rows in enumerate(counted)]),
            minlength=cfg.num_experts) for l in range(layers)]

    total = sum(int(rows.sum()) for rows in counted)
    mean = total / cfg.num_experts
    flat = dict(params["layers"]["moe"], router_bias=jnp.zeros_like(bias))
    before = loads({**params, "layers": {**params["layers"], "moe": flat}})
    assert max(c.max() for c in before) > 1.3 * mean
    moe = dict(params["layers"]["moe"], router_bias=bias)
    after = loads({**params, "layers": {**params["layers"], "moe": moe}})
    for c in after:
        assert c.sum() == total and abs(c - mean).max() <= 0.1 * mean


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts shorter and longer than a chunk through ``CBEngine``
    (chunked prefill from and to the tails, decode through pages and
    tails): every sampled token's log-probability against the reference's
    score of the same sequence."""
    eng = _engine(cfg, params)
    prompts = _prompts([5, 17, 33, 47], seed=7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=12, stop_token_ids=())
    try:
        outs = eng.generate(prompts, sp)
        info = eng.moe_info()
    finally:
        eng.stop()
    assert eng.chunk_dispatches > 0
    for prompt, out in zip(prompts, outs):
        toks, lps = out["token_ids"], out["logprobs"]
        assert len(toks) == 12
        want, _ent = ref.score(params, file_keys(cfg), prompt + toks, 12)
        np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
    assert info["cca_tail_rows"] > 0 and info["kda_state_rows"] == 0
    assert info["moe_choices"] == info["moe_routed"]


@pytest.mark.parametrize("rows", [9, 33])
def test_one_choice_a_token_by_table_is_the_tiled_path(monkeypatch, cfg,
                                                       params, rows):
    """A decode step's form on a TPU (``blocks._expert_rows``),
    interpreted, with ONE choice a token, routed by the carried latent."""
    from tests.moe_forms import assert_both_forms_agree

    lp = hybrid._layer_params(cfg, params["layers"], 1)[1]
    x = jax.random.normal(jax.random.PRNGKey(5), (rows, cfg.hidden_size))
    carried = jax.random.normal(jax.random.PRNGKey(6),
                                (rows, cfg.router_hidden_size))
    p, i, _ = blocks._latent_route(cfg, x, lp, carried)
    assert_both_forms_agree(monkeypatch, cfg, x, lp, jnp.arange(rows) != 1,
                            1, route=(p, i))
