"""The hybrid family (``models/hybrid.py``: KDA layers, an MLA layer, a
routed MLP with a sigmoid router behind a dense layer) at the
``hybrid-tiny`` preset on the CPU, in float32, against the benchmark's
plain reference (``benchmark/references/hybrid_kda_mla_moe.py``: the
token-by-token recurrence, the expanded attention, every expert on every
position).

The limits are float32's: the program and the reference compute the same
sums in another order (a chunked form against a recurrence, an absorbed
product against an expanded one, a sorted grouped matmul against a loop
over the experts), each a few ulps of a value of order 1, through 3
layers: 5e-6 on logits of at most 0.7 in magnitude; readings are 5e-7. A
wrong position, mask, state row, page or expert moves a logit by 1e-2 or
more."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import blocks, cache_spec, decoder, hybrid
from polyrl_tpu.models.mixers import base, kda, mla
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    plan = cache_spec.layer_plan(cfg)
    first, held = cache_spec.experts_held(cfg)
    return {
        "num_hidden_layers": cfg.num_layers,
        "kept_layers": [p.published for p in plan],
        "layer_group_size": cfg.layer_group_size,
        "first_k_dense_replace": sum(p.mlp == "dense" for p in plan),
        "num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim_,
        "kda_lower_bound": cfg.kda_lower_bound,
        "rms_norm_eps": cfg.rms_norm_eps,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta, "experts_held": [first, held],
        "num_experts": held, "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "hybrid_kda_mla_moe")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("hybrid-tiny", dtype=jnp.float32)


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


def test_the_tiny_preset_is_two_kda_layers_and_an_mla_layer(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [(p.mixer, p.mlp) for p in plan] == [
        ("kda", "dense"), ("kda", "moe"), ("mla", "moe")]
    assert (cfg.num_experts, cfg.n_group, cache_spec.experts_held(cfg)) == \
        (16, 4, (0, 4))
    assert cache_spec.is_stateful(cfg) and not cache_spec.is_uniform(cfg)
    # every preset from before the family is the uniform pattern
    for name in ("tiny", "moe-tiny", "qwen2.5-7b", "qwen3-30b-a3b"):
        old = decoder.get_config(name)
        assert cache_spec.is_uniform(old) and not cache_spec.is_stateful(old)
        assert {p.mixer for p in cache_spec.layer_plan(old)} == {"gqa"}
    # what a sequence keeps: pages for the latent, a slot for the state
    spec = cache_spec.cache_spec(cfg)
    assert [type(c).__name__ for c in spec] == ["Slot", "Slot", "Paged"]
    assert spec[2] == cache_spec.Paged(1, 1, 128)   # 32 + 8 in whole lanes
    assert cache_spec.paged_bytes_per_token(cfg) == 128 * 4
    assert cache_spec.slot_bytes(cfg) == 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)


def test_forward_is_the_references_forward(cfg, params, ref):
    ids = np.asarray(_prompts([45], seed=1)[0])
    pos = jnp.arange(45)[None]
    got, cache = decoder.forward(params, cfg, jnp.asarray(ids)[None], pos,
                                 jnp.ones((1, 45)))
    want = ref.logits(params, file_keys(cfg), ids)
    assert cache is None
    assert float(jnp.abs(got[0] - want).max()) < LOGIT_TOL
    assert float(jnp.abs(want).max()) > 0.3
    # padding on the left: a state starts from zero at the first real token
    left = jnp.concatenate([jnp.zeros((1, 7), jnp.int32),
                            jnp.asarray(ids)[None]], axis=1)
    mask = jnp.concatenate([jnp.zeros((1, 7)), jnp.ones((1, 45))], axis=1)
    pos = jnp.maximum(jnp.arange(52) - 7, 0)[None]
    got, _ = decoder.forward(params, cfg, left, pos, mask, remat=True)
    assert float(jnp.abs(got[0, 7:] - want).max()) < LOGIT_TOL


def test_forward_has_a_gradient(cfg, params):
    """All the trainer gets in this PR: the chunked form is plain
    ``jax.numpy``."""
    ids = jnp.asarray(_prompts([20], seed=2))
    pos, mask = jnp.arange(20)[None], jnp.ones((1, 20))

    def loss(p):
        logits, _ = decoder.forward(p, cfg, ids, pos, mask)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    grads = jax.grad(loss)(params)
    norms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).max()), grads)
    assert all(np.isfinite(v) for v in jax.tree_util.tree_leaves(norms))
    assert norms["layers"]["kda"]["wf"] > 0 and norms["layers"]["mla"]["wkv_b"] > 0
    assert norms["layers"]["moe"]["we_down"] > 0


def test_kda_chunked_form_is_the_recurrence():
    b, t, h, d = 2, 48, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    q = base.l2norm(jax.random.normal(ks[0], (b, t, h, d)))
    k = base.l2norm(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    # decays from none to the bound of -5 a position
    g = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, d)) * 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d)) * 0.1
    s1, o1 = kda.kda_chunked(s0, q, k, v, g, beta, 16)
    s, outs = s0, []
    for i in range(t):
        s, o = kda.kda_recurrent_step(s, q[:, i], k[:, i], v[:, i],
                                         g[:, i], beta[:, i])
        outs.append(o)
    assert float(jnp.abs(o1 - jnp.stack(outs, 1)).max()) < 5e-6
    assert float(jnp.abs(s1 - s).max()) < 5e-6
    # the bound of -5 a position over a step of 16 stays inside float32
    worst = jnp.full_like(g, -5.0)
    s2, o2 = kda.kda_chunked(s0, q, k, v, worst, beta, 16)
    assert bool(jnp.isfinite(o2).all() and jnp.isfinite(s2).all())


def test_absorbed_mla_is_the_expanded_form(cfg, params):
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["mla"])
    t = 21
    h_in = jax.random.normal(jax.random.PRNGKey(3), (1, t, cfg.hidden_size))
    pos = jnp.arange(t)[None]
    q_nope, q_rope, lat = mla._mla_qkv(cfg, lp, h_in, pos)
    assert lat.shape == (1, t, 128) and not bool(jnp.any(lat[..., 40:]))
    want = mla.mla_expanded(cfg, lp, q_nope, q_rope, lat,
                               jnp.ones((1, t), bool), pos)[0, -1]
    # the last token as a decode step over pages of 8
    from polyrl_tpu.ops.mla_attention import (latent_paged_attention_pallas,
                                              latent_paged_attention_ref)

    pool = jnp.zeros((1, 6, 8, 128)).at[0, 1:4].set(
        jnp.pad(lat[0], ((0, 3), (0, 0))).reshape(3, 8, 128))
    table = jnp.asarray([[1, 2, 3, 0], [0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([t, 0], jnp.int32)
    q_lat = mla.mla_absorb(cfg, lp, q_nope[0, -1:], q_rope[0, -1:])
    q_lat = jnp.concatenate([q_lat, q_lat])
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    for fn in (latent_paged_attention_ref,
               lambda *a: latent_paged_attention_pallas(*a, interpret=True)):
        o_lat = fn(q_lat, pool, table, lens, cfg.kv_lora_rank, scale)
        got = mla.mla_unabsorb(cfg, lp, o_lat)
        assert float(jnp.abs(got[0] - want).max()) < 2e-6
        assert not bool(jnp.any(got[1]))          # a row without a request


def test_prefill_then_paged_decode_gives_the_references_logits(cfg, params,
                                                               ref):
    """The engine's device functions without the engine: a 40-token prompt
    in chunks of 16, 16 and 8 through ``prefill_suffix_into_pages`` (state
    and latent pages carried from chunk to chunk), then 5 tokens through
    ``forward_paged_decode``, in slot 2 of 3 beside an empty row."""
    seq = _prompts([45], seed=4)[0]
    want = ref.logits(params, file_keys(cfg), seq)
    pools = decoder.make_paged_pools(cfg, 12, 8, dtype=jnp.float32, slots=3)
    # leftovers of an earlier request in the slot: a first chunk ignores them
    pools = (pools[0], tuple((s + 3.0, c + 1.0) for s, c in pools[1]))
    pages = [3, 4, 5, 6, 7, 8]
    slot = jnp.int32(2)
    for start, n in ((0, 16), (16, 16), (32, 8)):
        ids = jnp.zeros((16,), jnp.int32).at[:n].set(
            jnp.asarray(seq[start:start + n]))
        pre = jnp.asarray((pages[:start // 8] + [0, 0, 0, 0])[:4], jnp.int32)
        new = jnp.asarray((pages[start // 8:] + [0])[:2], jnp.int32)
        pools, logits = decoder.prefill_suffix_into_pages(
            params, cfg, ids, jnp.int32(n), jnp.int32(start), pools, pre,
            new, slot)
        assert float(jnp.abs(logits - want[start + n - 1]).max()) < LOGIT_TOL
    table = jnp.zeros((3, 8), jnp.int32).at[2, :6].set(jnp.asarray(pages))
    active = jnp.asarray([False, False, True])
    untouched = jax.tree_util.tree_map(lambda a: a[:2], pools[1])
    for i in range(40, 45):
        tokens = jnp.asarray([0, 0, seq[i]], jnp.int32)
        lens = jnp.asarray([0, 0, i], jnp.int32)
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)
        assert float(jnp.abs(logits[2] - want[i]).max()) < LOGIT_TOL
    # rows without a request left their state rows alone
    for a, b in zip(jax.tree_util.tree_leaves(untouched),
                    jax.tree_util.tree_leaves(pools[1])):
        assert bool(jnp.array_equal(a, b[:2]))
    # one live row: 2 sparse layers x 4 choices, 2 KDA layers
    assert int(load[3]) == 8 and int(load[4]) == 2 and int(load[0]) <= 8


@pytest.fixture(scope="module")
def served(cfg, params):
    """Six sampled requests through ``CBEngine`` with 4 slots: prompts of
    5 and 9 in one batched wave, 16 alone, 23, 41 and 60 in chunks of 16,
    12 tokens each in fused dispatches of 4; the last two requests reuse
    the slots of the first to finish."""
    eng = _engine(cfg, params).start()
    prompts = _prompts((5, 16, 23, 41, 60, 9))
    outs = eng.generate(prompts, SamplingParams(temperature=1.0,
                                                max_new_tokens=12))
    info, recoveries = eng.moe_info(), eng.recoveries
    eng.stop()
    return prompts, outs, info, recoveries


def test_engine_logprobs_are_the_references(cfg, params, ref, served):
    """State survives a chunk boundary, a fused dispatch and a slot's reuse
    by the next request: every sampled token's log-probability is the
    reference's full forward's."""
    prompts, outs, info, recoveries = served
    assert recoveries == 0
    for prompt, out in zip(prompts, outs):
        assert len(out["token_ids"]) == 12 and out["finish_reason"] == "length"
        want, _ = ref.score(params, file_keys(cfg),
                            prompt + out["token_ids"], 12)
        assert np.abs(want - np.asarray(out["logprobs"])).max() < LOGP_TOL
    # 6 requests x 11 decoded tokens: 2 KDA layers; 2 sparse layers x 4
    assert info["kda_state_rows"] == 6 * 11 * 2
    assert info["moe_choices"] == 6 * 11 * 2 * 4
    assert 0 < info["moe_routed"] < info["moe_choices"]


def test_the_four_shares_of_a_routed_layer_add_up_to_the_whole(cfg, ref):
    """Each of 4 chips holds 4 of the 16 experts and routes over all 16;
    the shares' results, with the shared expert counted once, are what the
    uncut reference gives for the layer, and the program's block on a
    share is the reference's on that share."""
    full = dataclasses.replace(cfg, experts_held=None)
    lp = jax.tree_util.tree_map(
        lambda a: a[0],
        decoder.init_params(jax.random.PRNGKey(5), full)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (33, cfg.hidden_size))
    route = (cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
             cfg.routed_scaling_factor, cfg.norm_topk_prob)
    whole = ref.routed_mlp(x, lp, (0, 16), *route)
    shared = ref.routed_mlp(x, lp, (0, 0), *route)
    total, hits = shared, 0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        mine = {**lp, **{k: lp[k][first:first + 4]
                         for k in ("we_gate", "we_up", "we_down")}}
        got, load = decoder._moe_mlp(share, x, mine)
        want = ref.routed_mlp(x, mine, (first, 4), *route)
        assert float(jnp.abs(got - want).max()) < 2e-6
        total = total + (got - shared)
        hits += int(load[0])
    assert float(jnp.abs(total - whole).max()) < 5e-6
    assert hits == 33 * cfg.num_experts_per_tok   # every choice lands once
    # the router is 16 wide and group-limited: 2 of 4 groups a token
    w = ref.route(x, lp["router"], lp["router_bias"], *route)
    groups = (w.reshape(33, 4, 4) > 0).any(-1).sum(-1)
    assert int(groups.max()) <= cfg.topk_group
    assert np.allclose(np.asarray(w.sum(-1)), cfg.routed_scaling_factor,
                       atol=1e-5)


def test_an_evened_router_bias_evens_the_loads(cfg, params, ref):
    """The benchmark's ``even_router_bias`` (the reference's own router):
    with random weights a few experts are every token's favourites; under
    the bias it finds, the PROGRAM's router gives every expert of both
    sparse layers the mean load within a tenth."""
    ids = jax.random.randint(jax.random.PRNGKey(9), (8, 64), 1, 512)
    pos = jnp.broadcast_to(jnp.arange(64), (8, 64))
    bias = ref.even_router_bias(params, file_keys(cfg), ids, 16)
    assert bias.shape == (2, 16) and bias.dtype == jnp.float32

    def loads(tree):
        chosen, route = [], blocks._sigmoid_route

        def spy(c, x, lp):
            w, i = route(c, x, lp)
            chosen.append(i)
            return w, i

        blocks._sigmoid_route = spy
        try:
            hybrid.run_sequence(tree, cfg, tree["embed"][ids], pos,
                                jnp.ones((8, 64), bool))
        finally:
            blocks._sigmoid_route = route
        return [np.bincount(np.asarray(i).reshape(8, 64, -1)[:, 16:].ravel(),
                            minlength=16) for i in chosen]

    mean = 8 * 48 * cfg.num_experts_per_tok / 16
    before = loads(params)
    assert max(c.max() for c in before) > 1.8 * mean
    moe = dict(params["layers"]["moe"], router_bias=bias)
    after = loads({**params, "layers": {**params["layers"], "moe": moe}})
    for c in after:
        assert c.sum() == 16 * mean and abs(c - mean).max() < 0.1 * mean


def _stream(q, until=None):
    """Tokens and log-probabilities off a request's queue, to its end or
    until ``until(tokens)``."""
    toks, lps = [], []
    while until is None or not until(toks):
        item = q.get(timeout=120)
        if not isinstance(item, dict):
            break
        toks += item["token_ids"]
        lps += item["logprobs"]
    return toks, lps


def test_a_slots_recurrent_state_is_the_references_recurrence(cfg, params,
                                                              ref):
    """``CBEngine.recurrent_state``: what a running request's slot holds is
    the reference's token-by-token recurrence over the prompt (three
    chunks) and the answer's tokens fed back so far, layer by layer; and
    the benchmark's comparison (``planes/rollout_hybrid.py::compare``)
    passes on it part by part and fails by the state's limit alone once
    the state is rounded to bfloat16."""
    plane = harness.load_named("planes", "rollout_hybrid")
    eng = _engine(cfg, params).start()
    try:
        assert eng.recurrent_state("nobody") is None
        prompt = _prompts([41], seed=11)[0]
        q = eng.submit("held", prompt, SamplingParams(temperature=1.0,
                                                      max_new_tokens=80))
        toks, lps = _stream(q, lambda t: len(t) >= 20)
        consumed, states = eng.recurrent_state("held")
        more, more_lps = _stream(q)
    finally:
        eng.stop()
    toks, lps = toks + more, lps + more_lps
    fed = consumed - len(prompt)
    assert 19 <= fed < 80 and len(states) == 2
    assert all(s.shape == (cfg.num_heads, cfg.head_dim_, cfg.head_dim_)
               and s.dtype == np.float32 for s in states)
    c = file_keys(cfg)
    want = ref.trace(params, c, prompt + toks[:fed], len(prompt), 16)
    for mine, theirs in zip(states, want["states"]):
        assert float(plane.rel(mine, theirs)) < 2e-5
    assert np.abs(want["logprobs"] - np.asarray(lps[:16])).max() < LOGP_TOL
    # the comparison of the cell, on this request
    samples = [(prompt, toks[:16], lps[:16])]
    held = [{"answer": toks[:fed], "states": states}]
    limits = {"logprob_mean_abs_diff_max": 1e-5,
              "logprob_max_abs_diff_max": 1e-5, "state_rel_diff_max": 1e-4,
              "experts_rel_diff_max": 1e-4}
    walked = plane.walk(ref, cfg, params, c, samples, held)
    got = plane.compare(ref, params, c, limits, samples, held, walked)
    assert got["ok"] and got["positions"] == 16, got
    assert got["experts_positions"] > 0 and got["state_tokens"] == [consumed]
    import ml_dtypes
    held[0]["states"] = [s.astype(ml_dtypes.bfloat16).astype(np.float32)
                         for s in states]
    got = plane.compare(ref, params, c, limits, samples, held, walked)
    assert not got["ok"] and 1e-4 < got["state_rel_diff"] < 1e-2
    assert got["experts_rel_diff"] < 1e-4
    # experts on int8's grid in the program's place: the experts' limit
    moe = dict(params["layers"]["moe"])
    for key in decoder.EXPERT_KEYS:
        moe[key] = ref._int8(moe[key])
    rounded = {**params, "layers": {**params["layers"], "moe": moe}}
    held[0]["states"] = states
    walked = plane.walk(ref, cfg, rounded, c, samples, held)
    got = plane.compare(ref, params, c, limits, samples, held, walked,
                        again=True)
    assert not got["ok"] and got["experts_rel_diff"] > 1e-3
    assert got["state_rel_diff"] < 1e-4


def test_prefill_first_holds_decode_while_a_prompt_is_prefilled(cfg, params):
    """``prefill_first``: while a chunked prefill is under way no decode
    program is dispatched; without it a chunk and a decode dispatch take
    turns. The running request's tokens are the same either way."""
    short, long = _prompts([9, 60], seed=13)
    greedy = SamplingParams(temperature=0.0, max_new_tokens=100)

    def serve(**kw):
        eng = _engine(cfg, params, **kw)
        order = []
        for name, mark in (("_advance_chunk_job", "c"), ("_step_once", "d")):
            def logged(real=getattr(eng, name), mark=mark):
                order.append(mark)
                return real()
            setattr(eng, name, logged)
        eng.start()
        try:
            qa = eng.submit("a", short, greedy)
            head, _ = _stream(qa, lambda t: len(t) >= 4)
            qb = eng.submit("b", long, greedy)
            _stream(qb, lambda t: len(t) >= 1)
            rest, _ = _stream(qa, lambda t: len(t) >= 36)
        finally:
            eng.stop()
        chunks = [i for i, m in enumerate(order) if m == "c"]
        return (order[chunks[0]:chunks[-1]].count("d"), len(chunks),
                (head + rest)[:40])

    held, n_held, toks_held = serve(prefill_first=True)
    turns, n_turns, toks_turns = serve()
    # three extends of 16 and the last chunk's admission
    assert n_held == n_turns == 4
    assert held == 0 and turns == 3
    assert toks_held == toks_turns


def test_a_stateful_model_takes_the_from_token_0_paths_or_raises(cfg, params,
                                                                 ref):
    """No snapshot to re-enter a sequence from, so: speculation is an error
    at construction; there is no prefix cache and so no hit, publish,
    spill or salvage publish; a group's siblings and a resumed partial
    prefill from token 0; and all of it by the model's layers, not by an
    option."""
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(cfg, params, spec_tokens=2)
    eng = _engine(cfg, params, kv_spill=True).start()
    try:
        assert eng.stateful and eng.prefix_cache is None
        assert eng.kvspill is None and not eng.decode_group_share
        prompt = _prompts([40], seed=7)[0]
        sp = SamplingParams(temperature=0.0, max_new_tokens=6)
        # a GRPO group of 3 of one prompt: each prefills alone (3 chunks)
        qs = [eng.submit(f"g{i}", prompt, sp, group_id="g", group_size=3)
              for i in range(3)]
        outs = []
        for q in qs:
            toks = []
            while True:
                item = q.get(timeout=120)
                if not isinstance(item, dict):
                    break
                toks += item["token_ids"]
            outs.append(toks)
        assert outs[0] == outs[1] == outs[2] and len(outs[0]) == 6
        assert eng.group_forked_requests == 0
        assert eng.sibling_attach_dispatches == 0
        assert eng.chunk_dispatches == 3 * 2      # two 16-token extends each
        # a partial resumed as a new prompt: prefilled from token 0, and the
        # continuation is the reference's
        before = eng.chunk_dispatches
        resumed = prompt + outs[0][:3]
        out = eng.generate([resumed], SamplingParams(temperature=1.0,
                                                     max_new_tokens=4))[0]
        assert eng.chunk_dispatches - before == 2
        want, _ = ref.score(params, file_keys(cfg),
                            resumed + out["token_ids"], 4)
        assert np.abs(want - np.asarray(out["logprobs"])).max() < LOGP_TOL
        assert eng.salvage_published_pages == 0 and eng.recoveries == 0
    finally:
        eng.stop()
    # a model without a state keeps every one of these features
    dense = decoder.get_config("tiny", dtype=jnp.float32)
    eng = _engine(dense, decoder.init_params(jax.random.PRNGKey(0), dense),
                  spec_tokens=2)
    assert not eng.stateful and eng.prefix_cache is not None


def test_a_hybrid_tree_goes_through_update_weights_and_the_fabrics_layout(
        cfg, params, ref):
    from polyrl_tpu.transfer import layout as lay

    fresh = decoder.init_params(jax.random.PRNGKey(11), cfg)
    layout = lay.build_layout(fresh)
    buf = lay.alloc_buffer(layout)
    lay.pack_params(fresh, layout, buf)
    back = lay.unflatten_like(params, lay.unpack_params(buf, layout))
    for a, b in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    names = set(layout.by_name())
    assert any("kda" in n and "a_log" in n for n in names)
    assert any("moe" in n and "router_bias" in n for n in names)
    eng = _engine(cfg, params).start()
    try:
        sp = SamplingParams(temperature=1.0, max_new_tokens=5)
        prompt = _prompts([20], seed=8)
        eng.generate(prompt, sp)
        eng.update_weights(jax.tree_util.tree_map(jnp.asarray, back))
        out = eng.generate(prompt, sp)[0]
        want, _ = ref.score(fresh, file_keys(cfg),
                            prompt[0] + out["token_ids"], 5)
        assert np.abs(want - np.asarray(out["logprobs"])).max() < LOGP_TOL
        with pytest.raises(ValueError, match="structure"):
            eng.update_weights({"embed": params["embed"]})
    finally:
        eng.stop()


def test_ling_preset_equals_the_benchmark_file():
    """The program's preset with the file's overrides is the file, key
    for key: the family's keys reach the program through the preset alone
    (``harness.MODEL_FIELDS`` carries only the dense GQA keys), so a
    disagreement cannot hide behind the overrides."""
    path = os.path.join(harness.BENCH_DIR, "configs", "ling-3.0-flash.json")
    config = harness.load_config(path)
    raw = config["config"]
    preset = decoder.get_config(config["preset"])
    cfg = decoder.get_config(config["preset"],
                             **harness.model_overrides(config))
    assert cfg == preset              # the overrides change nothing
    family = {"moe_intermediate_size": "moe_intermediate_size",
              "moe_shared_expert_intermediate_size":
              "moe_shared_expert_intermediate_size",
              "num_experts_per_tok": "num_experts_per_tok",
              "n_group": "n_group", "topk_group": "topk_group",
              "routed_scaling_factor": "routed_scaling_factor",
              "norm_topk_prob": "norm_topk_prob",
              "scoring_func": "scoring_func",
              "layer_group_size": "layer_group_size",
              "kv_lora_rank": "kv_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim",
              "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim",
              "short_conv_kernel_size": "short_conv_kernel_size",
              "kda_lower_bound": "kda_lower_bound"}
    for key, field in {**harness.MODEL_FIELDS, **family}.items():
        if key in raw:
            assert getattr(cfg, field) == raw[key], key
    assert raw["score_function"] == cfg.scoring_func == "sigmoid"
    assert raw["q_lora_rank"] is None and raw["qk_head_dim"] == 192
    # the cut: what is held here, beside the published counts
    plan = cache_spec.layer_plan(cfg)
    assert [p.published for p in plan] == raw["kept_layers"] == \
        list(cfg.kept_layers)
    assert raw["first_k_dense_replace"] == sum(p.mlp == "dense" for p in plan)
    assert [p.mixer for p in plan] == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert tuple(raw["experts_held"]) == cache_spec.experts_held(cfg) == (0, 128)
    assert raw["num_experts"] == 128 and cfg.num_experts == 512
    assert raw["num_nextn_predict_layers"] == 0
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers"]
    whole = decoder.get_config("ling-3.0-flash")
    pub = raw["published"]
    assert (whole.num_layers, whole.first_k_dense_replace, whole.num_experts,
            whole.vocab_size) == (pub["num_hidden_layers"],
                                  pub["first_k_dense_replace"],
                                  pub["num_experts"], pub["vocab_size"])
    assert cfg == decoder.cut_to_share(whole, tuple(raw["kept_layers"]),
                                       raw["chips_sharing_a_layer"])
    # every number of the catalog's row under the same key, but the cut
    # (no network here: the row is beside the model-configs guide)
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(rows):
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash")
        with open(path) as f:
            on_disk = json.load(f)
        assert on_disk["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert on_disk[key] == value, key


@pytest.mark.parametrize("rows", [9, 33])
def test_a_share_of_the_experts_by_table_is_the_tiled_path(monkeypatch, cfg,
                                                           params, rows):
    """A decode step's form on a TPU (``blocks._expert_rows``),
    interpreted, on the 4 of 16 experts the preset holds, the sigmoid
    router's group-limited choices, a layer of the whole stacks."""
    from tests.moe_forms import assert_both_forms_agree

    l = next(l for l, p in enumerate(cache_spec.layer_plan(cfg))
             if p.mlp == "moe")
    lp = hybrid._layer_params(cfg, params["layers"], l)[1]
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, cfg.hidden_size))
    assert_both_forms_agree(monkeypatch, cfg, x, lp, jnp.arange(rows) != 1,
                            hybrid.kind_index(cfg)[l][1])
