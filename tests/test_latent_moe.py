"""DeepSeek-V3's decoder (latent attention in EVERY layer with a query
latent and YaRN, a routed MLP with a sigmoid router behind a dense layer;
``models/hybrid.py``) at the ``mla-moe-tiny`` preset on the CPU, in
float32, against the benchmark's plain reference
(``benchmark/references/mla_moe.py``: the expanded attention a group of
heads at a time, every expert on every position), and the engine's rule
for a model whose cache is all pages and no K/V pair
(``cache_spec.is_stateful`` False, ``cache_spec.without_kernel``).

The limits are float32's: the program and the reference compute the same
sums in another order (a running softmax over blocks of keys against a
whole one, an absorbed product against an expanded one, a sorted grouped
matmul against a loop over the experts), each a few ulps of a value of
order 1, through 3 layers: 5e-6 on logits of at most 0.7 in magnitude;
readings are under 1e-6. A wrong position, frequency, scale, mask, page
or expert moves a logit by 1e-2 or more."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import cache_spec, decoder, hf_loader
from polyrl_tpu.models.mixers import mla
from polyrl_tpu.models.mixers.base import key_block
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    plan = cache_spec.layer_plan(cfg)
    first, held = cache_spec.experts_held(cfg)
    s = cfg.rope_scaling
    return {
        "num_hidden_layers": cfg.num_layers,
        "kept_layers": [p.published for p in plan],
        "first_k_dense_replace": sum(p.mlp == "dense" for p in plan),
        "num_attention_heads": cfg.num_heads,
        "rms_norm_eps": cfg.rms_norm_eps,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": None if s is None else {
            "type": "yarn", "factor": s.factor, "beta_fast": s.beta_fast,
            "beta_slow": s.beta_slow, "mscale": s.mscale,
            "mscale_all_dim": s.mscale_all_dim,
            "original_max_position_embeddings":
                s.original_max_position_embeddings},
        "experts_held": [first, held], "n_routed_experts": held,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "mla_moe")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("mla-moe-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return decoder.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


ENGINE = dict(max_slots=4, page_size=8, max_seq_len=128,
              prompt_buckets=(16, 64), num_pages=80, prefill_chunk=16,
              steps_per_dispatch=4)


def _engine(cfg, params, **kw):
    return CBEngine(cfg, params, **{**ENGINE, "kv_cache_dtype": jnp.float32,
                                    **kw})


def test_the_tiny_preset_is_latent_attention_in_every_layer(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [(p.mixer, p.mlp) for p in plan] == [
        ("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    assert (cfg.num_experts, cfg.n_group, cache_spec.experts_held(cfg)) == \
        (16, 4, (0, 4))
    assert cfg.q_lora_rank and not cfg.mla_head_gate
    assert cfg.rope_scaling.rope_type == "yarn"
    # all pages, no slot: neither the stacked-scan decoder nor stateful
    assert not cache_spec.is_uniform(cfg) and not cache_spec.is_stateful(cfg)
    spec = cache_spec.cache_spec(cfg)
    assert spec == (cache_spec.Paged(1, 1, 128),) * 3   # 32 + 8 in whole lanes
    assert cache_spec.paged_bytes_per_token(cfg) == 3 * 128 * 4
    assert cache_spec.slot_bytes(cfg) == 0
    paged, state = cache_spec.make_pools(cfg, 5, 8, slots=3)
    assert len(paged) == 3 and state == () and paged[0].shape == (1, 5, 8, 128)
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("mla",)
        assert cache_spec.without_kernel(decoder.get_config("tiny"),
                                         feature) == ()
    assert cache_spec.without_kernel(
        decoder.get_config("hybrid-tiny"), "spec_tokens") == ("kda", "mla")
    tree = decoder.init_params(jax.random.PRNGKey(0), cfg)["layers"]["mla"]
    assert sorted(tree) == ["kv_norm", "q_norm", "wkv_a", "wkv_b", "wo",
                            "wq_a", "wq_b"]


def test_forward_is_the_references_forward(cfg, params, ref):
    ids = np.asarray(_prompts([45], seed=1)[0])
    pos = jnp.arange(45)[None]
    got, cache = decoder.forward(params, cfg, jnp.asarray(ids)[None], pos,
                                 jnp.ones((1, 45)))
    want = ref.logits(params, file_keys(cfg), ids)
    assert cache is None
    assert float(jnp.abs(got[0] - want).max()) < LOGIT_TOL
    assert float(jnp.abs(want).max()) > 0.3
    # YaRN and the query latent are part of the result: without either the
    # logits move by far more than the tolerance
    plain = dataclasses.replace(cfg, rope_scaling=None)
    off, _ = decoder.forward(params, plain, jnp.asarray(ids)[None], pos,
                             jnp.ones((1, 45)))
    assert float(jnp.abs(off[0] - want).max()) > 100 * LOGIT_TOL
    # a gradient reaches the query latent through the blocked form
    def loss(p):
        logits, _ = decoder.forward(p, cfg, jnp.asarray(ids)[None], pos,
                                    jnp.ones((1, 45)), remat=True)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    g = jax.grad(loss)(params)["layers"]["mla"]
    assert float(jnp.abs(g["wq_a"]).max()) > 0
    assert float(jnp.abs(g["wkv_b"]).max()) > 0


def test_yarn_frequencies_and_scale_for_the_published_keys(ref):
    """By hand, for rope 64, theta 10000, factor 40 over 4096, beta 32 and
    1: 4096 positions make 32 turns at dimension 64 ln(4096 / 64 pi) / (2
    ln 1e4) = 10.47 and 1 turn at 22.51, so frequencies 0-10 are kept,
    23-31 are divided by 40, and 16 is 6/13 of the way; m = 0.1 ln 40 + 1
    = 1.368888, the logits' scale 192^-0.5 * m^2 = 0.135234."""
    cfg = decoder.get_config("dots.vlm1")
    inv = mla.rope_inv_freq(cfg)
    base = 10000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(
        inv[16], base[16] * (7 / 13 + 6 / 13 / 40), rtol=1e-12)
    assert np.all(np.diff(inv) < 0)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3688879) < 1e-6
    assert abs(mla.mla_scale(cfg) - 192 ** -0.5 * m * m) < 1e-12
    assert abs(mla.mla_scale(cfg) - 0.135234) < 1e-6
    assert mla.rope_amplitude(cfg) == 1.0
    # the reference reads the published keys to the same numbers
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "dots.vlm1.json")) as f:
        raw = json.load(f)
    r_inv, r_amp, r_more = ref.rope_frequencies(raw)
    np.testing.assert_allclose(r_inv, inv, rtol=1e-12)
    assert r_amp == 1.0 and abs(r_more - m * m) < 1e-12
    # no scaling, no change (Ling's rope)
    ling = decoder.get_config("ling-3.0-flash")
    np.testing.assert_allclose(
        mla.rope_inv_freq(ling), 6e6 ** (-np.arange(32) / 32.0),
        rtol=1e-12)
    assert mla.mla_scale(ling) == 192 ** -0.5


def test_absorbed_mla_is_the_expanded_form(cfg, params):
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["mla"])
    t = 21
    h_in = jax.random.normal(jax.random.PRNGKey(3), (1, t, cfg.hidden_size))
    pos = jnp.arange(t)[None]
    q_nope, q_rope, lat = mla._mla_qkv(cfg, lp, h_in, pos)
    assert lat.shape == (1, t, 128) and not bool(jnp.any(lat[..., 40:]))
    want = mla.mla_expanded(cfg, lp, q_nope, q_rope, lat,
                               jnp.ones((1, t), bool), pos)[0, -1]
    from polyrl_tpu.ops.mla_attention import (latent_paged_attention_pallas,
                                              latent_paged_attention_ref)

    pool = jnp.zeros((1, 6, 8, 128)).at[0, 1:4].set(
        jnp.pad(lat[0], ((0, 3), (0, 0))).reshape(3, 8, 128))
    table = jnp.asarray([[1, 2, 3, 0], [0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([t, 0], jnp.int32)
    q_lat = mla.mla_absorb(cfg, lp, q_nope[0, -1:], q_rope[0, -1:])
    q_lat = jnp.concatenate([q_lat, q_lat])
    for fn in (latent_paged_attention_ref,
               lambda *a: latent_paged_attention_pallas(*a, interpret=True)):
        o_lat = fn(q_lat, pool, table, lens, cfg.kv_lora_rank,
                   mla.mla_scale(cfg))
        got = mla.mla_unabsorb(cfg, lp, o_lat)
        assert float(jnp.abs(got[0] - want).max()) < 2e-6
        assert not bool(jnp.any(got[1]))          # a row without a request


@pytest.mark.parametrize("block", [8, 16, 24])
def test_prefill_blocked_over_keys_is_the_unblocked_form(cfg, params, block):
    """Two rows of 16 queries that continue prefixes of 37 and 20 tokens
    in a 40-row bucket: blocks of 8, 16 and 24 keys (the last does not
    divide the 56 keys) against one block of all of them; a block past
    every query's reach is skipped, not read."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["mla"])
    tp, t = 40, 16
    h_in = jax.random.normal(jax.random.PRNGKey(4), (2, tp + t,
                                                     cfg.hidden_size))
    pre_len = jnp.asarray([37, 20])
    pos = jnp.broadcast_to(jnp.arange(tp + t), (2, tp + t))
    q_nope, q_rope, lat = mla._mla_qkv(cfg, lp, h_in, pos)
    key_ok = jnp.concatenate(
        [jnp.arange(tp)[None] < pre_len[:, None],
         jnp.arange(t)[None] < jnp.asarray([[16], [9]])], axis=1)
    q_at = jnp.broadcast_to(tp + jnp.arange(t), (2, t))
    # what a padded prefix row holds must not matter: poison it
    lat = jnp.where(key_ok[..., None], lat, 1e4)
    args = (cfg, lp, q_nope[:, tp:], q_rope[:, tp:], lat, key_ok, q_at)
    whole = mla.mla_expanded(*args)
    assert key_block(cfg, 2, t) >= tp + t       # one block by default
    got = mla.mla_expanded(*args, block=block)
    assert got.shape == (2, t, cfg.num_heads, cfg.v_head_dim)
    assert float(jnp.abs(got - whole).max()) < 2e-6
    # at the published size: 512 keys a block at 128 heads, 2048 at 32
    assert key_block(decoder.get_config("dots.vlm1"), 1, 512) == 512
    assert key_block(decoder.get_config("ling-3.0-flash"), 1,
                            512) == 2048


def test_prefill_then_paged_decode_gives_the_references_logits(cfg, params,
                                                               ref):
    """The engine's device functions without the engine: a 40-token prompt
    in chunks of 16, 16 and 8 through ``prefill_suffix_into_pages``
    (latent pages carried from chunk to chunk in all three layers), then 5
    tokens through ``forward_paged_decode``, in slot 2 of 3 beside an
    empty row."""
    seq = _prompts([45], seed=4)[0]
    want = ref.logits(params, file_keys(cfg), seq)
    pools = decoder.make_paged_pools(cfg, 12, 8, dtype=jnp.float32, slots=3)
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
    for i in range(40, 45):
        tokens = jnp.asarray([0, 0, seq[i]], jnp.int32)
        lens = jnp.asarray([0, 0, i], jnp.int32)
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)
        assert float(jnp.abs(logits[2] - want[i]).max()) < LOGIT_TOL
    assert pools[1] == ()
    # one live row at 45 tokens: 2 sparse layers x 4 choices, no KDA
    # layer, 3 MLA layers x 45 latent rows
    assert int(load[3]) == 8 and int(load[4]) == 0 and int(load[0]) <= 8
    assert int(load[5]) == 3 * 45


@pytest.fixture(scope="module")
def served():
    """Six sampled requests through ``create_server``'s engine with 4
    slots, as every other model is served: prompts of 5 and 9 in one
    batched wave, 16 alone, 23, 41 and 60 in chunks of 16, 12 tokens each
    in fused dispatches of 4; the last two reuse the slots of the first to
    finish."""
    from polyrl_tpu.rollout.serve import create_server

    srv = create_server("mla-moe-tiny", dtype="float32", host="127.0.0.1",
                        seed=0, **ENGINE)
    try:
        eng = srv.engine
        assert not eng.stateful and eng.prefix_cache is not None
        prompts = _prompts((5, 16, 23, 41, 60, 9))
        outs = eng.generate(prompts, SamplingParams(temperature=1.0,
                                                    max_new_tokens=12))
        return prompts, outs, eng.moe_info(), eng.recoveries, eng.params
    finally:
        srv.stop()


def test_engine_logprobs_are_the_references(cfg, ref, served):
    prompts, outs, info, recoveries, params = served
    assert recoveries == 0
    rows = 0
    for prompt, out in zip(prompts, outs):
        assert len(out["token_ids"]) == 12 and out["finish_reason"] == "length"
        want, _ = ref.score(params, file_keys(cfg),
                            prompt + out["token_ids"], 12)
        assert np.abs(want - np.asarray(out["logprobs"])).max() < LOGP_TOL
        # a decode step at context n attends n + 1 rows in each layer
        rows += 3 * sum(len(prompt) + k + 1 for k in range(11))
    assert info["mla_rows_read"] == rows
    assert info["kda_state_rows"] == 0
    assert info["moe_choices"] == 6 * 11 * 2 * 4
    assert 0 < info["moe_routed"] < info["moe_choices"]


def test_the_sixteen_shares_of_a_routed_layer_add_up_to_the_whole(cfg, ref):
    """Each of 16 chips holds one of the 16 experts and routes over all
    16; the shares' results, with the shared expert counted once, are what
    the uncut reference gives for the layer, and the program's block on a
    share is the reference's on that share."""
    full = dataclasses.replace(cfg, experts_held=None)
    lp = jax.tree_util.tree_map(
        lambda a: a[0],
        decoder.init_params(jax.random.PRNGKey(5), full)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (33, cfg.hidden_size))
    route = (cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
             cfg.routed_scaling_factor, cfg.norm_topk_prob)
    routed = harness.load_named("references", "hybrid_kda_mla_moe")
    whole = routed.routed_mlp(x, lp, (0, 16), *route)
    shared = routed.routed_mlp(x, lp, (0, 0), *route)
    total, hits = shared, 0
    for first in range(16):
        share = dataclasses.replace(cfg, experts_held=(first, 1))
        mine = {**lp, **{k: lp[k][first:first + 1]
                         for k in ("we_gate", "we_up", "we_down")}}
        got, load = decoder._moe_mlp(share, x, mine)
        want = routed.routed_mlp(x, mine, (first, 1), *route)
        assert float(jnp.abs(got - want).max()) < 2e-6
        total = total + (got - shared)
        hits += int(load[0])
    assert float(jnp.abs(total - whole).max()) < 5e-6
    assert hits == 33 * cfg.num_experts_per_tok   # every choice lands once
    # the reference's own layer function is the same block
    z = ref._sizes(file_keys(full))
    assert z.held == (0, 16) and z.plan == ("dense", "moe", "moe")


def test_dots_preset_equals_the_benchmark_file():
    """The program's preset with the file's overrides is the file, key
    for key: the family's keys reach the program through the preset alone
    (``harness.MODEL_FIELDS`` carries only the dense GQA keys)."""
    path = os.path.join(harness.BENCH_DIR, "configs", "dots.vlm1.json")
    config = harness.load_config(path)
    raw = config["config"]
    preset = decoder.get_config(config["preset"])
    cfg = decoder.get_config(config["preset"],
                             **harness.model_overrides(config))
    assert cfg == preset              # the overrides change nothing
    family = {"moe_intermediate_size": "moe_intermediate_size",
              "num_experts_per_tok": "num_experts_per_tok",
              "n_group": "n_group", "topk_group": "topk_group",
              "routed_scaling_factor": "routed_scaling_factor",
              "norm_topk_prob": "norm_topk_prob",
              "scoring_func": "scoring_func",
              "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim",
              "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim"}
    for key, field in {**harness.MODEL_FIELDS, **family}.items():
        if key in raw:
            assert getattr(cfg, field) == raw[key], key
    assert cfg.moe_shared_expert_intermediate_size == \
        raw["n_shared_experts"] * raw["moe_intermediate_size"]
    assert cfg.rope_scaling == hf_loader.rope_scaling_from_hf(
        raw["rope_scaling"])
    assert not cfg.mla_head_gate and not cfg.layer_group_size
    plan = cache_spec.layer_plan(cfg)
    assert [p.published for p in plan] == raw["kept_layers"] == \
        list(cfg.kept_layers) == [0, 3, 4, 5, 6]
    assert raw["first_k_dense_replace"] == sum(p.mlp == "dense"
                                               for p in plan) == 1
    assert {p.mixer for p in plan} == {"mla"}
    assert tuple(raw["experts_held"]) == cache_spec.experts_held(cfg) == (0, 16)
    assert raw["n_routed_experts"] == raw["num_experts"] == 16
    assert cfg.num_experts == 256
    assert raw["num_nextn_predict_layers"] == 0
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    whole = decoder.get_config("dots.vlm1")
    pub = raw["published"]
    assert (whole.num_layers, whole.first_k_dense_replace, whole.num_experts,
            whole.vocab_size) == (pub["num_hidden_layers"],
                                  pub["first_k_dense_replace"],
                                  pub["n_routed_experts"], pub["vocab_size"])
    assert cfg == decoder.cut_to_share(
        whole, tuple(raw["kept_layers"]), raw["chips_sharing_a_layer"],
        vocabulary_shares=raw["vocabulary_shares"])
    # every number of the catalog's row under the same key, but the cut
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(rows):
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots.vlm1.inst")
        with open(path) as f:
            on_disk = json.load(f)
        assert on_disk["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert on_disk[key] == value, key


# -- the engine's rule, split in two --------------------------------------------


def _logprobs_of(eng, prompt, n, **submit):
    q = eng.submit(submit.pop("rid", "r"), prompt,
                   SamplingParams(temperature=0.0, max_new_tokens=n),
                   **submit)
    toks, lps = [], []
    while True:
        item = q.get(timeout=120)
        if not isinstance(item, dict):
            return toks, lps
        toks += item["token_ids"]
        lps += item["logprobs"]


def test_what_acts_on_pages_runs_on_the_latent_pool(cfg, params, ref):
    """All pages, no K/V pair: the prefix cache hits and publishes, a GRPO
    group's siblings attach to their prompt's pages, the ledger books a
    latent page's bytes; what needs a GQA kernel is refused or off with
    the mixer named."""
    with pytest.raises(ValueError, match=r"spec_tokens.*mla layers"):
        _engine(cfg, params, spec_tokens=2)
    eng = _engine(cfg, params, kv_spill=True).start()
    try:
        assert not eng.stateful and eng.prefix_cache is not None
        assert eng.kvspill is None and not eng.decode_group_share
        prompt = _prompts([40], seed=7)[0]
        # cold: three chunks of 16, 16 and 8 tokens
        cold = _logprobs_of(eng, prompt, 6)
        assert eng.chunk_dispatches == 2
        assert (eng.prefix_cache.req_hits, eng.prefix_cache.req_misses) == \
            (0, 1)
        # again: the 4 full pages that the prompt's 40 tokens leave a
        # suffix behind (the last full page is recomputed for its logits)
        # come from the cache, and the answer is the cold one's
        warm = _logprobs_of(eng, prompt, 6)
        assert eng.prefix_cache.req_hits == 1
        assert eng.chunk_dispatches == 2          # nothing prefilled again
        assert warm[0] == cold[0]
        np.testing.assert_allclose(warm[1], cold[1], atol=LOGP_TOL)
        want, _ = ref.score(params, file_keys(cfg), prompt + cold[0], 6)
        assert np.abs(want - np.asarray(warm[1])).max() < LOGP_TOL
        # a page's bytes: one 128-wide float32 row a token a layer
        eng.kv_memory_info()
        assert eng.kvledger.page_bytes == 3 * 8 * 128 * 4
        # a GRPO group of 3 of a new prompt: the leader prefills, the
        # siblings attach to its published pages in one wave
        other = _prompts([41], seed=8)[0]
        before = eng.chunk_dispatches
        qs = [eng.submit(f"g{i}", other, SamplingParams(
            temperature=0.0, max_new_tokens=6), group_id="g", group_size=3)
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
        assert eng.group_forked_requests == 2
        assert eng.sibling_attach_dispatches >= 1
        assert eng.chunk_dispatches - before == 2     # the leader's alone
        assert eng.recoveries == 0
    finally:
        eng.stop()


def test_yarn_comes_through_the_hf_loader():
    got = hf_loader.rope_scaling_from_hf({
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"})
    assert got == decoder.get_config("dots.vlm1").rope_scaling
    assert got.rope_type == "yarn" and got.factor == 40.0
    # keys a config leaves out take HF's defaults
    short = hf_loader.rope_scaling_from_hf({
        "rope_type": "yarn", "factor": 4.0,
        "original_max_position_embeddings": 32768})
    assert (short.beta_fast, short.beta_slow, short.mscale,
            short.mscale_all_dim) == (32.0, 1.0, 1.0, 0.0)
    llama = hf_loader.rope_scaling_from_hf({
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 8192})
    assert llama == decoder.get_config("llama3-8b").rope_scaling
    assert hf_loader.rope_scaling_from_hf(None) is None
    assert hf_loader.rope_scaling_from_hf({"type": "default"}) is None
    with pytest.raises(NotImplementedError, match="linear"):
        hf_loader.rope_scaling_from_hf({"type": "linear", "factor": 2.0})
    # a GQA layer has no YaRN: said, not run with unscaled frequencies
    gqa = decoder.get_config("tiny", rope_scaling=got)
    with pytest.raises(NotImplementedError, match="yarn"):
        decoder.rope_cos_sin(gqa, jnp.arange(4)[None])


@pytest.mark.parametrize("rows", [9, 33])
def test_a_share_of_the_experts_by_table_is_the_tiled_path(monkeypatch, cfg,
                                                           params, rows):
    """A decode step's form on a TPU (``blocks._expert_rows``),
    interpreted, on the experts the preset holds, the last sparse layer of
    the whole stacks."""
    from polyrl_tpu.models import cache_spec, hybrid
    from tests.moe_forms import assert_both_forms_agree

    l = max(l for l, p in enumerate(cache_spec.layer_plan(cfg))
            if p.mlp == "moe")
    lp = hybrid._layer_params(cfg, params["layers"], l)[1]
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, cfg.hidden_size))
    assert_both_forms_agree(monkeypatch, cfg, x, lp, jnp.arange(rows) != 1,
                            hybrid.kind_index(cfg)[l][1])
