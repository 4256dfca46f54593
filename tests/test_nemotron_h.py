"""Nemotron-H's decoder (``models/hybrid.py`` with ``mixers/mamba2.py``:
layers of ONE sublayer by a published pattern, Mamba-2 mixers with a
float32 state in the slot, attention without positions, routed experts of
two matrices under ``relu(.)^2`` beside an ungated shared expert) at the
``nemotron-h-tiny`` preset on the CPU, in float32, against the benchmark's
plain reference (``benchmark/references/nemotron_h.py``: whole sequences,
the recurrence position by position).

The limits are float32's: the program and the reference compute the same
sums in another order (the chunked SSD form against the recurrence, pages
against a whole sequence, a running softmax against a whole row), each a
few ulps of a value of order 1, through 5 layers: 5e-6 on logits of up to
0.7 in magnitude."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import blocks, cache_spec, decoder, hybrid
from polyrl_tpu.models.mixers import mamba2
from polyrl_tpu.ops import ssd_state
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6
CHUNK, PAGE = 16, 8
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads."""
    return {
        "hybrid_override_pattern": cfg.hybrid_override_pattern,
        "mamba_num_heads": cfg.mamba_num_heads,
        "mamba_head_dim": cfg.mamba_head_dim,
        "n_groups": cfg.mamba_n_groups,
        "ssm_state_size": cfg.ssm_state_size,
        "conv_kernel": cfg.ssm_conv_kernel,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "experts_held": list(cache_spec.experts_held(cfg)),
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob, "n_group": cfg.n_group}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "nemotron_h")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("nemotron-h-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """The preset's weights with the vectors that are one or zero as
    initialised drawn (a vector that is left out must show)."""
    tree = decoder.init_params(jax.random.PRNGKey(0), cfg)

    def drawn(path, a):
        name = path[-1].key
        if name in ("norm", "norm_w", "d_skip", "conv_bias", "final_norm"):
            key = jax.random.PRNGKey(sum(map(ord, name)))
            mean = 0.0 if name == "conv_bias" else 1.0
            return (mean + 0.2 * jax.random.normal(key, a.shape)).astype(
                a.dtype)
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


def test_the_plan_follows_from_the_published_pattern(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [(p.mixer, p.mlp) for p in plan] == [
        ("mamba2", None), (None, "moe"), ("gqa", None), (None, "moe"),
        ("mamba2", None)]
    assert cache_spec.one_sublayer(cfg) and not cache_spec.is_uniform(cfg)
    assert cache_spec.is_stateful(cfg)
    # ONE K/V pair of 2 heads of 16 a token; 2 states [2, 16, 32] float32
    # and 2 tails [3, 128] a slot
    assert cache_spec.paged_bytes_per_token(cfg) == 2 * 2 * 16 * 4
    assert cache_spec.slot_bytes(cfg) == 2 * (2 * 16 * 32 * 4 + 3 * 128 * 4)
    assert cache_spec.pool_index(cfg) == ((None, 0), (None, None), (0, None),
                                          (None, None), (None, 1))
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("gqa", "mamba2")
    assert hybrid.kind_index(cfg) == [(0, 0), (0, 0), (0, 0), (0, 1), (1, 0)]
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    assert set(tree) == {"norm", "mamba2", "gqa", "moe"}
    assert tree["norm"].shape == (5, 64)
    assert set(tree["moe"]) == {"router", "router_bias", "we_up", "we_down",
                                "ws_up", "ws_down"}


def test_the_published_model_counts_its_published_parameters():
    whole = decoder.get_config("nemotron-3-nano-30b-a3b")
    kinds = [(p.mixer, p.mlp) for p in cache_spec.layer_plan(whole)]
    assert whole.hybrid_override_pattern == PATTERN and len(kinds) == 52
    assert (kinds.count(("mamba2", None)), kinds.count(("gqa", None)),
            kinds.count((None, "moe"))) == (23, 6, 23)
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), whole))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == 31_577_940_288      # the published 31.6B
    share = decoder.get_config("nemotron-3-nano-30b-a3b-share8")
    assert cache_spec.layer_plan(share) == cache_spec.layer_plan(whole)
    assert cache_spec.experts_held(share) == (0, 16)
    assert share.vocab_size == 16384
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), share))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 5_258_420_544
    # 23 states of 2 MiB with their 36,864 B tails a slot; 6 K/V pairs of
    # 2 heads of 128 a token
    assert cache_spec.slot_bytes(share) == 23 * (2 * 2**20 + 36_864)
    assert cache_spec.paged_bytes_per_token(share) == 6 * 1024
    assert shapes["layers"]["mamba2"]["w_in"].shape == (23, 2688, 10304)


def test_a_character_that_is_not_written_is_refused_by_name(cfg):
    dense = dataclasses.replace(cfg, hybrid_override_pattern="ME-EM")
    with pytest.raises(NotImplementedError, match="'-'"):
        cache_spec.layer_plan(dense)
    with pytest.raises(ValueError, match="names 4 layers"):
        cache_spec.layer_plan(dataclasses.replace(
            cfg, hybrid_override_pattern="ME*E"))


@pytest.mark.parametrize("length", [5, 8, 21, 40])
def test_whole_sequence_forward_agrees_with_the_reference(ref, cfg, params,
                                                          length):
    """Under a chunk of the SSD form (8), one whole, and several with a
    padded last one."""
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


@pytest.mark.parametrize("n_prompt", [3, CHUNK, 2 * CHUNK + 1,
                                      5 * CHUNK + PAGE + 3])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(
        ref, cfg, params, n_prompt):
    """Prompts under a chunk, of one whole chunk, and past several (a
    page's edge among them): the prefill calls after the first start from
    the slot's state and tail and the pages' keys; then 19 decode steps
    (across a page's edge) through state, tail and pages, each step's
    logits against the reference's full forward of the whole sequence; the
    step's counters; at the end both Mamba-2 layers' states are the
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
    before = [np.asarray(a[0]) for rows in pools[1] for a in rows]
    names = hybrid.load_names(cfg)
    # a new kind's entry is the vector's last
    assert names[-1] == "ssd_state_rows" and "paged_rows_read" in names
    for t in range(n_prompt, n_prompt + n_new):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.array([0, ids[t]]), jnp.array([0, t]), pools,
            jnp.asarray(table), jnp.array([0, t]), active=live)
        np.testing.assert_allclose(np.asarray(logits[1]), want[t],
                                   atol=LOGIT_TOL, rtol=0)
        counted = dict(zip(names, load.tolist()))
        # ONE attention layer's keys, two Mamba-2 layers' states, two
        # expert layers' choices of one live row
        assert counted["paged_rows_read"] == t + 1
        assert counted["ssd_state_rows"] == 2
        assert counted["moe_choices"] == 2 * cfg.num_experts_per_tok
    # a row without a request left its state and its tail as they were
    after = [np.asarray(a[0]) for rows in pools[1] for a in rows]
    for a, was in zip(after, before):
        np.testing.assert_array_equal(a, was)
    tr = ref.trace(params, c, ids.tolist(), n_prompt, n_new)
    held = hybrid.held_state(cfg, pools[1], 1)
    assert len(held) == 2 and held[0].shape == (4, 16, 16)
    for mine, theirs in zip(held, tr["states"]):
        np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL, rtol=1e-5)


def test_the_chunked_ssd_form_is_the_recurrence():
    """``ssd_chunked`` over 37 positions in steps of 8 (a chunk's edge
    inside, 3 positions of padding in the last and a row padded by none)
    against ``ssd_recurrent_step`` a position at a time."""
    rng = np.random.default_rng(5)
    b, t, g, r, p, n = 2, 40, 2, 3, 4, 16
    w = r * p
    x = jnp.asarray(rng.normal(size=(b, t, g, w)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, t, g, n)), jnp.float32)
              for _ in range(2))
    la = -jnp.asarray(rng.uniform(0.01, 1.5, size=(b, t, g * r)), jnp.float32)
    valid = jnp.asarray(np.arange(t)[None] < np.array([[37], [40]]))
    x = jnp.where(valid[..., None, None], x, 0.0)
    la = jnp.where(valid[..., None], la, 0.0)
    s0 = jnp.asarray(rng.normal(size=(b, g, n, w)), jnp.float32)
    state, y = mamba2.ssd_chunked(s0, x, bm, cm, la, 8)
    s, outs = s0, []
    a = jnp.repeat(jnp.exp(la), p, axis=-1).reshape(b, t, g, w)
    for i in range(t):
        s, yi = ssd_state.ssd_recurrent_step(s, x[:, i], a[:, i], bm[:, i],
                                             cm[:, i])
        outs.append(yi)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s), atol=2e-5,
                               rtol=2e-6)
    want = np.stack([np.asarray(v) for v in outs], 1)
    np.testing.assert_allclose(np.asarray(y)[0, :37], want[0, :37], atol=2e-5,
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(y)[1], want[1], atol=2e-5,
                               rtol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Guide section 4: the PROGRAM's routed experts of each of the two
    shares (experts 0-3 and 4-7 of 8, the router whole in both) plus the
    shared expert ONCE are the reference's uncut layer."""
    whole = dataclasses.replace(cfg, experts_held=None)
    tree = decoder.init_params(jax.random.PRNGKey(3), whole)
    moe = tree["layers"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))
    c_whole = file_keys(whole)
    layer = 1
    uncut = (ref.routed_block(tree, c_whole, layer, h)
             + ref.shared_block(tree, c_whole, layer, h))
    total = np.zeros_like(uncut)
    for first in (0, 4):
        part = dataclasses.replace(cfg, experts_held=(first, 4))
        mine = {k: (v[:, first:first + 4] if k in blocks.EXPERT_KEYS else v)
                for k, v in moe.items()}
        lp = {k: v for k, v in hybrid._layer_params(
            part, {**tree["layers"], "moe": mine}, 3)[1].items()
            if not k.startswith("ws_")}
        routed = np.asarray(blocks._moe_mlp(part, h, lp, None, layer)[0])
        want = ref.routed_block(tree, c_whole, layer, h, first=first, count=4)
        np.testing.assert_allclose(routed, want, atol=LOGIT_TOL)
        assert np.abs(want).max() > 1e-3
        total += routed
    lp = hybrid._layer_params(whole, tree["layers"], 3)[1]
    both = np.asarray(blocks._moe_mlp(whole, h, lp, None, layer)[0])
    shared = both - np.asarray(blocks._moe_mlp(
        whole, h, {k: v for k, v in lp.items() if not k.startswith("ws_")},
        None, layer)[0])
    np.testing.assert_allclose(total + shared, uncut, atol=LOGIT_TOL)
    np.testing.assert_allclose(both, uncut, atol=LOGIT_TOL)


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts under and over a chunk and across a page's edge through
    ``CBEngine`` (chunked prefill from and to state, tail and pages, the
    fused multi-step decode dispatch): every sampled token's
    log-probability against the reference's score of the same sequence;
    the profiler's counters against the client's count."""
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
    steps = [(len(p) + i + 1) for p in prompts for i in range(11)]
    assert counted["ssd_state_rows"] == 2 * len(steps)
    assert counted["paged_rows_read"] == sum(steps)
    assert counted["ssd_kernel_steps"] == 0      # the oracle, off a TPU
    assert eng.moe_info()["moe_choices"] == 2 * 2 * len(steps)


@pytest.mark.parametrize("floor, want", [
    (1, {("ext", 1), ("ext", 2), ("ext", 4), ("ext", 8), ("ext", 16),
         ("sfx", 4), ("sfx", 16)}),
    (16, {("ext", 16), ("sfx", 16)})])
def test_a_floor_on_the_prefix_buckets_builds_fewer_programs(ref, cfg, params,
                                                             floor, want):
    """``prefix_pages_floor``: every chunk of a prompt attends over at
    least that many prefix pages (the padded ones masked by the prefix's
    length), so the chunks of prompts of every length share ONE extend
    and ONE final program where the default builds one a power of two;
    the log-probabilities are the reference's either way."""
    eng = _engine(cfg, params, prefix_pages_floor=floor)
    prompts = _prompts([41, 100], seed=7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=6, stop_token_ids=())
    try:
        outs = eng.generate(prompts, sp)
    finally:
        eng.stop()
    assert {(k[0], k[2]) for k in eng._prefill_fns
            if k[0] in ("ext", "sfx")} == want
    for prompt, out in zip(prompts, outs):
        score, _ = ref.score(params, file_keys(cfg),
                             prompt + out["token_ids"], 6)
        np.testing.assert_allclose(out["logprobs"], score, atol=LOGP_TOL,
                                   rtol=0)
    with pytest.raises(ValueError, match="prefix_pages_floor"):
        _engine(cfg, params, prefix_pages_floor=21)


def test_a_reused_slot_starts_from_a_zero_state(ref, cfg, params):
    """One slot, two requests after each other: the second's
    log-probabilities are the reference's, whatever the first left in the
    slot's state and tail."""
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
    """``CBEngine.recurrent_state``: the Mamba-2 layers' states as the
    published ``[H, P, N]`` of a request that is decoding, in layer order,
    against the reference after the tokens it has consumed."""
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
    assert len(rows) == 2 and rows[0].shape == (4, 16, 16)
    for mine, theirs in zip(rows, tr["states"]):
        np.testing.assert_allclose(mine, theirs, atol=LOGIT_TOL, rtol=1e-5)


@pytest.mark.parametrize("control, moves", [
    ("state_bf16", "state"), ("no_decay", "state"),
    ("int8_experts", "logprobs")])
def test_each_control_moves_what_its_limit_watches(ref, cfg, params, control,
                                                   moves):
    """The reference under each control of ``correct`` leaves the sound
    reference by far more than the program does."""
    seq = _prompts([60], seed=13)[0]
    c = file_keys(cfg)
    sound = ref.trace(params, c, seq, 40, 20)
    moved = ref.trace(params, c, seq, 40, 20, control)
    # the slowest quarter of the first layer's 4 heads, the same whatever
    # the control (the reference's own dt and A)
    assert sound["slow"][0].shape == (1,) and len(sound["slow"]) == 2
    np.testing.assert_array_equal(moved["slow"][0], sound["slow"][0])
    if moves == "state":
        a, b = moved["states"][0], sound["states"][0]
        assert np.linalg.norm(a - b) / np.linalg.norm(b) > 1e-3
    else:
        assert np.abs(moved["logprobs"] - sound["logprobs"]).max() > 1e-4
        np.testing.assert_array_equal(moved["states"][0], sound["states"][0])
    first = ref.trace(params, c, seq, 40, 20, control, upto=1)
    np.testing.assert_array_equal(first["states"][0], moved["states"][0])
    assert first["logprobs"] is None and len(first["states"]) == 1


def test_float8_weights_move_the_log_probabilities(ref, cfg, params):
    """``fp8_weights``, the control of the log-probabilities' limits: every
    matrix of every sublayer and the head at three bits of mantissa leaves
    the sound reference's log-probabilities by hundredths of a nat, and
    the routed experts alone by what ``routed_block`` never shows (its
    ``control`` is the experts' int8 alone)."""
    seq = _prompts([60], seed=13)[0]
    c = file_keys(cfg)
    sound = ref.trace(params, c, seq, 40, 20)
    moved = ref.trace(params, c, seq, 40, 20, "fp8_weights")
    assert np.abs(moved["logprobs"] - sound["logprobs"]).mean() > 1e-3
    a, b = moved["states"][0], sound["states"][0]
    assert np.linalg.norm(a - b) / np.linalg.norm(b) > 1e-3
    h = sound["moe_in"][0]
    np.testing.assert_array_equal(
        ref.routed_block(params, c, 0, h, "fp8_weights"),
        ref.routed_block(params, c, 0, h))
