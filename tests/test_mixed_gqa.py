"""Laguna's decoder (``models/hybrid.py`` with ``mixers/gqa.py``: rope'd
softmax GQA of two kinds in one plan, full layers of 6 query heads in pages
under YaRN on half of a head's columns, window layers of 8 query heads in
rings of the slot's under a plain rope, a sigmoid gate a head, a softmax
router whose renormalised weights are scaled beside a shared expert, 4 of
16 experts held) at the ``mixed-tiny`` preset on the CPU, in float32,
against the benchmark's plain reference
(``benchmark/references/moe_gqa_mixed.py``: whole sequences, a blocked
softmax, every held expert on every position).

The limits are float32's: the program and the reference compute the same
sums in another order (a ring against a masked whole sequence, the paged
kernel's oracle against a blocked softmax, a grouped matmul over sorted
rows against every expert in turn), each a few ulps of a value of order 1,
through 5 layers: 5e-6 on logits of at most 0.75 in magnitude; readings
are 1e-7 to 5e-7. A wrong position, mask, page, ring row, rope or gate
moves a logit by 1e-3 or more, and bfloat16 in float32's place by 5e-3
(``test_bfloat16_fails_the_limit``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import blocks, cache_spec, decoder, hybrid, mixers
from polyrl_tpu.models.mixers import gqa
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

LOGIT_TOL = 5e-6
LOGP_TOL = 5e-6
CHUNK, PAGE, WINDOW = 16, 4, 8


def file_keys(cfg) -> dict:
    """A ``ModelConfig`` of the family under the published keys that the
    reference reads, the per-layer lists cut to the kept layers."""
    kept = cfg.kept_layers or tuple(range(cfg.num_layers))

    def block(r):
        s = r.scaling
        out = {"rope_theta": r.rope_theta, "rope_type": "default",
               "partial_rotary_factor": r.partial_rotary_factor}
        if s is not None:
            out.update(rope_type=s.rope_type, factor=s.factor,
                       original_max_position_embeddings=
                       s.original_max_position_embeddings,
                       beta_slow=s.beta_slow, beta_fast=s.beta_fast,
                       attention_factor=s.attention_factor)
        return out

    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "sliding_window": cfg.sliding_window,
        "rms_norm_eps": cfg.rms_norm_eps,
        "layer_types": [cfg.layer_types[i] for i in kept],
        "num_attention_heads_per_layer":
            [cfg.num_heads_per_layer[i] for i in kept],
        "mlp_layer_types": ["sparse" if i >= cfg.first_k_dense_replace
                            else "dense" for i in kept],
        "rope_parameters": {t: block(r) for t, r in cfg.rope_parameters},
        "num_experts": cache_spec.experts_held(cfg)[1],
        "experts_held": list(cache_spec.experts_held(cfg)),
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size":
            cfg.moe_shared_expert_intermediate_size,
        "moe_routed_scaling_factor": cfg.routed_scaling_factor,
        "gating": cfg.attn_head_gate,
        "tie_word_embeddings": cfg.tie_word_embeddings}


@pytest.fixture(scope="module")
def ref():
    return harness.load_named("references", "moe_gqa_mixed")


@pytest.fixture(scope="module")
def cfg():
    return decoder.get_config("mixed-tiny", dtype=jnp.float32)


def _wide(cfg, rng):
    """The preset's weights with the router's and the gate's drawn ten
    times as wide: at 0.02 every gate is a half and every expert's
    probability a sixteenth, and a gate or a weight that is left out or
    misplaced would hardly show."""
    tree = decoder.init_params(rng, cfg)

    def drawn(path, a):
        return a * 10.0 if path[-1].key in ("router", "wg") else a

    return jax.tree_util.tree_map_with_path(drawn, tree)


@pytest.fixture(scope="module")
def params(cfg):
    return _wide(cfg, jax.random.PRNGKey(0))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, page_size=PAGE, max_seq_len=128,
                prompt_buckets=(16, 64), num_pages=120, prefill_chunk=CHUNK,
                steps_per_dispatch=4, kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def test_the_two_kinds_follow_from_the_published_keys(cfg):
    plan = cache_spec.layer_plan(cfg)
    assert [p.mixer for p in plan] == ["gqa", "gqa_window", "gqa_window",
                                       "gqa_window", "gqa"]
    assert [p.mlp for p in plan] == ["dense"] + ["moe"] * 4
    assert cache_spec.is_stateful(cfg) and not cache_spec.is_uniform(cfg)
    ring = cache_spec.Ring(2, 16, 8, jnp.float32)
    pages = cache_spec.Paged(2, 2, 16)
    assert cache_spec.cache_spec(cfg) == (pages, ring, ring, ring, pages)
    assert [cache_spec.gqa_heads(cfg, p) for p in plan] == [6, 8, 8, 8, 6]
    assert cache_spec.gqa_rope(cfg, plan[0]).scaling.rope_type == "yarn"
    assert cache_spec.gqa_rope(cfg, plan[1]) == decoder.RopeParameters(100.0)
    # two full layers' K/V a token; three rings a slot
    assert cache_spec.paged_bytes_per_token(cfg) == 2 * 2 * 2 * 16 * 4
    assert cache_spec.slot_bytes(cfg) == 3 * 2 * 2 * 16 * 8 * 4
    for feature in cache_spec.FEATURE_KERNELS:
        assert cache_spec.without_kernel(cfg, feature) == ("gqa",
                                                           "gqa_window")
    assert hybrid.load_names(cfg)[:4] == (*hybrid.MOE_LOAD,
                                          hybrid.MOE_CHOICES)
    assert {"paged_rows_read", "window_rows_read"} <= set(
        hybrid.load_names(cfg))
    # the published model: every fourth layer full at 48 heads, the rest
    # windows of 512 at 64; 33.4 B parameters, the norms' 165,888 aside
    full = decoder.get_config("laguna-xs.2")
    kinds = [p.mixer for p in cache_spec.layer_plan(full)]
    assert kinds == ["gqa", "gqa_window", "gqa_window", "gqa_window"] * 10
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), full))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == 33_442_430_976 + 165_888
    # one chip's share: layers 0-8, experts 0-31, the whole vocabulary
    cut = decoder.get_config("laguna-xs.2-share8")
    assert [p.published for p in cache_spec.layer_plan(cut)] == list(range(9))
    assert cache_spec.experts_held(cut) == (0, 32)
    assert cut.vocab_size == 100_352
    assert cache_spec.paged_bytes_per_token(cut) == 3 * 4096
    assert cache_spec.slot_bytes(cut) == 6 * 2 * 1024 * 1024
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == 1_611_694_080 + 38_912
    with pytest.raises(ValueError, match="query heads in one stack"):
        decoder.init_params(jax.random.PRNGKey(0), dataclasses.replace(
            cfg, num_heads_per_layer=(6, 8, 8, 4, 6)))


def test_the_ropes_are_the_published_blocks(ref, cfg):
    """YaRN over the turned half of a head as the reference reads the
    published block, the published ``attention_factor`` on cos and sin;
    the window layers' plain rope over the whole head."""
    from polyrl_tpu.models.mixers import base

    keys = file_keys(decoder.get_config("laguna-xs.2"))["rope_parameters"]
    full = decoder.get_config("laguna-xs.2")
    plan = cache_spec.layer_plan(full)
    for p, kind in ((plan[0], "full_attention"),
                    (plan[1], "sliding_attention")):
        r = cache_spec.gqa_rope(full, p)
        want = ref.rope_of(keys[kind], 128)
        rot = int(128 * r.partial_rotary_factor)
        np.testing.assert_allclose(
            base.yarn_inv_freq(r.rope_theta, rot, r.scaling),
            np.asarray(want.inv_freq), rtol=1e-14)
        assert base.yarn_amplitude(r.scaling) == want.amplitude
    want = ref.rope_of(keys["full_attention"], 128)
    assert len(want.inv_freq) == 32 and want.amplitude == 1.4158883083359672
    # the fastest frequencies are kept, the slowest divided by 64
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    # (64 turns in 4,096 positions at dimension 5.7, one at 15.8)
    np.testing.assert_allclose(want.inv_freq[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(want.inv_freq[16:], plain[16:] / 64.0,
                               rtol=1e-12)
    assert (np.asarray(want.inv_freq[6:16]) < plain[6:16]).all()


@pytest.mark.parametrize("length", [5, WINDOW, 37, 64])
def test_whole_sequence_forward_agrees_with_the_reference(ref, cfg, params,
                                                          length):
    ids = np.asarray(_prompts([length], seed=length)[0])
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids)[None],
                             jnp.arange(length)[None], jnp.ones((1, length)))
    want = ref.logits(params, file_keys(cfg), ids)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


def test_bfloat16_fails_the_limit(ref, cfg, params):
    """The limit is tight enough that the program in bfloat16 in float32's
    place fails it."""
    ids = np.asarray(_prompts([37], seed=37)[0])
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got, _ = decoder.forward(tree, low, jnp.asarray(ids)[None],
                             jnp.arange(37)[None], jnp.ones((1, 37)))
    want = ref.logits(params, file_keys(cfg), ids)
    err = np.abs(np.asarray(got[0], np.float32) - np.asarray(want)).max()
    assert err > 100 * LOGIT_TOL


def test_padding_on_the_right_leaves_the_real_positions_alone(cfg, params):
    ids = jnp.asarray(_prompts([24])[0])[None]
    pos = jnp.arange(24)[None]
    whole, _ = decoder.forward(params, cfg, ids, pos, jnp.ones((1, 24)))
    mask = (jnp.arange(24) < 17).astype(jnp.float32)[None]
    cut, _ = decoder.forward(params, cfg, ids.at[:, 17:].set(0), pos, mask)
    np.testing.assert_allclose(np.asarray(cut[0, :17]),
                               np.asarray(whole[0, :17]), atol=LOGIT_TOL)


def _ring_rows(ring, consumed: int):
    """The rows of a held ring ``ring`` [window, Hkv, 2D] that hold a token
    after ``consumed`` tokens, oldest first."""
    w = ring.shape[0]
    return np.stack([ring[t % w] for t in range(max(0, consumed - w),
                                                consumed)])


def _prefill(cfg, params, pools, ids, n_prompt, pages, slot):
    """``ids[:n_prompt]`` through ``hybrid.prefill`` in chunks of ``CHUNK``
    into the pages ``pages`` and the slot ``slot``: (pools, each chunk's
    (last position, last-token logits))."""
    per, seen = CHUNK // PAGE, []
    for at in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - at)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :n] = ids[at:at + n]
        done = at // PAGE
        pools, logits = hybrid.prefill(
            params, cfg, jnp.asarray(chunk), jnp.array([n]), jnp.int32(at),
            pools, jnp.asarray(pages[None, :done]),
            jnp.asarray(pages[None, done:done + per]), jnp.array([slot]))
        seen.append((at + n - 1, np.asarray(logits[0])))
    return pools, seen


def _decode(cfg, params, pools, ids, start, stop, pages):
    """Tokens ``ids[start:stop]`` one a step through the row 1 of two (row
    0 has no request): (pools, each step's (logits of row 1, load))."""
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


@pytest.mark.parametrize("n_prompt", [3, WINDOW - 1, WINDOW, CHUNK,
                                      2 * CHUNK + 1, 2 * CHUNK + PAGE])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(
        ref, cfg, params, n_prompt):
    """Prompts under the window, as long as it, of one whole chunk, and
    past two chunks (a page boundary among them): the prefill calls after
    the first start from the slot's rings and the full layers' pages; then
    11 decode steps (across the ring's wrap and a page boundary) through
    rings and pages, each step's logits against the reference's full
    forward of the whole sequence; at the end each ring, placed by ``t %
    window``, is the reference's rotated keys and values."""
    n_new = 11
    ids = np.asarray(_prompts([n_prompt + n_new], seed=n_prompt)[0], np.int32)
    want = np.asarray(ref.logits(params, file_keys(cfg), ids))
    pools = decoder.make_paged_pools(cfg, 24, PAGE, dtype=jnp.float32,
                                     slots=3)
    # what the slot's last request left behind must not be read
    pools = jax.tree_util.tree_map(lambda a: a + 7.0, pools)
    pages = np.arange(1, 17, dtype=np.int32)     # the row's pages in order
    pools, seen = _prefill(cfg, params, pools, ids, n_prompt, pages, 1)
    for at, logits in seen:
        np.testing.assert_allclose(logits, want[at], atol=LOGIT_TOL, rtol=0)
    before = [np.asarray(a) for a in pools[1][0]]
    pools, seen = _decode(cfg, params, pools, ids, n_prompt, n_prompt + n_new,
                          pages)
    for t, (logits, load) in zip(range(n_prompt, n_prompt + n_new), seen):
        np.testing.assert_allclose(logits, want[t], atol=LOGIT_TOL, rtol=0)
        # keys of the 2 full layers' pages, keys of the 3 rings
        assert load["paged_rows_read"] == 2 * (t + 1)
        assert load["window_rows_read"] == 3 * min(t + 1, WINDOW)
        assert load["moe_choices"] == 4 * cfg.num_experts_per_tok
    # the row without a request wrote to no ring but its own null page
    n_ring = WINDOW // PAGE
    for a, b in zip(pools[1][0], before):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:1 + n_ring],
                                      b[:, 1:1 + n_ring])
    n = n_prompt + n_new
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), n_prompt, n_new)
    held = hybrid.held_state(cfg, pools[1], 1)
    assert len(held) == len(tr["rings"]) == 3
    for mine, (theirs, first) in zip(held, tr["rings"]):
        assert first == n - WINDOW and mine.shape == (WINDOW, 2, 32)
        np.testing.assert_allclose(_ring_rows(mine, n), theirs,
                                   atol=LOGIT_TOL)


@pytest.mark.parametrize("length", [WINDOW - 3, WINDOW, WINDOW + 5])
def test_the_ring_is_the_last_window_tokens_rotated_as_written(
        ref, cfg, params, length):
    """One prefill call of a sequence shorter than, as long as and longer
    than the window: the rows of a window layer's ring that hold a token
    are the reference's rotated keys and values of the last ``window``
    tokens, and a row that holds none is what it was."""
    ids = np.asarray(_prompts([length], seed=40 + length)[0], np.int32)
    pools = decoder.make_paged_pools(cfg, 12, PAGE, dtype=jnp.float32,
                                     slots=3)
    pools = jax.tree_util.tree_map(lambda a: a + 3.0, pools)
    pools, _ = _prefill(cfg, params, pools, ids, length,
                        np.arange(1, 1 + CHUNK // PAGE, dtype=np.int32), 2)
    tr = ref.trace(params, file_keys(cfg), ids.tolist(), length - 1, 1)
    held = [hybrid.held_state(cfg, pools[1], slot) for slot in range(3)]
    for l, (theirs, first) in enumerate(tr["rings"]):
        mine = held[2][l]
        assert first == max(0, length - WINDOW)
        np.testing.assert_allclose(_ring_rows(mine, length), theirs,
                                   atol=LOGIT_TOL)
        untouched = [r for r in range(WINDOW) if r >= length]
        assert (mine[untouched] == 3.0).all()
        # and no other slot's pages were written
        for other in (0, 1):
            assert (held[other][l] == 3.0).all()


def test_the_shares_add_up_to_the_uncut_layer(ref, cfg):
    """The four shares' parts of a sparse layer (experts 0-3, 4-7, 8-11,
    12-15 of 16, the router whole in each), the shared expert counted
    once, add up to the uncut reference's layer; and the program's block
    on a share is the reference's on that share."""
    whole = dataclasses.replace(cfg, experts_held=None)
    tree = _wide(whole, jax.random.PRNGKey(5))
    moe = tree["layers"]["moe"]
    keys = file_keys(whole)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (40, 64)),
                   np.float32)
    for layer in (0, 3):
        uncut = ref.routed_block(tree, keys, layer, h)
        parts = []
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            mine = {**tree, "layers": {**tree["layers"], "moe": {
                k: v[:, first:first + 4] if k in ref.EXPERTS else v
                for k, v in moe.items()}}}
            part = ref.routed_block(mine, keys, layer, h, held=(first, 4))
            parts.append(part)
            lp = hybrid._layer_params(share, mine["layers"], layer + 1)[1]
            lp = {k: v for k, v in lp.items() if not k.startswith("ws_")}
            got, load = blocks._moe_mlp(share, jnp.asarray(h), lp, None,
                                        layer)
            np.testing.assert_allclose(np.asarray(got), part, atol=LOGIT_TOL)
            # 40 rows x 4 choices, of which this share holds some
            assert 0 < int(load[0]) < 160
        np.testing.assert_allclose(sum(parts), uncut, atol=LOGIT_TOL)
        assert min(np.abs(p).max() for p in parts) > 1e-3
        # the whole layer: every share's part and the shared expert once
        lp = hybrid._layer_params(whole, tree["layers"], layer + 1)[1]
        got, _ = blocks._moe_mlp(whole, jnp.asarray(h), lp, None, layer)
        np.testing.assert_allclose(
            np.asarray(got), uncut + ref.shared_block(tree, keys, layer, h),
            atol=LOGIT_TOL)


def ring_rel(mine, theirs) -> float:
    return float(np.linalg.norm(mine - theirs) / np.linalg.norm(theirs))


FAULT_WINDOW, FAULT_PROMPT, FAULT_NEW = 128, 140, 16


def _served(cfg_run, tree, ids):
    """``ids`` through chunked prefill and decode as the engine runs them:
    (the log-probabilities of ``ids[FAULT_PROMPT + 1:]``, the first window
    layer's ring at the end, float32)."""
    n_prompt, n_new = FAULT_PROMPT, FAULT_NEW
    pools = decoder.make_paged_pools(cfg_run, 48, PAGE, dtype=cfg_run.dtype,
                                     slots=3)
    pages = np.arange(1, 41, dtype=np.int32)
    pools, _ = _prefill(cfg_run, tree, pools, ids, n_prompt, pages, 1)
    pools, seen = _decode(cfg_run, tree, pools, ids, n_prompt,
                          n_prompt + n_new - 1, pages)
    logp = jax.nn.log_softmax(
        np.stack([s[0] for s in seen]).astype(np.float32), axis=-1)
    lps = np.asarray(logp)[np.arange(n_new - 1), ids[n_prompt + 1:]]
    return lps, hybrid.held_state(cfg_run, pools[1], 1)[0]


@pytest.fixture(scope="module")
def fault_case(ref, cfg):
    """A model with a window of 128 and weights as they are drawn (0.02:
    attention near uniform, as the benchmark's are); one sequence; the
    reference's trace of it; and what a SOUND program in bfloat16 reads
    against the reference: the mean log-probability difference and the
    first window layer's ring, position by position."""
    wide = dataclasses.replace(cfg, sliding_window=FAULT_WINDOW)
    tree = decoder.init_params(jax.random.PRNGKey(2), wide)
    keys = file_keys(wide)
    ids = np.asarray(_prompts([FAULT_PROMPT + FAULT_NEW], seed=9)[0],
                     np.int32)
    sound = ref.trace(tree, keys, ids.tolist(), FAULT_PROMPT, FAULT_NEW)
    n = FAULT_PROMPT + FAULT_NEW - 1
    ring, first = ref.trace(tree, keys, ids[:n].tolist(), FAULT_PROMPT,
                            1)["rings"][0]
    assert first == n - FAULT_WINDOW
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tree)
    lps, mine = _served(dataclasses.replace(wide, dtype=jnp.bfloat16), low,
                        ids)
    return {"cfg": wide, "tree": tree, "keys": keys, "ids": ids,
            "sound": sound, "ring": ring, "tokens": n,
            "bf16_lp": float(np.abs(lps - sound["logprobs"][1:]).mean()),
            "bf16_ring": ring_rel(_ring_rows(mine, n), ring)}


@pytest.mark.parametrize("fault", ["window_minus", "ring_one_place_on"])
def test_a_window_fault_is_caught_by_the_ring_and_missed_by_the_logprobs(
        ref, fault_case, fault, monkeypatch):
    """The direction ``window_rel_diff`` relies on (``planes/
    rollout_mixed.py``): with weights as they are drawn a window one key
    short, or a ring written one place on, moves the log-probabilities of
    the sampled tokens by about what bfloat16's own rounding moves them
    (under twice a sound bf16 reading: no limit between the two has room
    on both sides), and the first window layer's ring, compared
    position by position, by a row's whole weight: fifteen times a sound
    bf16 reading and more."""
    case = fault_case
    wide, tree, ids, sound = (case["cfg"], case["tree"], case["ids"],
                              case["sound"])
    if fault == "window_minus":
        # the REFERENCE with a window of 127 in the program's place, as the
        # control on the chip has it
        off = ref.trace(tree, case["keys"], ids.tolist(), FAULT_PROMPT,
                        FAULT_NEW, control="window_minus")
        lp_diff = np.abs(off["logprobs"] - sound["logprobs"]).mean()
        (a, a0), (b, b0) = off["rings"][0], sound["rings"][0]
        assert a0 == b0 + 1
        rows = np.zeros_like(b)
        rows[1:] = a
        ring_diff = ring_rel(rows, b)
    else:
        # the PROGRAM writing a step's keys one ring row on
        per_step = mixers.MIXERS["gqa_window"].per_step

        def one_on(cfg_, ctx):
            ring = per_step(cfg_, ctx)
            ring.off = jnp.where(ctx.live, (ring.off + 1) % PAGE, 0)
            return ring

        lps, _ = _served(wide, tree, ids)
        np.testing.assert_allclose(lps, sound["logprobs"][1:],
                                   atol=LOGP_TOL)
        monkeypatch.setitem(mixers.MIXERS, "gqa_window", dataclasses.replace(
            mixers.MIXERS["gqa_window"], per_step=one_on))
        lps, ring = _served(wide, tree, ids)
        lp_diff = np.abs(lps - sound["logprobs"][1:]).mean()
        ring_diff = ring_rel(_ring_rows(ring, case["tokens"]), case["ring"])
    # readings: 0.0013 and 0.0016 nats against bf16's 0.0010; 0.096 (one
    # row of 128: sqrt(1/128) = 0.088) and 0.51 against bf16's 0.0043
    assert 0 < lp_diff < 2 * case["bf16_lp"]
    assert ring_diff > 15 * case["bf16_ring"]


def test_the_engine_serves_it_and_scores_as_the_reference_does(ref, cfg,
                                                               params):
    """Prompts shorter than the window, longer than a chunk and across a
    page boundary through ``CBEngine`` (chunked prefill from and to rings
    and pages, the fused multi-step decode dispatch): every sampled
    token's log-probability against the reference's score of the same
    sequence; the profiler's counters against the client's count."""
    eng = _engine(cfg, params)
    prompts = _prompts([5, 17, 33, 47], seed=7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=12, stop_token_ids=())
    try:
        outs = eng.generate(prompts, sp)
        counted = eng.profiler.counters()
        info = eng.moe_info()
    finally:
        eng.stop()
    assert eng.stateful and eng.prefix_cache is None
    assert eng.chunk_dispatches > 0
    for prompt, out in zip(prompts, outs):
        toks, lps = out["token_ids"], out["logprobs"]
        assert len(toks) == 12
        want, _ent = ref.score(params, file_keys(cfg), prompt + toks, 12)
        np.testing.assert_allclose(lps, want, atol=LOGP_TOL, rtol=0)
    # a request's decode steps: its 2nd to 12th token (the first is the
    # prefill's), each over the keys before it and itself
    steps = [(len(p) + i + 1) for p in prompts for i in range(11)]
    assert counted["paged_rows_read"] == 2 * sum(steps)
    assert counted["window_rows_read"] == 3 * sum(min(n, WINDOW)
                                                  for n in steps)
    assert info["moe_choices"] == 4 * 4 * len(steps)
    assert 0 < info["moe_routed"] < info["moe_choices"]


def test_a_reused_slot_starts_from_an_empty_ring(ref, cfg, params):
    """One slot, two requests after each other: the second's
    log-probabilities are the reference's, whatever the first left in the
    slot's rings."""
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


def test_the_scopes_of_a_decode_step(cfg, params):
    """The scopes the per-layer metrics read, in a decode step's lowered
    text: the projections, the two cores, the output product, the routed
    MLP's three and the head."""
    pools = jax.eval_shape(lambda: decoder.make_paged_pools(
        cfg, 24, PAGE, dtype=jnp.float32, slots=3))

    def step(params, pools):
        return decoder.forward_paged_decode(
            params, cfg, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), pools, jnp.zeros((2, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32))

    text = jax.jit(step).lower(params, pools).as_text(debug_info=True)
    for scope in ("attn_qkv", "attn_core", "swa_core", "attn_out",
                  "mlp/moe_route", "mlp/moe_experts", "mlp/moe_shared",
                  "head"):
        assert scope in text, scope
    assert gqa.GQA.counts == ("paged_rows_read",)
    assert gqa.GQA_WINDOW.counts == ("window_rows_read",)


@pytest.mark.parametrize("rows", [9, 40])
def test_a_share_of_the_experts_by_table_is_the_tiled_path(monkeypatch, cfg,
                                                           params, rows):
    """A decode step's form on a TPU (``blocks._expert_rows``),
    interpreted, on the share of the experts the preset holds, with the
    routed scaling factor and the shared expert."""
    from tests.moe_forms import assert_both_forms_agree

    l = max(l for l, p in enumerate(cache_spec.layer_plan(cfg))
            if p.mlp == "moe")
    lp = hybrid._layer_params(cfg, params["layers"], l)[1]
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, cfg.hidden_size))
    assert_both_forms_agree(monkeypatch, cfg, x, lp, jnp.arange(rows) != 1,
                            hybrid.kind_index(cfg)[l][1], atol=LOGIT_TOL)
