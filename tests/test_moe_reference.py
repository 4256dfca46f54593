"""The dropless MoE block against the benchmark's plain float32 reference
(``benchmark/references/moe_gqa.py``: every expert applied to every
position, weighted by the top-k renormalised softmax; no sort, no grouped
matmul, nothing of the code under test) on seeded weights at a tiny size.

Tolerance, float32 on both sides: 1e-4 relative to the largest logit (or
log-prob, or gradient entry) of the comparison. The two sides differ in
summation order only (the system sums an expert's rows in a grouped
matmul and a token's k experts last; the reference sums over all experts
with zeros), which at these sizes is 1e-6; rounding the experts alone to
bf16 moves the logits by 3e-4 of their scale and fails it
(``test_bf16_experts_exceed_the_tolerance``)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import blocks, decoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-4
# the published keys moe_gqa reads, for the tiny model below
SIZES = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "qk_norm": True, "num_experts": 8, "num_experts_per_tok": 2,
         "moe_intermediate_size": 96, "norm_topk_prob": True}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "moe_gqa_under_test",
        os.path.join(ROOT, "benchmark", "references", "moe_gqa.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(seed=0, **over):
    cfg = decoder.get_config(
        "moe-tiny", dtype=jnp.float32, num_experts=8, rms_norm_eps=1e-6,
        **over)
    return cfg, decoder.init_params(jax.random.PRNGKey(seed), cfg)


def _same_experts_router(params):
    """A router of zeros: every expert's probability is 1/E exactly, and
    ``lax.top_k`` breaks the tie by index, so every token goes to experts
    0..k-1, each with weight 1/k: the most uneven routing there is."""
    layers = dict(params["layers"])
    layers["router"] = jnp.zeros_like(layers["router"])
    return {**params, "layers": layers}


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err <= rtol, err
    return err


def _ids(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


def _forward_logits(params, cfg, ids):
    t = len(ids)
    logits, _ = decoder.forward(
        params, cfg, jnp.asarray(ids)[None], jnp.arange(t)[None],
        jnp.ones((1, t)))
    return logits[0]


def _prefill_then_decode_logits(params, cfg, ids, n_prompt, page=8):
    """Logits of positions n_prompt-1 .. len(ids)-1: the prompt prefilled
    in two chunks (``prefill_into_pages`` then
    ``prefill_suffix_into_pages``, as a chunked admission does), then one
    ``forward_paged_decode`` step a token, teacher-forced, beside an
    inactive second row."""
    pools = decoder.make_paged_pools(cfg, 32, page)
    first = (n_prompt // 2) // page * page          # whole pages
    n_pg = -(-len(ids) // page)
    pages = jnp.arange(1, 1 + n_pg, dtype=jnp.int32)

    def padded(chunk, width):
        return jnp.asarray(np.pad(chunk, (0, width - len(chunk))))

    pools, _ = decoder.prefill_into_pages(
        params, cfg, padded(ids[:first], first), jnp.int32(first), pools,
        pages[:first // page])
    rest = n_prompt - first
    width = -(-rest // page) * page
    pools, last = decoder.prefill_suffix_into_pages(
        params, cfg, padded(ids[first:n_prompt], width), jnp.int32(rest),
        jnp.int32(first), pools, pages[:first // page],
        pages[first // page:first // page + width // page])
    out = [last]
    table = jnp.stack([jnp.pad(pages, (0, 8 - n_pg)),
                       jnp.zeros(8, jnp.int32)])
    for pos in range(n_prompt, len(ids)):
        logits, pools, load = decoder.forward_paged_decode(
            params, cfg, jnp.asarray([ids[pos], 7]),
            jnp.asarray([pos, 0]), pools, table, jnp.asarray([pos, 0]),
            active=jnp.asarray([True, False]))
        # one live row: k pairs a layer, on k experts, one row each
        k, n_l = cfg.num_experts_per_tok, cfg.num_layers
        assert load.tolist() == [n_l * k, n_l * k, n_l]
        out.append(logits[0])
    return jnp.stack(out)


def test_forward_logits_match_the_reference(ref):
    cfg, params = _mk()
    ids = _ids(1, 40, cfg.vocab_size)
    _close(_forward_logits(params, cfg, ids), ref.logits(params, SIZES, ids))


def test_reference_scores_are_its_logits_whatever_the_padding(ref):
    """``score`` pads a sequence to whole buckets (one program a bucket,
    not one a length): the scored positions read the same as from
    ``logits`` of the sequence alone, at either side of a bucket's edge."""
    cfg, params = _mk()
    c = {**SIZES, "vocab_size": cfg.vocab_size}
    for n in (ref._BUCKET - 3, ref._BUCKET, ref._BUCKET + 5):
        ids = _ids(n, n, cfg.vocab_size)
        logp = jax.nn.log_softmax(ref.logits(params, c, ids)[-9:-1], axis=-1)
        want = np.take_along_axis(np.asarray(logp), ids[-8:, None], 1)[:, 0]
        got, _ent = ref.score(params, c, ids.tolist(), 8)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_chunked_prefill_then_paged_decode_matches_the_reference(ref):
    cfg, params = _mk()
    ids = _ids(2, 37, cfg.vocab_size)
    got = _prefill_then_decode_logits(params, cfg, ids, n_prompt=29)
    _close(got, ref.logits(params, SIZES, ids)[28:])


def test_cb_engine_logprobs_match_the_reference_and_load_is_counted(ref):
    """Chunked prefill and paged decode through ``CBEngine``: the
    log-probability of every sampled token against the reference's, and
    the MoE load the engine reports for the steps it ran."""
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, params = _mk()
    engine = CBEngine(cfg, params, pad_token_id=0, max_slots=4, page_size=8,
                      max_seq_len=96, prompt_buckets=(16, 48), num_pages=64,
                      prefill_chunk=16, steps_per_dispatch=4,
                      kv_cache_dtype=jnp.float32)
    try:
        prompts = [_ids(3, 41, cfg.vocab_size).tolist(),
                   _ids(4, 9, cfg.vocab_size).tolist()]
        sp = SamplingParams(temperature=1.0, max_new_tokens=12,
                            stop_token_ids=())
        outs = engine.generate(prompts, sp, timeout=300.0)
        info = engine.moe_info()
    finally:
        engine.stop()
    for prompt, o in zip(prompts, outs):
        assert len(o["token_ids"]) == 12
        want, _ent = ref.score(params, SIZES, prompt + o["token_ids"], 12)
        np.testing.assert_allclose(o["logprobs"], want, rtol=0, atol=RTOL)
    # every decoded token but each request's first (which its prefill
    # samples) is one live row of one decode step: k pairs a layer
    k, n_l = cfg.num_experts_per_tok, cfg.num_layers
    assert info["moe_routed"] == 2 * 11 * k * n_l
    assert info["moe_routed"] / cfg.num_experts \
        <= info["moe_load_max"] <= info["moe_routed"] / k
    assert info["moe_load_max"] <= info["moe_experts_hit"] \
        <= info["moe_routed"]


def test_dense_engine_reports_no_moe_load():
    from polyrl_tpu.rollout.cb_engine import CBEngine

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    engine = CBEngine(cfg, decoder.init_params(jax.random.PRNGKey(0), cfg),
                      pad_token_id=0, max_slots=2, page_size=8,
                      max_seq_len=32, prompt_buckets=(8,), num_pages=16)
    try:
        assert engine.moe_info() == {}
    finally:
        engine.stop()


def _packed_batch(cfg):
    """Two rows, each packing two sequences and three pad columns."""
    b, t = 2, 16
    ids = np.random.default_rng(5).integers(1, cfg.vocab_size, (b, t))
    seg = np.zeros((b, t), np.int32)
    pos = np.zeros((b, t), np.int32)
    lm = np.zeros((b, t), np.float32)
    spans = [(0, 6, 1), (6, 13, 2)]
    for s, e, sid in spans:
        seg[:, s:e] = sid
        pos[:, s:e] = np.arange(e - s)
        lm[:, s + 2:e] = 1.0
    return ids.astype(np.int32), seg, pos, lm, spans


def _packed_sum(params, cfg, batch):
    from polyrl_tpu.trainer.actor import _packed_logprobs_entropy

    ids, seg, pos, lm, _spans = batch
    lp, _ = _packed_logprobs_entropy(
        params, cfg, jnp.asarray(ids), jnp.asarray(pos),
        jnp.asarray((seg > 0).astype(np.float32)), jnp.asarray(seg), True,
        False, loss_mask=jnp.asarray(lm))
    return lp


def _reference_packed(ref, params, batch):
    """The same log-probs, one sequence at a time."""
    ids, _seg, _pos, lm, spans = batch
    out = jnp.zeros(ids.shape, jnp.float32)
    for row in range(ids.shape[0]):
        for s, e, _sid in spans:
            logp = jax.nn.log_softmax(
                ref.logits(params, SIZES, ids[row, s:e]), axis=-1)
            tok = jnp.take_along_axis(
                logp[:-1], jnp.asarray(ids[row, s + 1:e])[:, None], 1)[:, 0]
            out = out.at[row, s + 1:e].set(tok)
    return out * lm


def test_packed_logprobs_and_their_gradient_match_the_reference(ref):
    """The trainer's packed log-probs (remat'd scan over the layers) and
    ``jax.grad`` of their sum with respect to router and expert weights."""
    cfg, params = _mk()
    batch = _packed_batch(cfg)
    _close(_packed_sum(params, cfg, batch),
           _reference_packed(ref, params, batch))
    got = jax.grad(lambda p: jnp.sum(_packed_sum(p, cfg, batch)))(params)
    want = jax.grad(
        lambda p: jnp.sum(_reference_packed(ref, p, batch)))(params)
    for key in ("router", "we_gate", "we_up", "we_down"):
        assert np.abs(np.asarray(want["layers"][key])).max() > 0
        _close(got["layers"][key], want["layers"][key])


def test_bf16_experts_exceed_the_tolerance(ref):
    """The tolerance is tight enough to tell a lower precision: with the
    experts rounded to bf16 the same comparison fails by far."""
    cfg, params = _mk()
    layers = dict(params["layers"])
    for key in ("we_gate", "we_up", "we_down"):
        layers[key] = layers[key].astype(jnp.bfloat16).astype(jnp.float32)
    ids = _ids(1, 40, cfg.vocab_size)
    got = np.asarray(_forward_logits({**params, "layers": layers}, cfg, ids))
    want = np.asarray(ref.logits(params, SIZES, ids))
    assert np.abs(got - want).max() / np.abs(want).max() > 2 * RTOL


def test_bf16_system_stays_within_bf16_of_the_reference(ref):
    """The system in bf16 (weights, activations) against the float32
    reference of the same bf16 weights: 3e-2 of the largest logit, the
    rounding of activations of 8 mantissa bits through two layers. A
    position whose k-th and next router probabilities lie within that
    rounding may choose another expert; with this seed none does."""
    cfg = decoder.get_config("moe-tiny", dtype=jnp.bfloat16, num_experts=8,
                             rms_norm_eps=1e-6)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    ids = _ids(1, 40, cfg.vocab_size)
    _close(_forward_logits(params, cfg, ids), ref.logits(params, SIZES, ids),
           rtol=3e-2)


@pytest.mark.parametrize("path", ["trainer", "prefill", "decode"])
def test_nothing_is_dropped_when_every_token_takes_the_same_experts(ref, path):
    """All tokens on experts 0 and 1 (a capacity of twice the mean would
    keep 2 of every 4 choices): the output still equals the reference."""
    cfg, params = _mk()
    params = _same_experts_router(params)
    ids = _ids(6, 37, cfg.vocab_size)
    want = ref.logits(params, SIZES, ids)
    if path == "trainer":
        _close(_forward_logits(params, cfg, ids), want)
    elif path == "prefill":
        # one whole-prompt prefill into pages: the last position's logits
        pools = decoder.make_paged_pools(cfg, 16, 8)
        _pools, last = decoder.prefill_into_pages(
            params, cfg, jnp.asarray(np.pad(ids, (0, 3))), jnp.int32(37),
            pools, jnp.arange(1, 6, dtype=jnp.int32))
        _close(last, want[-1])
    else:
        got = _prefill_then_decode_logits(params, cfg, ids, n_prompt=29)
        _close(got, want[28:])


def test_large_tables_are_gathered_to_the_same_result(monkeypatch):
    """``_take`` reads small tables by a one-hot product and large ones by
    a gather: the block's output is the same either way, bit for bit."""
    cfg, params = _mk()
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.hidden_size))
    valid = jnp.arange(40) % 5 != 2
    hot, load = decoder._moe_mlp(cfg, x, lp, valid)
    monkeypatch.setattr(blocks, "_ONE_HOT_ROWS", 0)
    gathered, load2 = decoder._moe_mlp(cfg, x, lp, valid)
    np.testing.assert_array_equal(np.asarray(hot), np.asarray(gathered))
    assert load.tolist() == load2.tolist()


def test_rows_of_unvisited_tiles_reach_no_output(monkeypatch):
    """On a TPU the kernel leaves the rows of tiles past ``tiles_used``
    undefined (CPU's ``ragged_dot`` path zeroes them, so no other test
    sees it). Poisoned with NaN here: the block reads back only the rows
    its choices sit in, and its output is the same, bit for bit."""
    from polyrl_tpu.models import quant

    cfg, params = _mk()
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.hidden_size))
    valid = jnp.arange(12) % 3 != 1
    want, _ = decoder._moe_mlp(cfg, x, lp, valid)
    real = quant.grouped_matmul
    poisoned = []

    def undefined_past_the_used_tiles(x, ws, scales, lay, act=""):
        y = real(x, ws, scales, lay, act)
        unvisited = jnp.arange(y.shape[0]) >= lay.tiles_used[0] * lay.tile
        poisoned.append(int(jnp.sum(unvisited)))
        return jnp.where(unvisited[:, None], jnp.nan, y)

    monkeypatch.setattr(quant, "grouped_matmul", undefined_past_the_used_tiles)
    got, _ = decoder._moe_mlp(cfg, x, lp, valid)
    assert poisoned and min(poisoned) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_invalid_rows_return_zero_and_move_no_valid_row():
    cfg, params = _mk()
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.hidden_size))
    valid = jnp.arange(12) % 3 != 1
    out, load = decoder._moe_mlp(cfg, x, lp, valid)
    assert np.all(np.asarray(out)[~np.asarray(valid)] == 0.0)
    assert int(load[0]) == 8 * cfg.num_experts_per_tok
    # the valid rows alone give the same rows, whatever the others hold
    alone, _ = decoder._moe_mlp(cfg, x[valid], lp, None)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(valid)],
                               np.asarray(alone), rtol=1e-6, atol=1e-7)
    other, _ = decoder._moe_mlp(
        cfg, jnp.where(valid[:, None], x, 100.0), lp, valid)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(other))


def test_padding_content_does_not_reach_real_tokens():
    """Pad tokens are masked out of routing entirely, so real-token logits
    cannot depend on pad CONTENT."""
    cfg, params = _mk()
    ids_real = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 1,
                                  cfg.vocab_size)
    pad_a = jnp.zeros((2, 10), jnp.int32)
    pad_b = jax.random.randint(jax.random.PRNGKey(7), (2, 10), 1,
                               cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    mask = jnp.concatenate([jnp.ones((2, 6)), jnp.zeros((2, 10))], axis=1)
    a, _ = decoder.forward(params, cfg,
                           jnp.concatenate([ids_real, pad_a], axis=1),
                           pos, mask)
    b, _ = decoder.forward(params, cfg,
                           jnp.concatenate([ids_real, pad_b], axis=1),
                           pos, mask)
    np.testing.assert_allclose(np.asarray(a[:, :6]), np.asarray(b[:, :6]),
                               rtol=1e-6, atol=1e-7)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("n", [64, 512])
def test_expert_matmuls_run_over_the_chosen_rows_only(n):
    """From the jaxpr: every operation that multiplies with the stacked
    experts [E, ., .] is a grouped matmul over at most N*k + E*t rows (t
    the row tile: 16 at decode's 4 rows an expert, 64 at a prefill
    chunk's), never E*N."""
    cfg = decoder.get_config("qwen3-30b-a3b", num_layers=1)
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = 16 if n == 64 else 64
    lp = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        jax.eval_shape(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                   cfg))["layers"])
    x = jax.ShapeDtypeStruct((n, cfg.hidden_size), cfg.dtype)
    jaxpr = jax.make_jaxpr(lambda x, lp: decoder._moe_mlp(cfg, x, lp))(x, lp)
    with_experts = [
        eqn for eqn in _eqns(jaxpr.jaxpr)
        if not list(jax.core.jaxprs_in_params(eqn.params))    # a leaf
        and any(getattr(v.aval, "ndim", 0) == 3 and v.aval.shape[0] == e
               and v.aval.size >= e * cfg.hidden_size
               * cfg.moe_intermediate_size for v in eqn.invars)]
    assert len(with_experts) == 3          # gate, up, down
    for eqn in with_experts:
        assert eqn.primitive.name == "ragged_dot_general"
        rows = eqn.invars[0].aval.shape[0]
        assert rows <= n * k + e * t < e * n
