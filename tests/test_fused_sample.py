"""The head that samples (``ops/fused_sample.py``), its kernel interpreted
on the CPU at small shapes: against ``sampling.sample_token_vec`` on the
same float32 logits with the noise handed in, greedy rows mixed with
sampled ones, a vocabulary the tile does not divide, the generator against
JAX's own threefry and a chi-square test of its draws, the engine's step
with it (inactive rows, the counter), and the programs it must not touch
lowering to the text the parent lowered."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_trace_names as names
from polyrl_tpu.models import decoder
from polyrl_tpu.ops import fused_sample as fs
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams, sample_token_vec

RAGGED = 5 * 128 + 37     # as 151,936 = 1,187 x 128 leaves a ragged tile


def _case(s, d, v, seed=0):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (s, d), jnp.float32)
    w = 0.4 * jax.random.normal(jax.random.fold_in(k, 1), (d, v), jnp.float32)
    return x, w


def _oracle(logits, rng, temps):
    s = logits.shape[0]
    return sample_token_vec(logits, rng, temps, jnp.ones((s,)),
                            jnp.zeros((s,), jnp.int32), use_filters=False)


def _own_noise(rng, s, v):
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[:, None], (s, v))
    cols = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32)[None, :], (s, v))
    return fs.gumbel_noise(fs.key_words(rng), rows, cols)


TEMPS = jnp.asarray([1.0, 0.7, 0.0, 1.3, -1.0, 1.0, 0.25, 1.0, 0.0, 2.0])


@pytest.mark.parametrize("v,tile,tied", [
    (RAGGED, 256, False), (RAGGED, 512, False), (1024, 256, False),
    (1024, 1024, False), (RAGGED, 256, True), (50, 256, False),
])
def test_equals_the_sampler_on_the_same_logits_and_noise(v, tile, tied):
    """The noise ``jax.random.categorical`` would add, handed in: the same
    token, the same log-probability, sampled and greedy rows in one call
    (10 rows: a row block of 8 and a ragged one)."""
    s = TEMPS.shape[0]
    x, w = _case(s, 64, v)
    rng = jax.random.PRNGKey(7)
    noise = jax.random.gumbel(rng, (s, v), jnp.float32)
    tok, logp = fs.head_sample_pallas(
        x, w.T if tied else w, rng, TEMPS, noise, tied=tied, tile=tile,
        interpret=True)
    want_tok, want_logp = _oracle(x @ w, rng, TEMPS)
    np.testing.assert_array_equal(tok, want_tok)
    np.testing.assert_allclose(logp, want_logp, atol=1e-5, rtol=0)


def test_greedy_rows_take_the_first_argmax_under_the_raw_softmax():
    """Greedy rows ignore noise and temperature; among equal logits the
    lowest column wins, across lanes and across tiles."""
    s, v = 4, RAGGED
    x, w = _case(s, 64, v)
    # two columns of one lane in different tiles, and two lanes, tie
    w = w.at[:, 300].set(w[:, 44]).at[:, 45].set(w[:, 44])
    x = x.at[:, :].set(jnp.where(x @ w[:, 44:45] > 0, x, -x))
    w = w.at[:, 44].multiply(6.0).at[:, 45].multiply(6.0) \
         .at[:, 300].multiply(6.0)
    logits = x @ w
    assert (jnp.argmax(logits[:3], axis=-1) == 44).all()
    temps = jnp.asarray([0.0, -1.0, 0.0, 1.0])
    tok, logp = fs.head_sample_pallas(x, w, jax.random.PRNGKey(1), temps,
                                      tile=256, interpret=True)
    np.testing.assert_array_equal(tok[:3], [44, 44, 44])
    want = jax.nn.log_softmax(logits, axis=-1)[:3, 44]
    np.testing.assert_allclose(logp[:3], want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("tile", [256, 512])
def test_a_ragged_last_tile_is_masked(tile):
    """Beyond the vocabulary a tile holds whatever the block's padding
    holds: no draw lands there and the log-sum-exp leaves it out, at
    every key, with the kernel's own noise."""
    s, v = 8, RAGGED
    x, w = _case(s, 32, v, seed=3)
    temps = jnp.full((s,), 1.5)
    lse = jax.nn.logsumexp(x @ w / 1.5, axis=-1)
    for seed in range(6):
        tok, logp = fs.head_sample_pallas(
            x, w, jax.random.PRNGKey(seed), temps, tile=tile, interpret=True)
        assert (tok >= 0).all() and (tok < v).all()
        z = jnp.take_along_axis(x @ w / 1.5, tok[:, None], axis=1)[:, 0]
        np.testing.assert_allclose(logp, z - lse, atol=1e-5, rtol=0)


def test_the_generator_is_threefry_and_does_not_depend_on_the_tile():
    from jax.extend.random import threefry_2x32

    rng = jax.random.PRNGKey(11)
    i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)  # noqa: E731
    count = jnp.arange(512, dtype=jnp.uint32) * 7919
    want = i32(threefry_2x32(jax.random.key_data(rng), count))
    k0, k1 = fs.key_words(rng)
    x0, x1 = fs._threefry2x32(k0, k1, i32(count[:256]), i32(count[256:]))
    np.testing.assert_array_equal(jnp.concatenate([x0, x1]), want)
    # the kernel's own draw is the draw under its noise handed in, at
    # any tile width
    s, v = 10, RAGGED
    x, w = _case(s, 64, v, seed=5)
    noise = _own_noise(rng, s, v)
    assert abs(float(noise.mean()) - 0.5772) < 0.05
    given = fs.head_sample_pallas(x, w, rng, TEMPS, noise, tile=256,
                                  interpret=True)
    for tile in (256, 512):
        own = fs.head_sample_pallas(x, w, rng, TEMPS, tile=tile,
                                    interpret=True)
        np.testing.assert_array_equal(own[0], given[0])
        np.testing.assert_array_equal(own[1], given[1])
    other = fs.head_sample_pallas(x, w, jax.random.PRNGKey(12), TEMPS,
                                  tile=256, interpret=True)
    assert (other[0] != given[0]).any()


@pytest.mark.parametrize("temp", [0.7, 1.0])
def test_draws_follow_the_softmax(temp):
    """Chi-square of 20,000 draws (two keys of 10,000 rows) over a
    50-token distribution with the kernel's own noise: 49 degrees of
    freedom, 85.4 is the 99.9% point."""
    v, n = 50, 10_000
    x, w = _case(1, 16, v, seed=9)
    w = 0.25 * w                        # logits within a few nats
    logits = (x @ w)[0]
    p = np.asarray(jax.nn.softmax(logits / temp), np.float64)
    assert p.min() * 2 * n > 5          # every cell expects a few draws
    rows = jnp.broadcast_to(x, (n, 16))
    counts = np.zeros(v)
    for seed in (21, 22):
        tok, logp = fs.head_sample_pallas(
            rows, w, jax.random.PRNGKey(seed), jnp.full((n,), temp),
            interpret=True)
        counts += np.bincount(np.asarray(tok), minlength=v)
        np.testing.assert_allclose(
            logp, jnp.log(jnp.asarray(p, jnp.float32))[tok], atol=1e-5)
    chi2 = float(np.sum((counts - 2 * n * p) ** 2 / (2 * n * p)))
    assert chi2 < 85.4, chi2


# -- the engine's step ------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.get_config("tiny")
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def in_head(monkeypatch):
    """Off a TPU ``samples_in_head`` says no: answer as on one, so that
    the other three conditions decide (the kernel then runs interpreted)."""
    real = decoder.samples_in_head

    def as_on_a_tpu(*args):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return real(*args)

    monkeypatch.setattr(decoder, "samples_in_head", as_on_a_tpu)


def _engine(cfg, params, **kw):
    return CBEngine(cfg, params, pad_token_id=0, max_slots=4, page_size=8,
                    max_seq_len=64, prompt_buckets=(16,), num_pages=32,
                    steps_per_dispatch=2, kv_cache_dtype=jnp.float32, **kw)


def test_the_conditions_of_the_fused_step(tiny, in_head):
    """No filters, a plain array for a head (tied or not), one chip; and
    nothing of it off a TPU."""
    from polyrl_tpu.models.quant import quantize_params

    cfg, params = tiny
    assert decoder.samples_in_head(cfg, params, False, False)
    assert not decoder.samples_in_head(cfg, params, True, False)
    assert not decoder.samples_in_head(cfg, params, False, True)
    assert not decoder.samples_in_head(cfg, quantize_params(params), False,
                                       False)
    tied = decoder.get_config("tiny", tie_word_embeddings=True)
    assert decoder.samples_in_head(
        tied, decoder.init_params(jax.random.PRNGKey(0), tied), False, False)


def test_off_a_tpu_the_step_keeps_head_and_sampler(tiny):
    cfg, params = tiny
    assert not decoder.samples_in_head(cfg, params, False, False)
    eng = _engine(cfg, params)
    try:
        assert not eng._samples_in_head(False)
    finally:
        eng.stop()


def test_inactive_rows_yield_pad_and_zero_from_the_fused_step(tiny, in_head):
    """Two fused steps, rows 0 and 2 live with a budget of one token: the
    first step samples for them alone, the second for nobody."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    try:
        assert eng._samples_in_head(False)
        eng._ensure_dev_state()
        st = eng._dev_state
        active = jnp.zeros_like(st["active"]).at[0].set(True).at[2].set(True)
        live = np.asarray(active)           # the step takes ``active`` over
        out = eng._get_step(False, 2)(
            eng.params, eng._pools[0], eng._pools[1], eng._rng,
            st["page_table"], st["seq_lens"], st["last_tokens"],
            st["n_generated"], jnp.ones_like(st["budgets"]), active,
            jnp.ones_like(st["temps"]), st["top_ps"], st["top_ks"],
            st["stop_table"])
        token, logp, done = (np.asarray(a) for a in out[3:6])
    finally:
        eng.stop()
    assert ((token[0][live] >= 0) & (token[0][live] < cfg.vocab_size)).all()
    assert (logp[0][live] < 0).all() and done[0][live].all()
    assert (token[0][~live] == 0).all() and (logp[0][~live] == 0.0).all()
    assert (token[1] == 0).all() and (logp[1] == 0.0).all()
    assert out[-1] is None                    # a dense model: no MoE load


def test_the_engine_with_it_decodes_as_without_and_counts_its_steps(
        monkeypatch):
    """Greedy decode is the same tokens and log-probabilities through the
    fused step as through head + sampler; ``fused_sample_steps`` moves
    with ``decode_steps_done`` there, stays 0 without, and stands still
    while a top-p request holds the step on the filtered program."""
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 9, 2, 77, 31], [400, 3, 3, 8]]
    greedy = SamplingParams(temperature=0.0, max_new_tokens=7)

    def run(sp):
        eng = _engine(cfg, params)
        try:
            outs = eng.generate(prompts, sp, timeout=300.0)
            return outs, eng.loop_profile_info()
        finally:
            eng.stop()

    plain, info = run(greedy)
    assert info["fused_sample_steps"] == 0 < info["decode_steps_done"]
    real = decoder.samples_in_head
    monkeypatch.setattr(
        decoder, "samples_in_head",
        lambda cfg, params, use_filters, many: not use_filters)
    fused, info = run(greedy)
    assert info["fused_sample_steps"] == info["decode_steps_done"] > 0
    for a, b in zip(plain, fused):
        assert a["token_ids"] == b["token_ids"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-5)
    sampled, info = run(SamplingParams(temperature=1.0, max_new_tokens=7))
    assert info["fused_sample_steps"] == info["decode_steps_done"] > 0
    assert all(len(o["token_ids"]) == 7 and max(o["logprobs"]) <= 0.0
               for o in sampled)
    _outs, info = run(SamplingParams(temperature=1.0, top_p=0.9,
                                     max_new_tokens=7))
    assert info["fused_sample_steps"] == 0 < info["decode_steps_done"]
    assert real(cfg, params, False, False) is False      # the CPU's answer


# -- what the change must not touch ------------------------------------------

def _step_args(eng, spec=False):
    eng._ensure_dev_state()
    st = eng._dev_state
    history = (st["tok_buf"],) if spec else ()
    return names._shapes((
        eng.params, eng._pools[0], eng._pools[1], eng._rng, *history,
        st["page_table"], st["seq_lens"], st["last_tokens"],
        st["n_generated"], st["budgets"], st["active"], st["temps"],
        st["top_ps"], st["top_ks"], st["stop_table"]))


# sha256 of ``lower().as_text()`` (no locations), first 16 digits, of the
# parent of PR 28 (8ceb67c) on the CPU at tests/test_trace_names.py's tiny
# engine; the unfiltered step too, which off a TPU keeps head + sampler
PARENT_TEXT = {
    "step_filtered": "9d0cd1397d488245",
    "step_plain_off_a_tpu": "af128a9909e94676",
    "spec_step": "006ad4a887515ddd",
    "prefill_one": "48773e56e36ac0dd",
    "actor_update": "1421b0fd76770fa3",
}


@pytest.mark.parametrize("program", sorted(PARENT_TEXT))
def test_other_programs_lower_to_the_parents_text(tiny, in_head, program):
    """Where the fused step may engage it does (``in_head``), and every
    program but the unfiltered decode step lowers to what it did."""
    if program == "actor_update":
        lowered = names._lower_actor_update(tiny)
    elif program == "spec_step":
        eng = names._engine(tiny, spec_tokens=2)
        lowered = eng._get_spec_step(False, 3, 2).__wrapped__.lower(
            *_step_args(eng, spec=True))
    elif program == "prefill_one":
        lowered = names._lower_prefill(names._engine(tiny))
    elif program == "step_filtered":
        eng = names._engine(tiny)
        lowered = eng._get_step(True, 2).__wrapped__.lower(*_step_args(eng))
    else:
        with pytest.MonkeyPatch.context() as m:
            m.setattr(decoder, "samples_in_head", lambda *a: False)
            lowered = names._lower_step(names._engine(tiny))
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert digest[:16] == PARENT_TEXT[program]
