"""The one-pass lightning state-update kernel (``ops/lightning_state.py``)
against ``lightning.lightning_recurrent_step`` and the chunked form,
interpreted on the CPU: live and dead rows, a stack with more slots than
the step has rows, no decay and a fast one, two head counts, a row's heads
in several blocks; one decode step of a model whose head size the kernel
accepts, kernel against oracle through ``hybrid.paged_decode``; and the
sparse layers' attention over a selected table through the GQA decode
kernel (``ops/paged_attention.py``, interpreted) against its oracle."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder, hybrid
from polyrl_tpu.models.mixers import lightning
from polyrl_tpu.ops import lightning_state, paged_attention

TOL = 5e-6
D = 128


def _operands(rows, heads, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (rows, heads, D)) * D ** -0.5
    k = jax.random.normal(ks[1], (rows, heads, D)) * D ** -0.5
    v = jax.random.normal(ks[2], (rows, heads, D))
    decay = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (heads,)) - 2))
    return ks[4], q, k, v, decay


@pytest.mark.parametrize("case,slots,rows,heads,hb", [
    ("all rows live", 3, 3, 4, None),
    ("some rows dead", 4, 4, 4, None),
    ("more slots than rows", 5, 2, 4, None),
    ("no decay", 2, 2, 4, None),
    ("a fast decay", 2, 2, 4, None),
    ("eight heads", 3, 2, 8, None),
    ("heads in two blocks", 3, 2, 16, 8),
])
def test_the_kernel_is_the_recurrence(case, slots, rows, heads, hb):
    key, q, k, v, decay = _operands(rows, heads, seed=len(case))
    state = 0.1 * jax.random.normal(key, (slots, heads, D, D))
    live = jnp.ones((rows,), bool)
    if case == "some rows dead":
        live = jnp.asarray([True, False, True, False])
    decay = {"no decay": jnp.ones_like(decay),
             "a fast decay": jnp.full_like(decay, 0.4)}.get(case, decay)
    want_s, want_o = lightning.lightning_recurrent_step(
        state[:rows], q, k, v, decay)
    new, o = lightning_state.lightning_state_pallas(
        state, q, jnp.where(live[:, None, None], k, 0.0), v,
        jnp.where(live[:, None], decay[None], 1.0), interpret=True, hb=hb)
    assert new.shape == state.shape and o.shape == want_o.shape
    lv = np.asarray(live)
    assert float(jnp.abs(new[:rows][lv] - want_s[lv]).max()) < TOL
    assert float(jnp.abs(o[lv] - want_o[lv]).max()) < TOL
    # a row without a request keeps its state to the bit, and so does
    # every slot past the step's rows
    assert bool(jnp.array_equal(new[:rows][~lv], state[:rows][~lv]))
    assert bool(jnp.array_equal(new[rows:], state[rows:]))
    # one position of the chunked form is the same step
    chunked, oc = lightning.lightning_chunked(
        state[:rows], q[:, None], k[:, None], v[:, None], -jnp.log(decay),
        jnp.ones((rows, 1)), 1)
    assert float(jnp.abs(chunked[lv] - new[:rows][lv]).max()) < TOL
    assert float(jnp.abs(oc[:, 0][lv] - o[lv]).max()) < TOL


def test_the_dispatcher_follows_the_static_shapes():
    assert lightning_state.accepts((97, 32, 128, 128), jnp.float32)
    assert not lightning_state.accepts((97, 32, 128, 128), jnp.bfloat16)
    assert not lightning_state.accepts((3, 4, 16, 16), jnp.float32)
    # off a TPU the dispatcher takes the oracle whatever the shape
    assert not lightning_state.in_kernel((97, 32, 128, 128), jnp.float32)
    full = decoder.get_config("minicpm-sala")
    assert not lightning.in_kernel(full, 96)


def test_a_decode_step_through_the_kernel_is_the_oracles(monkeypatch):
    """``hybrid.paged_decode`` on the tiny model at a lightning head size
    of 128, three slots of which the middle one has no request, the stack
    one slot longer than the step: next states, pages and logits under the
    kernel (forced, interpreted) against the oracle's."""
    cfg = dataclasses.replace(
        decoder.get_config("minicpm-sala-tiny", dtype=jnp.float32),
        lightning_head_dim=D)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    assert "lightning_kernel_steps" not in hybrid.step_counters(cfg, 3)
    pools = decoder.make_paged_pools(cfg, 8, 8, dtype=jnp.float32, slots=4)
    key = jax.random.PRNGKey(1)
    pools = (pools[0], tuple(
        (0.1 * jax.random.normal(jax.random.fold_in(key, n), s.shape),)
        for n, (s,) in enumerate(pools[1])))
    tokens = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 11], jnp.int32)
    table = jnp.asarray([[1, 0], [0, 0], [2, 3]], jnp.int32)
    active = jnp.asarray([True, False, True])

    def step():
        return decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)

    want_logits, want_pools, want_load = step()
    monkeypatch.setattr(lightning_state, "in_kernel", lightning_state.accepts)
    monkeypatch.setattr(
        lightning_state, "lightning_state_pallas", functools.partial(
            lightning_state.lightning_state_pallas, interpret=True))
    assert "lightning_kernel_steps" in hybrid.step_counters(cfg, 3)
    logits, got_pools, load = step()
    lv = np.asarray(active)
    assert float(jnp.abs(logits[lv] - want_logits[lv]).max()) < 1e-4
    assert bool(jnp.array_equal(load, want_load))
    for (s1,), (s0,), (old,) in zip(got_pools[1], want_pools[1], pools[1]):
        assert float(jnp.abs(s1 - s0).max()) < TOL
        assert bool(jnp.array_equal(s1[1], old[1]))
        assert bool(jnp.array_equal(s1[3], old[3]))


@pytest.mark.parametrize("lens", [(64 * 63 + 5, 64 * 3 + 64), (1, 0)])
def test_a_selected_table_through_the_gqa_kernel_is_the_oracles(lens):
    """The sparse layers' decode attention: a (row, K/V head) a row of 16
    query heads over ONE head of ``Hkv * N`` pages, its table the chosen
    pages in rising order with the part-filled page last (pages of another
    head's run among them), through ``paged_attention_pallas`` interpreted
    against ``paged_attention_ref``."""
    rng = np.random.default_rng(0)
    hkv, n_pages, ps, d, g = 2, 70, 64, 128, 16
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(hkv, n_pages, ps, d)),
                                  jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(lens), g, d)), jnp.float32)
    table = jnp.asarray(np.stack([
        np.sort(rng.choice(np.arange(1, hkv * n_pages), 128, replace=False))
        for _ in lens]), jnp.int32)
    view = lambda a: a.reshape(1, hkv * n_pages, ps, d)
    want = paged_attention.paged_attention_ref(
        q, view(k_pool), view(v_pool), table, jnp.asarray(lens))
    got = paged_attention.paged_attention_pallas(
        q, view(k_pool), view(v_pool), table, jnp.asarray(lens),
        interpret=True)
    live = np.asarray(lens) > 0
    assert float(jnp.abs(got - want)[live].max()) < 2e-5
