"""The one-pass KDA state-update kernel (``ops/kda_state.py``) against
``kda.kda_recurrent_step``, interpreted on the CPU: live and dead rows,
a stack with more slots than the step has rows, the decay at its bound and
at none, ``beta`` at both ends, two head counts, a row's heads in several
blocks; and one decode step of a model whose head size the kernel accepts,
kernel against oracle through ``hybrid.paged_decode``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder, hybrid
from polyrl_tpu.models.mixers import base, kda
from polyrl_tpu.ops import kda_state

# what tests/test_hybrid.py holds the chunked form to
TOL = 5e-6
D = 128


def _operands(rows, heads, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = base.l2norm(jax.random.normal(ks[0], (rows, heads, D))) * D ** -0.5
    k = base.l2norm(jax.random.normal(ks[1], (rows, heads, D)))
    v = jax.random.normal(ks[2], (rows, heads, D))
    # decays from none to the bound of -5 a position
    g = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (rows, heads, D)) * 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads)))
    return ks[5], q, k, v, g, beta


@pytest.mark.parametrize("case,slots,rows,heads,hb", [
    ("all rows live", 3, 3, 4, None),
    ("some rows dead", 4, 4, 4, None),
    ("more slots than rows", 5, 2, 4, None),
    ("g at the bound", 2, 2, 4, None),
    ("g at none", 2, 2, 4, None),
    ("beta 0", 2, 2, 4, None),
    ("beta 1", 2, 2, 4, None),
    ("eight heads", 3, 2, 8, None),
    ("heads in two blocks", 3, 2, 16, 8),
])
def test_the_kernel_is_the_recurrence(case, slots, rows, heads, hb):
    key, q, k, v, g, beta = _operands(rows, heads, seed=len(case))
    state = 0.1 * jax.random.normal(key, (slots, heads, D, D))
    live = jnp.ones((rows,), bool)
    if case == "some rows dead":
        live = jnp.asarray([True, False, True, False])
    g = {"g at the bound": jnp.full_like(g, -5.0),
         "g at none": jnp.zeros_like(g)}.get(case, g)
    beta = {"beta 0": jnp.zeros_like(beta),
            "beta 1": jnp.ones_like(beta)}.get(case, beta)
    want_s, want_o = kda.kda_recurrent_step(state[:rows], q, k, v, g, beta)
    new, o = kda_state.kda_state_pallas(
        state, q, k, v, jnp.where(live[:, None, None], g, 0.0),
        jnp.where(live[:, None], beta, 0.0), interpret=True, hb=hb)
    assert new.shape == state.shape and o.shape == want_o.shape
    lv = np.asarray(live)
    assert float(jnp.abs(new[:rows][lv] - want_s[lv]).max()) < TOL
    assert float(jnp.abs(o[lv] - want_o[lv]).max()) < TOL
    # a row without a request keeps its state to the bit, and so does
    # every slot past the step's rows
    assert bool(jnp.array_equal(new[:rows][~lv], state[:rows][~lv]))
    assert bool(jnp.array_equal(new[rows:], state[rows:]))
    if case == "beta 0":    # nothing written: the state only decays
        assert float(jnp.abs(
            new - state * jnp.exp(g)[..., None]).max()) < TOL


def test_the_block_of_heads_follows_the_static_shapes():
    # Ling's row is one block of 2 MiB; a wider model's is cut in whole
    # sublane tiles of heads; a size no tile divides takes the oracle
    assert kda_state._heads_per_block(32, 128, 128) == 32
    assert kda_state._heads_per_block(64, 128, 128) == 32
    assert kda_state._heads_per_block(48, 128, 128) == 24
    assert kda_state._heads_per_block(4, 128, 128) == 4
    assert kda_state._heads_per_block(16, 256, 256) == 8
    assert kda_state._heads_per_block(36, 128, 128) is None
    assert kda_state.accepts((129, 32, 128, 128), jnp.float32)
    assert not kda_state.accepts((129, 32, 128, 128), jnp.bfloat16)
    assert not kda_state.accepts((3, 4, 16, 16), jnp.float32)
    assert not kda_state.accepts((3, 36, 128, 128), jnp.float32)
    # off a TPU the dispatcher takes the oracle whatever the shape
    assert not kda_state.in_kernel((129, 32, 128, 128), jnp.float32)


def test_a_decode_step_through_the_kernel_is_the_oracles(monkeypatch):
    """``hybrid.paged_decode`` on the tiny hybrid at a head size of 128,
    three slots of which the middle one has no request, the stack one slot
    longer than the step: next state, latent pages and logits under the
    kernel (forced, interpreted) against the oracle's."""
    cfg = dataclasses.replace(
        decoder.get_config("hybrid-tiny", dtype=jnp.float32), head_dim=D)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    assert not "kda_kernel_steps" in hybrid.step_counters(cfg, 3)
    pools = decoder.make_paged_pools(cfg, 8, 8, dtype=jnp.float32, slots=4)
    key = jax.random.PRNGKey(1)
    pools = (pools[0], tuple(
        (0.1 * jax.random.normal(jax.random.fold_in(key, n), s.shape),
         0.1 * jax.random.normal(jax.random.fold_in(key, 10 + n), c.shape,
                                 c.dtype))
        for n, (s, c) in enumerate(pools[1])))
    tokens = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 11], jnp.int32)
    table = jnp.asarray([[1, 0], [0, 0], [2, 3]], jnp.int32)
    active = jnp.asarray([True, False, True])

    def step():
        return decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)

    want_logits, want_pools, want_load = step()
    monkeypatch.setattr(kda_state, "in_kernel", kda_state.accepts)
    assert "kda_kernel_steps" in hybrid.step_counters(cfg, 3)
    logits, got_pools, load = step()
    lv = np.asarray(active)
    assert float(jnp.abs(logits[lv] - want_logits[lv]).max()) < 1e-4
    assert bool(jnp.array_equal(load, want_load))
    for (s1, c1), (s0, c0), (old, _) in zip(got_pools[1], want_pools[1],
                                            pools[1]):
        assert float(jnp.abs(s1 - s0).max()) < TOL
        assert float(jnp.abs(c1 - c0).max()) < TOL
        # the row without a request and the slot past the step's rows
        assert bool(jnp.array_equal(s1[1], old[1]))
        assert bool(jnp.array_equal(s1[3], old[3]))
    # page 0 is the null page, where the row without a request writes
    for a, b in zip(got_pools[0], want_pools[0]):
        assert float(jnp.abs(a[:, 1:] - b[:, 1:]).max()) < 1e-5
