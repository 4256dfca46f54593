"""The one-pass Mamba state-update kernel (``ops/ssm_state.py``) against
``ssm.ssm_step``, interpreted on the CPU: live and dead rows, a stack
with more slots than the step has rows, rows that do not fill the last
block, ``dt`` at none, an inner width of several lane chunks and of one
that 1,024 does not divide; and one decode step of a model whose state the
kernel accepts, kernel against oracle through ``hybrid.paged_decode``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.models.mixers import ssm
from polyrl_tpu.ops import ssm_state

# what tests/test_sambay.py holds the scan to
TOL = 5e-6


def _operands(rows, n, inner, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lp = {"a_log": jnp.broadcast_to(jnp.log(jnp.arange(
              1, n + 1, dtype=jnp.float32))[:, None], (n, inner)),
          "d_skip": jnp.ones((inner,), jnp.float32)}
    c = jax.random.normal(ks[0], (rows, inner))
    # a step from a thousandth to one: the drawn bias's range and beyond
    dt = jnp.exp(jax.random.uniform(ks[1], (rows, inner),
                                    minval=jnp.log(1e-3), maxval=0.0))
    return (ks[4], lp, c, dt, jax.random.normal(ks[2], (rows, n)),
            jax.random.normal(ks[3], (rows, n)))


@pytest.mark.parametrize("case,slots,rows,n,inner", [
    ("all rows live", 8, 8, 16, 256),
    ("some rows dead", 8, 8, 16, 256),
    ("more slots than rows", 12, 3, 16, 128),
    ("rows past a block, slots past the rows", 20, 17, 8, 128),
    ("dt at none", 4, 4, 16, 128),
    ("two lane chunks", 3, 3, 16, 2048),
    ("a width 1,024 does not divide", 9, 9, 8, 384),
])
def test_the_kernel_is_the_recurrence(case, slots, rows, n, inner):
    key, lp, c, dt, bm, cm = _operands(rows, n, inner, seed=len(case))
    state = jax.random.normal(key, (slots, n, inner))
    live = jnp.ones((rows,), bool)
    if case == "some rows dead":
        live = jnp.arange(rows) % 3 != 1
    if case == "dt at none":
        dt = jnp.zeros_like(dt)
    want_s, want_m = ssm.ssm_step(lp, state[:rows], c, dt, bm, cm)
    held = jnp.where(live[:, None], dt, 0.0)
    new, m = ssm_state.ssm_state_pallas(
        state, -jnp.exp(lp["a_log"]), held, held * c, bm, cm, interpret=True)
    m = m + lp["d_skip"] * c
    assert new.shape == state.shape and m.shape == want_m.shape
    lv = np.asarray(live)
    assert float(jnp.abs(new[:rows][lv] - want_s[lv]).max()) < TOL
    assert float(jnp.abs(m[lv] - want_m[lv]).max()) < TOL
    # a row without a request keeps its state to the bit, and so does
    # every slot past the step's rows, inside the last block or not
    assert bool(jnp.array_equal(new[:rows][~lv], state[:rows][~lv]))
    assert bool(jnp.array_equal(new[rows:], state[rows:]))
    if case == "dt at none":    # nothing decays, nothing is written
        assert bool(jnp.array_equal(new, state))


def test_what_the_kernel_accepts():
    assert ssm_state.accepts((129, 16, 5120), jnp.float32)
    assert not ssm_state.accepts((129, 16, 5120), jnp.bfloat16)
    assert not ssm_state.accepts((5, 4, 128), jnp.float32)
    assert not ssm_state.accepts((5, 16, 96), jnp.float32)
    assert ssm_state._lane_chunk(5120) == 1024
    assert ssm_state._lane_chunk(384) == 128
    # off a TPU the dispatcher takes the oracle whatever the shape
    assert not ssm_state.in_kernel((129, 16, 5120), jnp.float32)


def test_a_decode_step_through_the_kernel_is_the_oracles(monkeypatch):
    """``hybrid.paged_decode`` on the tiny SambaY model at a state size of
    8 (its inner width is one lane tile), three slots of which the middle
    one has no request, the states one slot longer than the step: next
    states, rings, shared pages and logits under the kernel (forced,
    interpreted) against the oracle's."""
    cfg = dataclasses.replace(
        decoder.get_config("sambay-tiny", dtype=jnp.float32),
        ssm_state_size=8)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    pools = decoder.make_paged_pools(cfg, 8, 4, dtype=jnp.float32, slots=4)
    key = jax.random.PRNGKey(1)
    pools = (pools[0], tuple(
        tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 10 * n + j),
                                      a.shape, a.dtype)
              for j, a in enumerate(arrays)) if arrays[0].ndim == 3
        else arrays for n, arrays in enumerate(pools[1])))
    tokens = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 6], jnp.int32)
    table = jnp.asarray([[1, 0], [0, 0], [2, 3]], jnp.int32)
    active = jnp.asarray([True, False, True])

    def step():
        return decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)

    want_logits, want_pools, want_load = step()
    monkeypatch.setattr(ssm_state, "in_kernel", ssm_state.accepts)
    interpreted = ssm_state.ssm_state_pallas
    monkeypatch.setattr(
        ssm_state, "ssm_state_pallas",
        lambda *a: interpreted(*a, interpret=True))
    logits, got_pools, load = step()
    lv = np.asarray(active)
    assert float(jnp.abs(logits[lv] - want_logits[lv]).max()) < 1e-4
    assert bool(jnp.array_equal(load, want_load))
    scans = 0
    for got, want, old in zip(got_pools[1], want_pools[1], pools[1]):
        for a, b in zip(got, want):
            # (a ring's page 0 is the null page, where the row without a
            # request writes what its ``m``, which is not for use, made)
            at = slice(1, None) if a.ndim == 4 else slice(None)
            assert float(jnp.abs(a[:, at] - b[:, at]).max()) < TOL
        if old[0].ndim == 3:
            scans += 1
            # the row without a request and the slot past the step's rows
            assert bool(jnp.array_equal(got[0][1], old[0][1]))
            assert bool(jnp.array_equal(got[0][3], old[0][3]))
    assert scans == 4
    for a, b in zip(jax.tree_util.tree_leaves(got_pools[0]),
                    jax.tree_util.tree_leaves(want_pools[0])):
        assert float(jnp.abs(a[:, 1:] - b[:, 1:]).max()) < 1e-5
