"""The one-pass Mamba-2 (SSD) state-update kernel (``ops/ssd_state.py``)
against ``ssd_state.ssd_recurrent_step`` and the chunked form, interpreted
on the CPU: live and dead rows, a stack with more slots than the step has
rows, no decay and a fast one, groups of several lane tiles, a row's groups
in several blocks; and one decode step of a model whose state the kernel
accepts, kernel against oracle through ``hybrid.paged_decode``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder, hybrid
from polyrl_tpu.models.mixers import mamba2
from polyrl_tpu.ops import ssd_state

TOL = 5e-6


def _operands(rows, groups, n, w, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (rows, groups, w)) * 0.1
    b = jax.random.normal(ks[1], (rows, groups, n))
    c = jax.random.normal(ks[2], (rows, groups, n)) * n ** -0.5
    a = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (rows, groups, w)) - 2))
    return ks[4], x, a, b, c


@pytest.mark.parametrize("case,slots,rows,groups,n,w,gb", [
    ("all rows live", 3, 3, 2, 16, 128, None),
    ("some rows dead", 4, 4, 2, 16, 128, None),
    ("more slots than rows", 5, 2, 2, 16, 128, None),
    ("no decay", 2, 2, 2, 16, 128, None),
    ("a fast decay", 2, 2, 2, 16, 128, None),
    ("four lane tiles a group", 3, 2, 2, 128, 512, None),
    ("groups in two blocks", 3, 2, 16, 8, 128, 8),
])
def test_the_kernel_is_the_recurrence(case, slots, rows, groups, n, w, gb):
    key, x, a, b, c = _operands(rows, groups, n, w, seed=len(case))
    state = 0.1 * jax.random.normal(key, (slots, groups, n, w))
    live = jnp.ones((rows,), bool)
    if case == "some rows dead":
        live = jnp.asarray([True, False, True, False])
    a = {"no decay": jnp.ones_like(a),
         "a fast decay": jnp.full_like(a, 0.4)}.get(case, a)
    want_s, want_y = ssd_state.ssd_recurrent_step(state[:rows], x, a, b, c)
    keep = live[:, None, None]
    new, y = ssd_state.ssd_state_pallas(
        state, jnp.where(keep, x, 0.0), jnp.where(keep, a, 1.0), b, c,
        interpret=True, gb=gb)
    assert new.shape == state.shape and y.shape == want_y.shape
    lv = np.asarray(live)
    assert float(jnp.abs(new[:rows][lv] - want_s[lv]).max()) < TOL
    assert float(jnp.abs(y[lv] - want_y[lv]).max()) < TOL
    # a row without a request keeps its state to the bit, and so does
    # every slot past the step's rows
    assert bool(jnp.array_equal(new[:rows][~lv], state[:rows][~lv]))
    assert bool(jnp.array_equal(new[rows:], state[rows:]))
    # one position of the chunked form is the same step (a head a lane)
    chunked, yc = mamba2.ssd_chunked(
        state[:rows], x[:, None], b[:, None], c[:, None],
        jnp.log(a).reshape(rows, 1, groups * w), 1)
    assert float(jnp.abs(chunked[lv] - new[:rows][lv]).max()) < TOL
    assert float(jnp.abs(yc[:, 0][lv] - y[lv]).max()) < TOL


def test_the_dispatcher_follows_the_static_shapes():
    assert ssd_state.accepts((65, 8, 128, 512), jnp.float32)
    assert ssd_state._groups_per_block(8, 128, 512) == 8     # 2 MiB a row
    assert not ssd_state.accepts((65, 8, 128, 512), jnp.bfloat16)
    assert not ssd_state.accepts((3, 2, 16, 32), jnp.float32)
    # off a TPU the dispatcher takes the oracle whatever the shape
    assert not ssd_state.in_kernel((65, 8, 128, 512), jnp.float32)
    full = decoder.get_config("nemotron-3-nano-30b-a3b-share8")
    assert not mamba2.in_kernel(full, 64)
    assert mamba2.cache(full, None, jnp.bfloat16).arrays == (
        ("state", (8, 128, 512), jnp.float32),
        ("conv", (3, 6144), jnp.bfloat16))


def test_a_decode_step_through_the_kernel_is_the_oracles(monkeypatch):
    """``hybrid.paged_decode`` on the tiny model with Mamba-2 heads of 64
    (a group's two heads are one lane tile), three slots of which the
    middle one has no request, the stack one slot longer than the step:
    next states, tails, pages and logits under the kernel (forced,
    interpreted) against the oracle's."""
    cfg = dataclasses.replace(
        decoder.get_config("nemotron-h-tiny", dtype=jnp.float32),
        mamba_head_dim=64)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    assert "ssd_kernel_steps" not in hybrid.step_counters(cfg, 3)
    pools = decoder.make_paged_pools(cfg, 8, 8, dtype=jnp.float32, slots=4)
    key = jax.random.PRNGKey(1)
    pools = (pools[0], tuple(
        tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 2 * n + i),
                                      a.shape) for i, a in enumerate(rows))
        for n, rows in enumerate(pools[1])))
    tokens = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 11], jnp.int32)
    table = jnp.asarray([[1, 0], [0, 0], [2, 3]], jnp.int32)
    active = jnp.asarray([True, False, True])

    def step():
        return decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)

    want_logits, want_pools, want_load = step()
    monkeypatch.setattr(ssd_state, "in_kernel", ssd_state.accepts)
    monkeypatch.setattr(ssd_state, "ssd_state_pallas", functools.partial(
        ssd_state.ssd_state_pallas, interpret=True))
    assert "ssd_kernel_steps" in hybrid.step_counters(cfg, 3)
    logits, got_pools, load = step()
    lv = np.asarray(active)
    assert float(jnp.abs(logits[lv] - want_logits[lv]).max()) < 1e-4
    assert bool(jnp.array_equal(load, want_load))
    for got, want, old in zip(got_pools[1], want_pools[1], pools[1]):
        for s1, s0, was in zip(got, want, old):
            assert float(jnp.abs(s1 - s0).max()) < TOL
            assert bool(jnp.array_equal(s1[1], was[1]))
            assert bool(jnp.array_equal(s1[3], was[3]))
