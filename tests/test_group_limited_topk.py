"""``blocks._group_limited_topk`` chooses without a sort; what it chooses,
and in what order, is what ``lax.top_k`` chooses: descending score, the
lower index first among equals. The oracle below is the form the function
had until PR 49, written out with ``lax.top_k``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import blocks

pytestmark = pytest.mark.quick

# (rows, experts, n_group, topk_group, k): Ling's decode step, dots.vlm1's,
# a prefill chunk, a small one, and a router without groups
SHAPES = [(129, 512, 8, 4, 8), (65, 256, 8, 4, 8), (512, 512, 8, 4, 8),
          (7, 16, 4, 2, 2), (33, 64, 1, 1, 6)]


def _cfg(g, t, k):
    return types.SimpleNamespace(
        n_group=g, topk_group=t, num_experts_per_tok=k, norm_topk_prob=True,
        routed_scaling_factor=2.5)


def _oracle(cfg, biased):
    n, e = biased.shape
    g = cfg.n_group
    if g > 1:
        group = jnp.sum(jax.lax.top_k(biased.reshape(n, g, e // g), 2)[0],
                        axis=-1)
        _, keep = jax.lax.top_k(group, cfg.topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None, :],
                       axis=1)
        biased = jnp.where(jnp.repeat(kept, e // g, axis=1), biased,
                           -jnp.inf)
    return jax.lax.top_k(biased, cfg.num_experts_per_tok)[1]


def _scores(kind, n, e, g):
    """Choice scores [n, e] float32 as a sigmoid router makes them."""
    rng = np.random.default_rng(n * e + g)
    s = 1.0 / (1.0 + np.exp(-rng.normal(size=(n, e)).astype(np.float32)))
    if kind == "tied":
        # six levels: nearly every maximum, of a group and of a row, ties,
        # and so do whole groups' scores
        s = np.round(s * 5) / 5
    elif kind == "maximum twice":
        # each row's best group holds its maximum at two places, the
        # second of them the group's last
        width = e // g
        best = np.argmax(s, axis=1)
        last = (best // width + 1) * width - 1
        last = np.where(last == best, last - 1, last)
        s[np.arange(n), last] = s[np.arange(n), best]
    elif kind == "negative":
        s = s + rng.normal(size=(e,)).astype(np.float32) - 0.75
        assert (s < 0).mean() > 0.2 and (s > 0).mean() > 0.2
    return jnp.asarray(s, jnp.float32)


@pytest.mark.parametrize("kind", ["continuous", "tied", "maximum twice",
                                  "negative"])
@pytest.mark.parametrize("n,e,g,t,k", SHAPES)
def test_choice_and_its_order_are_top_ks(n, e, g, t, k, kind):
    cfg = _cfg(g, t, k)
    biased = _scores(kind, n, e, g)
    got = jax.jit(lambda b: blocks._group_limited_topk(cfg, b))(biased)
    want = _oracle(cfg, biased)
    assert got.shape == (n, k) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kind == "tied":
        # the case does tie: some row's choices hold one score twice
        top = np.take_along_axis(np.asarray(biased), np.asarray(want), 1)
        assert (np.diff(top, axis=1) == 0).any()


def test_sigmoid_route_weights_are_the_oracles_to_the_bit(monkeypatch):
    """The weights are the chosen scores over their float32 sum, which
    depends on the order they are summed in: ``_sigmoid_route`` with this
    choice and with the oracle's gives the same bits."""
    n, d, e = 129, 96, 512
    cfg = _cfg(8, 4, 8)
    rng = np.random.default_rng(49)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    lp = {"router": jnp.asarray(rng.normal(size=(d, e)) * 0.2, jnp.float32),
          "router_bias": jnp.asarray(rng.normal(size=(e,)) * 0.1,
                                     jnp.float32)}
    w, i = jax.jit(lambda x, lp: blocks._sigmoid_route(cfg, x, lp))(x, lp)
    monkeypatch.setattr(blocks, "_group_limited_topk", _oracle)
    w0, i0 = jax.jit(lambda x, lp: blocks._sigmoid_route(cfg, x, lp))(x, lp)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                  np.asarray(w0).view(np.uint32))
    assert abs(float(jnp.sum(w[0])) - cfg.routed_scaling_factor) < 1e-5
