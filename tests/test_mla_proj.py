"""The in-place ``wkv_b`` products of a decode step's latent attention
(``ops/mla_proj.py``) against ``mla.mla_absorb``'s and
``mla.mla_unabsorb``'s einsum, interpreted on the CPU: 128 and 32
heads, a layer other than the first of a stack of three, float32 and
bfloat16, several heads a grid step; the shapes the kernels refuse; one
decode step of the tiny hybrid and of the tiny all-latent model at head
sizes the kernels take, kernel against oracle through
``hybrid.paged_decode``; the engine's ``mla_proj_kernel_steps`` and its
reader."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import harness
from polyrl_tpu.models import decoder, hybrid
from polyrl_tpu.models.mixers import mla
from polyrl_tpu.ops import mla_proj
from polyrl_tpu.rollout.cb_engine import CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams

N = 128          # a head's nope and value size: one lane tile
LAYERS = 3


def _sized(preset: str):
    """A tiny preset at head sizes the kernels take."""
    return dataclasses.replace(
        decoder.get_config(preset, dtype=jnp.float32),
        qk_nope_head_dim=N, v_head_dim=N, kv_lora_rank=N)


def _operands(rows, heads, rank, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    stack = jax.random.normal(ks[0], (LAYERS, rank, heads * 2 * N)) * N ** -0.5
    q = jax.random.normal(ks[1], (rows, heads, N))
    o = jax.random.normal(ks[2], (rows, heads, rank)) * (N / rank) ** 0.5
    return stack.astype(dtype), q.astype(dtype), o.astype(dtype)


def _oracle(cfg, stack, layer, q, o):
    """The einsums, on the layer's slice as ``_layer_params`` cuts it;
    ``mla_absorb``'s first ``rank`` columns are the product."""
    lp = {"wkv_b": stack[layer]}
    q_rope = jnp.zeros((*q.shape[:2], cfg.qk_rope_head_dim), q.dtype)
    q_lat = mla.mla_absorb(cfg, lp, q, q_rope)
    return q_lat[..., :cfg.kv_lora_rank], mla.mla_unabsorb(cfg, lp, o)


@pytest.mark.parametrize("heads,rank,layer,dtype,hb", [
    (128, 512, 1, jnp.bfloat16, None),      # dots.vlm1's heads and rank
    (32, 512, 2, jnp.bfloat16, None),       # Ling's
    (128, 512, 2, jnp.float32, None),
    (32, 512, 0, jnp.float32, None),
    (32, 256, 1, jnp.bfloat16, 8),          # four grid steps
    (16, 128, 1, jnp.float32, 8),           # two
])
def test_the_kernels_are_the_einsums(heads, rank, layer, dtype, hb):
    cfg = dataclasses.replace(_sized("mla-moe-tiny"), num_heads=heads,
                              kv_lora_rank=rank, dtype=dtype)
    rows = 5
    assert mla_proj.accepts(cfg, rows)
    stack, q, o = _operands(rows, heads, rank, dtype, seed=heads + layer)
    want_q, want_o = _oracle(cfg, stack, layer, q, o)
    got_q = mla_proj.absorb(q, stack, layer=layer, hb=hb, interpret=True)
    got_o = mla_proj.unabsorb(o, stack, layer=layer, hb=hb, interpret=True)
    assert got_q.shape == (rows, heads, rank) and got_q.dtype == dtype
    assert got_o.shape == (rows, heads, N) and got_o.dtype == jnp.float32
    if dtype == jnp.bfloat16:
        # products of bf16 pairs are exact in float32 and a head's sum is
        # one MXU pass either way: bit for bit
        assert bool(jnp.array_equal(got_q, want_q))
        assert bool(jnp.array_equal(got_o, want_o))
    else:
        # entries of size 1; only the order of a sum differs
        assert float(jnp.abs(got_q - want_q).max()) < 4e-6
        assert float(jnp.abs(got_o - want_o).max()) < 4e-6
    # another layer's block is another answer
    other = (layer + 1) % LAYERS
    wrong_q, wrong_o = _oracle(cfg, stack, other, q, o)
    assert float(jnp.abs(got_q.astype(jnp.float32)
                         - wrong_q.astype(jnp.float32)).max()) > 0.5
    assert float(jnp.abs(got_o - wrong_o).max()) > 0.5


def test_the_block_of_heads_follows_the_static_shapes():
    per = mla_proj._heads_per_block
    # 2 MiB of windows: dots.vlm1's step; Ling's 129 rows leave room for
    # 8 heads' activations; float32
    assert per(128, 65, 512, 128, 2) == 16
    assert per(32, 129, 512, 128, 2) == 8
    assert per(128, 129, 512, 128, 2) == 8
    assert per(128, 65, 512, 128, 4) == 8
    # few heads are one step; whole sublane tiles of heads, or all of them
    assert per(4, 3, 128, 128, 4) == 4
    assert per(14, 65, 512, 128, 2) == 14
    assert per(48, 65, 512, 128, 2) == 16
    assert per(36, 65, 512, 128, 2) is None
    # a prefill chunk's rows leave no room for a tile of heads
    assert per(128, 512, 512, 128, 2) is None
    dots = decoder.get_config("dots.vlm1-share16")
    ling = decoder.get_config("ling-3.0-flash-share4")
    assert mla_proj.accepts(dots, 65) and mla_proj.accepts(ling, 129)
    assert not mla_proj.accepts(dots, 512)
    # head sizes of no whole lane tile, halves of two sizes, no latent
    assert not mla_proj.accepts(decoder.get_config("mla-moe-tiny"), 5)
    assert not mla_proj.accepts(decoder.get_config("hybrid-tiny"), 5)
    assert not mla_proj.accepts(
        dataclasses.replace(dots, v_head_dim=256), 65)
    assert not mla_proj.accepts(decoder.get_config("tiny"), 5)
    # off a TPU the dispatch takes the einsum whatever the shape
    assert not mla_proj.in_kernel(dots, 65)
    assert not "mla_proj_kernel_steps" in hybrid.step_counters(dots, 65)


def test_a_refused_shape_takes_the_einsum(monkeypatch):
    """With the backend's answer out of the way, the tiny preset's heads
    of 16 still multiply through the einsum: a decode step runs with the
    kernels taken away."""
    cfg = decoder.get_config("mla-moe-tiny", dtype=jnp.float32)
    monkeypatch.setattr(mla_proj, "in_kernel", mla_proj.accepts)
    monkeypatch.setattr(mla_proj, "absorb", None)
    monkeypatch.setattr(mla_proj, "unabsorb", None)
    assert not "mla_proj_kernel_steps" in hybrid.step_counters(cfg, 2)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    pools = decoder.make_paged_pools(cfg, 4, 8, dtype=jnp.float32, slots=2)
    lens = jnp.asarray([3, 9], jnp.int32)
    logits, _pools, _load = decoder.forward_paged_decode(
        params, cfg, jnp.asarray([5, 9], jnp.int32), lens, pools,
        jnp.asarray([[1, 0], [2, 3]], jnp.int32), lens)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("preset", ["hybrid-tiny", "mla-moe-tiny"])
def test_a_decode_step_through_the_kernels_is_the_oracles(monkeypatch,
                                                          preset):
    """``hybrid.paged_decode`` on the tiny hybrid (one MLA layer of three,
    no query latent, the head gate) and the tiny all-latent model (three
    MLA layers, a query latent) at head sizes of 128: three rows of which
    the middle one has no request; logits, load and pools under the
    kernels (forced, interpreted) against the einsum's."""
    cfg = _sized(preset)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    pools = decoder.make_paged_pools(cfg, 8, 8, dtype=jnp.float32, slots=3)
    key = jax.random.PRNGKey(1)
    pools = jax.tree_util.tree_map(
        lambda a: 0.1 * jax.random.normal(key, a.shape, a.dtype), pools)
    tokens = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 11], jnp.int32)
    table = jnp.asarray([[1, 0], [0, 0], [2, 3]], jnp.int32)
    active = jnp.asarray([True, False, True])

    def step():
        return decoder.forward_paged_decode(
            params, cfg, tokens, lens, pools, table, lens, active=active)

    assert not "mla_proj_kernel_steps" in hybrid.step_counters(cfg, 3)
    want_logits, want_pools, want_load = step()
    calls = []
    real = mla_proj.absorb
    monkeypatch.setattr(mla_proj, "in_kernel", mla_proj.accepts)
    monkeypatch.setattr(
        mla_proj, "absorb",
        lambda q, w, layer, **kw: calls.append((w.shape[0], layer))
        or real(q, w, layer=layer, **kw))
    assert "mla_proj_kernel_steps" in hybrid.step_counters(cfg, 3)
    logits, got_pools, load = step()
    # every MLA layer took its own block of the stack
    n_mla = sum(p.mixer == "mla" for p in hybrid.cache_spec.layer_plan(cfg))
    assert calls == [(n_mla, i) for i in range(n_mla)]
    lv = np.asarray(active)
    assert float(jnp.abs(logits[lv] - want_logits[lv]).max()) < 5e-6
    assert bool(jnp.array_equal(load, want_load))
    # page 0 is the null page, where the row without a request writes
    for a, b in zip(jax.tree_util.tree_leaves(got_pools[0]),
                    jax.tree_util.tree_leaves(want_pools[0])):
        assert float(jnp.abs(a[:, 1:] - b[:, 1:]).max()) < 5e-6


@pytest.mark.parametrize("kernel", [True, False])
def test_mla_proj_kernel_steps_move_with_an_engine_that_took_the_kernel(
        monkeypatch, kernel):
    """The engine asks once, at construction; a dispatch's steps reach
    ``mla_proj_kernel_steps`` when it lands, beside ``decode_steps_done``,
    and stay out of it on an engine whose program took the einsum."""
    if kernel:
        monkeypatch.setattr(mla_proj, "in_kernel", mla_proj.accepts)
    cfg = _sized("mla-moe-tiny")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    eng = CBEngine(cfg, params, max_slots=2, page_size=8, max_seq_len=32,
                   prompt_buckets=(16,), num_pages=16, steps_per_dispatch=4,
                   kv_cache_dtype=jnp.float32)
    assert ("mla_proj_kernel_steps" in eng._step_counters[False]) is kernel
    eng.start()
    try:
        assert eng.loop_profile_info()["mla_proj_kernel_steps"] == 0
        (out,) = eng.generate([[3, 1, 4, 1, 5]], SamplingParams(
            temperature=0.0, max_new_tokens=9))
        assert len(out["token_ids"]) == 9
        info = eng.loop_profile_info()
    finally:
        eng.stop()
    assert info["decode_steps_done"] >= 8
    assert info["mla_proj_kernel_steps"] == (
        info["decode_steps_done"] if kernel else 0)
    assert info["kda_kernel_steps"] == 0


def test_the_profiler_counts_a_kernel_dispatch_at_its_landing():
    from polyrl_tpu.obs.engine_profile import (CUMULATIVE_KEYS,
                                               EngineLoopProfiler)

    assert "mla_proj_kernel_steps" in CUMULATIVE_KEYS
    prof = EngineLoopProfiler()
    prof.on_dispatch("step", steps=8, rows=64,
                     counters=("mla_proj_kernel_steps",))
    prof.on_dispatch("step", steps=8, rows=64)
    assert prof.counters()["mla_proj_kernel_steps"] == 0
    prof.on_landed(1)
    assert prof.counters()["mla_proj_kernel_steps"] == 8
    prof.on_landed(1)                      # the einsum's dispatch
    assert prof.counters()["mla_proj_kernel_steps"] == 8
    assert prof.counters()["decode_steps_done"] == 16
    assert prof.server_info_fields()["mla_proj_kernel_steps"] == 8


@pytest.mark.parametrize("samples,want", [
    # every step of the window took the kernels; none; a part
    ([{"decode_steps_done": 80, "mla_proj_kernel_steps": 80},
      {"decode_steps_done": 880, "mla_proj_kernel_steps": 880}], 100.0),
    ([{"decode_steps_done": 80, "mla_proj_kernel_steps": 0},
      {"decode_steps_done": 880, "mla_proj_kernel_steps": 0}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_steps_done": 80, "mla_proj_kernel_steps": 16},
      {"decode_steps_done": 880, "mla_proj_kernel_steps": 216}], 25.0),
    # a parent's engine has no such counter; no step landed
    ([{"decode_steps_done": 80}, {"decode_steps_done": 880}], None),
    ([{"decode_steps_done": 80, "mla_proj_kernel_steps": 80},
      {"decode_steps_done": 80, "mla_proj_kernel_steps": 80}], None),
])
def test_mla_proj_kernel_share_of_a_server_info_pair(samples, want):
    got = harness.load_reader("mla_proj_kernel_share")(
        {"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))
