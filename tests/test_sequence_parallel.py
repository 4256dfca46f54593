"""Sequence-parallel attention tests on the 8-device CPU mesh (SURVEY §4:
pjit sharding and collectives exercised host-side). Ulysses and ring must
match dense attention bit-for-tolerance, including left-padding and GQA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from polyrl_tpu.models import decoder
from polyrl_tpu.ops.attention import attention, causal_mask
from polyrl_tpu.parallel import mesh as meshlib
from polyrl_tpu.parallel.sequence import (
    make_ring_attention,
    make_sharded_flash_attention,
    make_sp_attention,
    make_ulysses_attention,
)


@pytest.fixture(scope="module")
def sp_mesh(devices8):
    # dp=1, fsdp=2, tp=1, sp=4 — sequence axis genuinely multi-device
    return meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=2, tp=1, sp=4),
                             devices8)


def dense_reference(q, k, v, token_mask):
    t = q.shape[1]
    mask = causal_mask(t, t)[None, None, :, :] & (token_mask[:, None, None, :] > 0)
    return attention(q, k, v, mask=mask)


def make_qkv(rng, b=4, t=32, hq=8, hkv=8, d=16, left_pad=0):
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    mask = np.ones((b, t), np.float32)
    if left_pad:
        mask[:, :left_pad] = 0.0
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
@pytest.mark.parametrize("hkv,left_pad", [(8, 0), (2, 0), (8, 5)])
def test_sp_attention_matches_dense(sp_mesh, rng, mode, hkv, left_pad):
    q, k, v, tmask = make_qkv(rng, hkv=hkv, left_pad=left_pad)
    want = dense_reference(q, k, v, tmask)
    # padded rows produce garbage outputs in both impls (masked-everything
    # rows); only compare valid positions
    fn = make_sp_attention(sp_mesh, mode)
    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    mspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    args = (jax.device_put(q, spec), jax.device_put(k, spec),
            jax.device_put(v, spec), jax.device_put(tmask, mspec))
    got = jax.jit(fn)(*args)
    valid = np.asarray(tmask)[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(valid, np.asarray(got), 0),
                               np.where(valid, np.asarray(want), 0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_sp_attention_grads_match_dense(sp_mesh, rng, mode):
    q, k, v, tmask = make_qkv(rng, b=2, t=16, hq=4, hkv=4, d=8)
    fn = make_sp_attention(sp_mesh, mode)

    def loss_sp(q, k, v):
        return (fn(q, k, v, tmask) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_reference(q, k, v, tmask) ** 2).sum()

    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(qs, ks, vs)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_sp, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_decoder_forward_with_sp_attention(sp_mesh, rng, mode):
    """Full model forward with seq sharded over sp == dense single-logical
    forward (the verl Ulysses seam, stream_dp_actor.py:37)."""
    cfg = decoder.get_config("tiny", dtype=jnp.float32, vocab_size=128)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    b, t = 4, 32
    ids = jnp.asarray(rng.integers(0, 128, (b, t)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t)).astype(jnp.int32)
    mask = jnp.ones((b, t), jnp.float32)

    want, _ = decoder.forward(params, cfg, ids, pos, mask)

    attn_fn = make_sp_attention(sp_mesh, mode)
    dspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    rspec = NamedSharding(sp_mesh, P())
    params_s = jax.tree_util.tree_map(lambda x: jax.device_put(x, rspec), params)
    ids_s = jax.device_put(ids, dspec)
    pos_s = jax.device_put(pos, dspec)
    mask_s = jax.device_put(mask, dspec)

    got, _ = jax.jit(
        lambda p, i, po, m: decoder.forward(p, cfg, i, po, m, attn_fn=attn_fn)
    )(params_s, ids_s, pos_s, mask_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ring_memory_is_blockwise(sp_mesh, rng):
    """Ring attention never materializes the [T, T] score matrix per rank —
    sanity-check it compiles and runs at a length where the full dense mask
    would be 64x the block size."""
    q, k, v, tmask = make_qkv(rng, b=2, t=512, hq=4, hkv=4, d=8)
    fn = make_ring_attention(sp_mesh)
    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    mspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    out = jax.jit(fn)(jax.device_put(q, spec), jax.device_put(k, spec),
                      jax.device_put(v, spec), jax.device_put(tmask, mspec))
    want = dense_reference(q, k, v, tmask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- packed (remove-padding) × SP composition (VERDICT r4 item 3) ----------


def make_packed(rng, b=4, t=32, hq=8, hkv=8, d=16):
    """Packed-style rows: several segments per row (1-based ids), trailing
    pad (id 0). One segment deliberately spans the sp shard boundary."""
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
    seg = np.zeros((b, t), np.int32)
    # shard boundaries fall at t/4 steps (sp=4); segment 2 spans two of them
    u = t // 16
    bounds = [(0, 3 * u, 1), (3 * u, 10 * u, 2), (10 * u, 15 * u, 3)]
    for s, e, sid in bounds:
        seg[:, s:e] = sid
    seg[0, 15 * u:] = 4  # row 0: a 4th segment instead of trailing pad
    return q, k, v, jnp.asarray(seg)


def packed_reference(q, k, v, seg):
    """Single-logical-device packed attention — the exact kernel the non-SP
    packed path uses (ops/flash.py dense fallback on CPU: causal ∧
    same-segment ∧ valid)."""
    from polyrl_tpu.ops import flash

    return flash.flash_attention_train(
        q, k, v, (seg > 0).astype(jnp.float32), causal=True, segment_ids=seg)


@pytest.mark.quick
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
@pytest.mark.parametrize("hkv", [8, 2])
def test_sp_packed_attention_matches_flash(sp_mesh, rng, mode, hkv):
    q, k, v, seg = make_packed(rng, hkv=hkv)
    tmask = (seg > 0).astype(jnp.float32)
    want = packed_reference(q, k, v, seg)
    fn = make_sp_attention(sp_mesh, mode, packed=True)
    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    mspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    got = jax.jit(fn)(jax.device_put(q, spec), jax.device_put(k, spec),
                      jax.device_put(v, spec), jax.device_put(tmask, mspec),
                      jax.device_put(seg, mspec))
    valid = np.asarray(seg)[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(valid, np.asarray(got), 0),
                               np.where(valid, np.asarray(want), 0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.quick
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_sp_packed_attention_grads_match(sp_mesh, rng, mode):
    q, k, v, seg = make_packed(rng, b=2, t=16, hq=4, hkv=4, d=8)
    tmask = (seg > 0).astype(jnp.float32)
    fn = make_sp_attention(sp_mesh, mode, packed=True)
    valid = (np.asarray(seg) > 0)[:, :, None, None]

    def loss_sp(q, k, v):
        out = fn(q, k, v, tmask, seg)
        return (jnp.where(valid, out, 0.0) ** 2).sum()

    def loss_ref(q, k, v):
        out = packed_reference(q, k, v, seg)
        return (jnp.where(valid, out, 0.0) ** 2).sum()

    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(qs, ks, vs)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.quick
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_packed_logprobs_under_sp_match_single(sp_mesh, rng, mode):
    """The VERDICT parity bar: the actor's packed logprob pass with the
    segment-aware SP attention on the virtual mesh == the same pass
    single-logical-device (packed+sp=2+ vs packed+sp=1)."""
    from polyrl_tpu.trainer.actor import _packed_logprobs_entropy

    cfg = decoder.get_config("tiny", dtype=jnp.float32, vocab_size=128)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    b, t = 4, 32
    ids = jnp.asarray(rng.integers(1, 128, (b, t)), jnp.int32)
    seg = np.zeros((b, t), np.int32)
    pos = np.zeros((b, t), np.int32)
    lm = np.zeros((b, t), np.float32)
    for s, e, sid in [(0, 12, 1), (12, 26, 2), (26, 30, 3)]:
        seg[:, s:e] = sid
        pos[:, s:e] = np.arange(e - s)
        lm[:, s + 2:e] = 1.0  # first 2 tokens of each segment = "prompt"
    am = (seg > 0).astype(np.float32)
    seg, pos, lm, am = map(jnp.asarray, (seg, pos, lm, am))

    want_lp, want_ent = _packed_logprobs_entropy(
        params, cfg, ids, pos, am, seg, False, True, loss_mask=lm)

    sp_fn = make_sp_attention(sp_mesh, mode, packed=True)
    dspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    rspec = NamedSharding(sp_mesh, P())
    params_s = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, rspec), params)
    args = [jax.device_put(x, dspec) for x in (ids, pos, am, seg, lm)]
    got_lp, got_ent = jax.jit(
        lambda p, i, po, a, s, l: _packed_logprobs_entropy(
            p, cfg, i, po, a, s, False, True, loss_mask=l, attn_fn=sp_fn)
    )(params_s, *args)
    np.testing.assert_allclose(np.asarray(got_lp), np.asarray(want_lp),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_ent), np.asarray(want_ent),
                               rtol=2e-4, atol=2e-4)


# -- SP × TP composition (VERDICT r4 item 7) -------------------------------


@pytest.fixture(scope="module")
def sp_tp_mesh(devices8):
    # tp=2, sp=4 — heads tensor-parallel AND sequence context-parallel
    return meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=1, tp=2, sp=4),
                             devices8)


@pytest.mark.quick
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
@pytest.mark.parametrize("hkv", [8, 4])
def test_sp_tp_attention_matches_dense(sp_tp_mesh, rng, mode, hkv):
    """SP over a tp-sharded head layout == dense: heads stay tp-sharded in
    the shard_map specs (no head all-gather); Ulysses exchanges each tp
    shard's local heads over sp."""
    q, k, v, tmask = make_qkv(rng, hkv=hkv, left_pad=3)
    want = dense_reference(q, k, v, tmask)
    fn = make_sp_attention(sp_tp_mesh, mode)
    spec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp", "tp", None))
    mspec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp"))
    got = jax.jit(fn)(jax.device_put(q, spec), jax.device_put(k, spec),
                      jax.device_put(v, spec), jax.device_put(tmask, mspec))
    valid = np.asarray(tmask)[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(valid, np.asarray(got), 0),
                               np.where(valid, np.asarray(want), 0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.quick
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_sp_tp_packed_attention_matches_flash(sp_tp_mesh, rng, mode):
    """Packed (remove-padding) attention under sp=4 × tp=2."""
    q, k, v, seg = make_packed(rng)
    tmask = (seg > 0).astype(jnp.float32)
    want = packed_reference(q, k, v, seg)
    fn = make_sp_attention(sp_tp_mesh, mode, packed=True)
    spec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp", "tp", None))
    mspec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp"))
    got = jax.jit(fn)(jax.device_put(q, spec), jax.device_put(k, spec),
                      jax.device_put(v, spec), jax.device_put(tmask, mspec),
                      jax.device_put(seg, mspec))
    valid = np.asarray(seg)[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(valid, np.asarray(got), 0),
                               np.where(valid, np.asarray(want), 0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.quick
def test_sp_tp_no_head_allgather_in_hlo(sp_tp_mesh, rng):
    """The point of the composition: q/k/v enter the SP attention tp-SHARDED.
    The ring program's collective_permute operands must be hkv/tp-head
    blocks — full-head shapes in a permute would mean heads were gathered."""
    q, k, v, tmask = make_qkv(rng, b=2, t=32, hq=8, hkv=8, d=16)
    fn = make_ring_attention(sp_tp_mesh)
    spec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp", "tp", None))
    mspec = NamedSharding(sp_tp_mesh, P(("dp", "fsdp"), "sp"))
    args = (jax.device_put(q, spec), jax.device_put(k, spec),
            jax.device_put(v, spec), jax.device_put(tmask, mspec))
    txt = jax.jit(fn).lower(*args).as_text()
    perm_lines = [ln for ln in txt.splitlines()
                  if "collective_permute" in ln and "x16" in ln]
    assert perm_lines, "expected K/V collective_permutes"
    for ln in perm_lines:
        # per-shard K/V block: b x t/4 x hkv/tp x d = 2x8x4x16, never 8 heads
        assert "2x8x4x16" in ln, ln
        assert "2x8x8x16" not in ln, ln


def test_ulysses_minimal_gqa_expansion():
    """hkv % sp != 0 expands KV by the SMALLEST valid factor, not to hq:
    hkv=2, hq=8, sp=4 needs only 2x (to 4 heads), keeping half the GQA win."""
    from polyrl_tpu.parallel.sequence import _expand_kv_minimal

    b, t, d = 2, 8, 4
    k = jnp.ones((b, t, 2, d)); v = jnp.ones((b, t, 2, d))
    k2, v2 = _expand_kv_minimal(k, v, hq=8, sp=4)
    assert k2.shape[2] == 4 and v2.shape[2] == 4
    # divisible: untouched
    k8 = jnp.ones((b, t, 8, d))
    k3, _ = _expand_kv_minimal(k8, k8, hq=8, sp=4)
    assert k3 is k8


def test_ring_never_expands_kv(sp_mesh, rng):
    """Ring attention keeps rotating K/V blocks at hkv heads (heads never
    move between ranks, so GQA needs no expansion): the collective-permute
    operands in the lowered HLO must be hkv-head-shaped."""
    q, k, v, tmask = make_qkv(rng, b=2, t=32, hq=8, hkv=2, d=16)
    fn = make_ring_attention(sp_mesh)
    spec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp", None, None))
    mspec = NamedSharding(sp_mesh, P(("dp", "fsdp"), "sp"))
    args = (jax.device_put(q, spec), jax.device_put(k, spec),
            jax.device_put(v, spec), jax.device_put(tmask, mspec))
    txt = jax.jit(fn).lower(*args).as_text()
    perm_lines = [ln for ln in txt.splitlines() if "collective_permute" in ln]
    kv_perm_lines = [ln for ln in perm_lines if "x16x" in ln or "x16>" in ln]
    assert kv_perm_lines, "expected K/V collective_permutes in the program"
    for ln in kv_perm_lines:
        # per-shard K/V block: b/2 x t/4 x hkv x d = 1x8x2x16, never 8 heads
        assert "1x8x2x16" in ln, ln
        assert "1x8x8x16" not in ln, ln
    # and parity still holds
    got = jax.jit(fn)(*args)
    want = dense_reference(q, k, v, tmask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- no SP: the default attention under a mesh ------------------------------


@pytest.mark.parametrize("packed", [False, True])
def test_mesh_sharded_flash_matches_unsharded(devices8, rng, packed):
    """The trainer's default attention under a mesh (batch over dp×fsdp,
    heads over tp, sequence whole): same values and gradients as the
    unsharded call, GQA and left padding included."""
    from polyrl_tpu.ops import flash

    mesh = meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=2, tp=2),
                             devices8[:4])
    q, k, v, tmask = make_qkv(rng, hkv=4, left_pad=3)
    seg = jnp.asarray(np.where(np.arange(32)[None] < 3, 0,
                               1 + (np.arange(32)[None] >= 17))
                      .repeat(4, 0), jnp.int32)
    extra = (seg,) if packed else ()
    fn = make_sharded_flash_attention(mesh, packed=packed)

    def unsharded(q, k, v):
        return flash.flash_attention_train(
            q, k, v, tmask, causal=True, segment_ids=seg if packed else None)

    valid = tmask[:, :, None, None]

    def loss(f):
        return lambda q, k, v: jnp.sum((f(q, k, v) * valid) ** 2)

    got, g_got = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: fn(q, k, v, tmask, *extra)), argnums=(0, 1, 2))
    )(q, k, v)
    want, g_want = jax.value_and_grad(loss(unsharded), argnums=(0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
