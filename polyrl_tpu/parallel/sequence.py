"""Sequence/context parallelism: Ulysses all-to-all + ring attention.

The reference's long-context training mechanism is verl's Ulysses SP —
sequences sliced along length across ranks, attention computed by
all-to-all head exchange (SURVEY §5.7, ``stream_fsdp_workers.py:91``,
``stream_dp_actor.py:37``). The reference has no ring attention; SURVEY §2.3
calls for providing ring attention over ICI as the TPU-idiomatic context
parallelism for the very-long-context regime.

Both primitives run under ``shard_map`` over the ``sp`` mesh axis and share
one signature: q/k/v are [B, T, H, D] logically-global arrays sharded
P(batch, sp, None, None); ``token_mask`` is [B, T] validity (left-pad
aware); causal masking over GLOBAL positions is applied internally.

``packed=True`` returns the segment-aware variant — signature gains a
``segment_ids`` [B, T] argument (0 = pad, 1-based per row) and attention is
block-diagonal within segments, composing remove-padding training with SP
exactly as the reference's Ulysses slices packed varlen inputs
(``stream_dp_actor.py:37-47,135`` — its default long-context mode). A
packed segment may SPAN the rank boundary: the all-to-all / ring exchange
re-unifies the sequence before masking, so equality against gathered (or
rotating) segment ids is exact regardless of where the slice fell.

SP composes with TENSOR parallelism: the head dim of q/k/v is sharded over
``tp`` in the shard_map specs, so tp-sharded projections feed straight in
with no head all-gather. Ring attention never moves heads, so tp>1 is free;
Ulysses all-to-alls each tp shard's LOCAL heads over sp (correct because
heads shard contiguously over tp first: local q head j maps to local KV
head j // (Hq/Hkv) exactly as in the global layout, given Hkv % tp == 0 —
the constraint tp decoding already imposes). Ulysses therefore needs
``num_heads % (tp * sp) == 0``; train.py validates.

- Ulysses: all-to-all redistributes heads<->sequence so each rank computes
  full-sequence attention for H/sp heads — one cheap ICI all-to-all each
  way, best when H >= sp.
- Ring: K/V blocks rotate around the sp ring via ``ppermute`` with online
  (flash-style) softmax accumulation — memory O(T/sp) per rank, scales to
  sequences no single chip can hold.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from polyrl_tpu.ops.attention import repeat_kv
from polyrl_tpu.parallel.mesh import DP, FSDP, SP, TP

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # finite -inf (no exp NaNs)


def _expand_kv_minimal(k, v, hq: int, sp: int):
    """GQA under Ulysses: KV heads ride the same all-to-all as Q heads, so
    their count must divide by sp. When ``hkv % sp != 0``, expand by the
    SMALLEST factor r (r must divide the GQA group hq/hkv so head↔group
    association survives the head split, and make hkv*r % sp == 0) —
    full expansion to hq only as the last resort. This keeps most of the
    GQA memory win, e.g. hkv=8, hq=32, sp=16 expands 2× not 4×."""
    hkv = k.shape[2]
    if hkv % sp == 0:
        return k, v
    group = hq // hkv
    r = next((r for r in range(2, group + 1)
              if group % r == 0 and (hkv * r) % sp == 0), group)
    return repeat_kv(k, r), repeat_kv(v, r)


# --------------------------------------------------------------------------
# Ulysses
# --------------------------------------------------------------------------


def make_ulysses_attention(mesh: Mesh, axis: str = SP,
                           batch_axes=(DP, FSDP), packed: bool = False):
    """Returns attn_fn(q, k, v, token_mask) -> out, all [B, T, H, D] with the
    seq dim sharded over ``axis``. Ulysses ≙ all-to-all head redistribution
    (verl's FSDPUlyssesShardingManager equivalent). ``packed=True``: the fn
    takes a trailing ``segment_ids`` and the gathered full-sequence
    attention runs the SAME segment-id flash kernel as the non-SP packed
    path (Pallas on TPU, dense fallback elsewhere — ops/flash.py)."""
    sp = mesh.shape[axis]

    def _exchange(q, k, v):
        # local: q [B, Ts, Hq, D]; all_to_all -> [B, T, Hq/sp, D]
        k, v = _expand_kv_minimal(k, v, q.shape[2], sp)
        q_g = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
        k_g = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
        v_g = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
        return q_g, k_g, v_g

    def inner(q, k, v, token_mask, segment_ids=None):
        # the gathered full-sequence attention runs the flash kernel
        # (Pallas on TPU — O(T) memory; the whole point of SP is sequence
        # lengths where dense [B, H, T, T] logits cannot exist), with the
        # dense masked fallback off-TPU / non-tiling shapes (ops/flash.py).
        # Without explicit segment ids, padding rides the mask-derived ids
        # (pad=0 attends only pads; pad rows are garbage either way and
        # the loss masks them).
        from polyrl_tpu.ops import flash

        q_g, k_g, v_g = _exchange(q, k, v)
        mask_g = lax.all_gather(token_mask, axis, axis=1, tiled=True)  # [B, T]
        seg_g = (lax.all_gather(segment_ids, axis, axis=1, tiled=True)
                 if segment_ids is not None else None)
        out = flash.flash_attention_train(q_g, k_g, v_g, mask_g, causal=True,
                                          segment_ids=seg_g)
        return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)

    qkv_spec = P(batch_axes, axis, TP, None)  # heads stay tp-sharded
    mask_spec = P(batch_axes, axis)
    if packed:
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec, mask_spec),
            out_specs=qkv_spec, check_vma=False)
    return jax.shard_map(
        lambda q, k, v, tm: inner(q, k, v, tm), mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False)


# --------------------------------------------------------------------------
# Ring attention
# --------------------------------------------------------------------------


def ring_attention_local(q, k, v, token_mask, segment_ids=None, *,
                         axis: str = SP, sp: int):
    """The ring-attention body for use INSIDE a shard_map region that is
    manual on ``axis``: q/k/v are the LOCAL [b, T/sp, H, D] blocks; K/V
    (with their mask/segment ids) rotate around the ring via ``ppermute``
    with online-softmax merging over GLOBAL positions. Exposed so the
    pipeline's stage attention can run it inside its own manual region
    (sp × pp composition); ``make_ring_attention`` is the standalone
    shard_map wrapper.

    GQA-native: heads never leave their rank, so KV is NOT expanded at
    all — the rotating K/V blocks stay at hkv heads (the dominant
    memory/ICI cost) and Q heads group against their shared KV head in
    the einsum, exactly like ops.attention."""
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = d ** -0.5
    idx = lax.axis_index(axis)
    q32 = q.reshape(b, tq, hkv, g, d).astype(jnp.float32) * scale
    q_pos = idx * tq + jnp.arange(tq)  # global positions of local Q rows

    m = jnp.full((b, hkv, g, tq), _NEG, jnp.float32)
    l = jnp.zeros((b, hkv, g, tq), jnp.float32)
    o = jnp.zeros((b, tq, hkv, g, d), jnp.float32)
    k_cur, v_cur, mask_cur, seg_cur = k, v, token_mask, segment_ids

    for step in range(sp):
        src = (idx - step) % sp  # block id currently held
        tk = k_cur.shape[1]
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q32,
                            k_cur.astype(jnp.float32))
        kv_pos = src * tk + jnp.arange(tk)
        ok = (kv_pos[None, :] <= q_pos[:, None])[None, None, None, :, :]
        ok = ok & (mask_cur[:, None, None, None, :] > 0)
        if seg_cur is not None:
            ok = ok & (segment_ids[:, :, None]
                       == seg_cur[:, None, :])[:, None, None, :, :]
        logits = jnp.where(ok, logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m - m_new)                      # [b,hkv,g,tq]
        l = l * corr + p.sum(axis=-1)
        o = o * corr.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bhgqk,bkhd->bqhgd", p, v_cur.astype(jnp.float32))
        m = m_new
        if step < sp - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
            mask_cur = lax.ppermute(mask_cur, axis, perm)
            if seg_cur is not None:
                seg_cur = lax.ppermute(seg_cur, axis, perm)

    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (o / denom).reshape(b, tq, hq, d).astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis: str = SP, batch_axes=(DP, FSDP),
                        packed: bool = False):
    """Returns attn_fn(q, k, v, token_mask) -> out over a standalone
    shard_map (manual on ``axis``) around :func:`ring_attention_local` —
    the TPU context-parallel mode SURVEY §2.3 calls for. ``packed=True``:
    segment ids rotate WITH their K/V block and the mask adds same-segment
    equality (block-diagonal packed attention)."""
    sp = mesh.shape[axis]

    def inner(q, k, v, token_mask, segment_ids=None):
        return ring_attention_local(q, k, v, token_mask, segment_ids,
                                    axis=axis, sp=sp)

    qkv_spec = P(batch_axes, axis, TP, None)  # heads stay tp-sharded
    mask_spec = P(batch_axes, axis)
    if packed:
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec, mask_spec),
            out_specs=qkv_spec, check_vma=False)
    return jax.shard_map(
        lambda q, k, v, tm: inner(q, k, v, tm), mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False)


def make_sharded_flash_attention(mesh: Mesh, batch_axes=(DP, FSDP),
                                 packed: bool = False):
    """The training attention under a mesh WITHOUT sequence parallelism:
    ``flash_attention_train`` shard_mapped with the batch over
    ``batch_axes`` and the heads over tp; every chip sees whole sequences.
    The Pallas flash kernel is a Mosaic custom call and GSPMD refuses to
    partition one ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map") — so a sharded trainer's default
    attention has to say how it splits. Same signatures as the SP
    variants: (q, k, v, token_mask[, segment_ids])."""
    from polyrl_tpu.ops import flash

    def inner(q, k, v, token_mask, segment_ids=None):
        return flash.flash_attention_train(q, k, v, token_mask, causal=True,
                                           segment_ids=segment_ids)

    qkv_spec = P(batch_axes, None, TP, None)
    mask_spec = P(batch_axes, None)
    if packed:
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec, mask_spec),
            out_specs=qkv_spec, check_vma=False)
    return jax.shard_map(
        lambda q, k, v, tm: inner(q, k, v, tm), mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False)


def make_sp_attention(mesh: Mesh, mode: str, axis: str = SP,
                      batch_axes=(DP, FSDP), packed: bool = False):
    """Dispatch: 'ulysses' | 'ring' | 'dense' (None)."""
    if mode == "ulysses":
        return make_ulysses_attention(mesh, axis, batch_axes, packed=packed)
    if mode == "ring":
        return make_ring_attention(mesh, axis, batch_axes, packed=packed)
    if mode in ("dense", "none", None):
        return None
    raise ValueError(f"unknown sp attention mode {mode!r}")
