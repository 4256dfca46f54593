"""Flash attention for the TRAINING path (fwd+bwd, O(T) memory).

The reference trains with flash-attn varlen CUDA kernels
(``stream_dp_actor.py:41-43``, SURVEY.md §2.2 row 2); the TPU equivalent is
blockwise attention with an online softmax. We use JAX's bundled Pallas TPU
flash kernel (``jax.experimental.pallas.ops.tpu.flash_attention`` — public
JAX API with a custom VJP) behind a wrapper that:

- takes this codebase's [B, T, H, D] layout and a [B, T] validity mask,
- expresses padding through segment ids (pad=0, real=1 — pads only attend
  pads, which the loss masks out; packed sequences pass their own ids),
- handles GQA by repeating KV heads to the query head count,
- falls back to the dense masked implementation off-TPU or when the
  sequence length doesn't tile (Pallas blocks must divide T).

Without this, dense logits [B, H, T, T] f32 cap training at short T — the
reference recipe's 14336-token responses are unreachable (a single head row
at T=15360 is 900 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from polyrl_tpu.ops import dispatch
from polyrl_tpu.ops.attention import attention, causal_mask

_BLOCKS = (1024, 512, 256, 128)


def _pick_block(t: int) -> int | None:
    for b in _BLOCKS:
        if t % b == 0:
            return b
    return None


def supports_flash(t: int, head_dim: int) -> bool:
    return (jax.default_backend() == "tpu"
            and _pick_block(t) is not None
            and head_dim % 128 == 0)


def _dense(q, k, v, attn_mask, causal: bool, segment_ids=None):
    t = q.shape[1]
    if segment_ids is not None:
        # packed sequences: tokens attend only within their own segment
        # (block-diagonal), matching the Pallas kernel's SegmentIds semantics
        mask = (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
        mask = mask & (attn_mask[:, None, None, :] > 0)
    else:
        mask = attn_mask[:, None, None, :] > 0
    if causal:
        mask = causal_mask(t, t)[None, None] & mask
    return attention(q, k, v, mask=mask)


def flash_attention_train(q, k, v, attn_mask, *, causal: bool = True,
                          segment_ids=None):
    """q [B,T,Hq,D], k/v [B,T,Hkv,D], attn_mask [B,T] (1=valid). Returns
    [B,T,Hq,D]. ``segment_ids`` [B,T] overrides the mask-derived ids for
    packed-sequence training."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if not supports_flash(t, d):
        dispatch.note("train_attention", "dense")
        return _dense(q, k, v, attn_mask, causal, segment_ids)
    dispatch.note("train_attention", "flash")

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, SegmentIds, flash_attention)

    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    ids = (segment_ids if segment_ids is not None
           else attn_mask.astype(jnp.int32))
    blk = _pick_block(t)
    bs = BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk,
    )
    # the library owns the pallas_calls, forward and backward (no ``name=``
    # to give): the scope is their stable name on a device trace
    with jax.named_scope("flash_attention"):
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            segment_ids=SegmentIds(q=ids, kv=ids),
            causal=causal, sm_scale=d ** -0.5, block_sizes=bs)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def auto_train_attention():
    """attn_fn for ``decoder.forward``'s no-cache path: flash on TPU, dense
    masked attention elsewhere. Signature: (q, k, v, attn_mask)."""
    return functools.partial(flash_attention_train, causal=True)
