"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` axis.

The reference only stubs pipeline parallelism (``infer_pp`` config knob,
reference workers/config/rollout.py:132-134,198-202 — guarded
unimplemented); here it is a real execution mode, built the TPU-idiomatic
way: ONE compiled program, not per-stage processes.

- The stacked layer tree [L, ...] reshapes to [pp, L/pp, ...] and shards
  its leading (stage) dim over the ``pp`` mesh axis.
- A ``shard_map`` manual only on ``pp`` (jax partial-manual mode) runs the
  rotating schedule: at global step s, stage i applies its L/pp layers to
  microbatch (s - i), then hands its activation to stage i+1 via
  ``lax.ppermute``. Inside the stage body the other mesh axes (fsdp/tp/
  ep/...) stay AUTO, so GSPMD keeps inserting the usual FSDP all-gathers
  and TP collectives — pipeline composes with the existing shardings
  instead of re-implementing them.
- Backward needs no separate schedule: autodiff transposes ``ppermute``
  into the reverse rotation, which IS the backward pipeline.

Bubble fraction is the GPipe (pp-1)/(n_micro+pp-1); raise
``num_microbatches`` to amortize. Activations for all microbatches are
held replicated across stages (simple and correct; revisit if activation
memory ever dominates at depth).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from polyrl_tpu.parallel.mesh import PP, SP


def make_pipeline_layers_fn(mesh: Mesh, cfg, num_microbatches: int,
                            remat: bool = False, sp_ring: bool = False):
    """Returns ``layers_fn(layers, x, cos, sin, attn_mask)`` — a drop-in
    for the decoder's layer-stack scan (decoder.forward ``layers_fn``
    hook): x [B, T, d] → [B, T, d] with the stack executed as a pipeline.

    Requires ``cfg.num_layers % pp == 0`` and ``B % num_microbatches == 0``.

    ``sp_ring=True`` composes SEQUENCE parallelism into the pipeline: the
    shard_map goes manual on {pp, sp}, activations keep their seq dim
    sharded over sp, and the stage attention runs
    :func:`polyrl_tpu.parallel.sequence.ring_attention_local` — K/V blocks
    ring over sp INSIDE each stage while microbatches ring over pp. Needs
    ``T % sp == 0``. (Ulysses inside the stages is not implemented: its
    head all-to-all would reshard every stage boundary.)
    """
    from polyrl_tpu.models import decoder as _dec

    pp = mesh.shape[PP]
    sp = mesh.shape[SP] if sp_ring else 1
    n = num_microbatches
    if cfg.num_layers % pp != 0:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                         f"pp {pp}")
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def stage_apply(stage_layers, h, cos, sin, valid, seg):
        # stage attention goes through the flash wrapper (Pallas O(T)
        # memory on TPU, dense fallback elsewhere — ops/flash.py), NOT a
        # materialized [T, T] mask: packed long-context is exactly the
        # workload where dense per-stage logits would O(T²) the pipeline.
        # ``seg`` carries real segment ids in the packed case and the
        # validity mask (pad=0) otherwise — identical semantics to the
        # mask-derived ids flash uses everywhere else.
        # CAVEAT (hardware validation pending): the Pallas flash kernel
        # inside this partial-manual shard_map has only executed via the
        # CPU dense fallback on this rig — supports_flash() gates it off
        # for untileable shapes, but a TPU lowering failure of the
        # supported path would only surface on real hardware (same
        # exposure as every training-path flash call since r1).
        from polyrl_tpu.ops import flash

        am = valid.astype(h.dtype)
        if sp_ring:
            # seq dim is LOCAL (T/sp); ring the K/V blocks over sp within
            # this stage — global causality comes from the ring's own
            # axis-index positioning
            from polyrl_tpu.parallel.sequence import ring_attention_local

            attn = lambda q, k, v: ring_attention_local(  # noqa: E731
                q, k, v, am, seg, axis=SP, sp=sp)
        else:
            attn = lambda q, k, v: flash.flash_attention_train(  # noqa: E731
                q, k, v, am, causal=True, segment_ids=seg)

        def body(carry, lp):
            out, _ = _dec._layer_forward(cfg, carry, lp, cos, sin, None,
                                         None, attn_fn=attn,
                                         token_valid=valid)
            return out, None
        if remat:
            body = jax.checkpoint(body)
        h, _ = lax.scan(body, h, stage_layers)
        return h

    def inner(stage_layers, xs, coss, sins, valids, segs):
        # manual on pp only: stage dim is local (length 1) — drop it
        stage_layers = jax.tree_util.tree_map(lambda a: a[0], stage_layers)
        stage = lax.axis_index(PP)
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros_like(xs)

        def step_fn(carry, step):
            state, outs = carry
            # stage i works on microbatch (step - i); clip keeps indices
            # static-shaped — the warm-up/drain garbage never reaches a
            # real output slot (see write guard below)
            mb = jnp.clip(step - stage, 0, n - 1)
            inp = jnp.where(stage == 0, xs[jnp.clip(step, 0, n - 1)], state)
            h = stage_apply(stage_layers, inp, coss[mb], sins[mb],
                            valids[mb], segs[mb])
            out_idx = step - (pp - 1)
            ok = (stage == pp - 1) & (out_idx >= 0)
            oi = jnp.clip(out_idx, 0, n - 1)
            upd = jnp.where(ok, h, lax.dynamic_index_in_dim(
                outs, oi, 0, keepdims=False))
            outs = lax.dynamic_update_index_in_dim(outs, upd, oi, 0)
            state = lax.ppermute(h, PP, perm)
            return (state, outs), None

        (_, outs), _ = lax.scan(step_fn, (state, outs),
                                jnp.arange(n + pp - 1))
        # only the last stage wrote real outputs; everyone else holds
        # zeros — the psum replicates the result across the ring
        return lax.psum(outs, PP)

    def layers_fn(layers, x, cos, sin, attn_mask, segment_ids=None):
        """``segment_ids`` (optional [B, T], 0 = pad): packed
        (remove-padding) rows — the stages' internal attention masks turn
        block-diagonal within segments, composing packed training with
        pipeline parallelism (the packed caller binds them per batch via a
        closure, exactly like its attn lambda)."""
        b, t, d = x.shape
        # total over ANY batch size: logprob feeds (ibatch-sized) and
        # ragged tail micros flow through the same layers_fn as the
        # configured micro batches — pad rows up to a microbatch multiple
        # (fully masked: attention sees nothing, MoE routing skips them)
        # and slice back after
        b_pad = -(-b // n) * n
        if b_pad != b:
            grow = b_pad - b
            x = jnp.pad(x, ((0, grow), (0, 0), (0, 0)))
            cos = jnp.pad(cos, ((0, grow),) + ((0, 0),) * (cos.ndim - 1))
            sin = jnp.pad(sin, ((0, grow),) + ((0, 0),) * (sin.ndim - 1))
            attn_mask = jnp.pad(attn_mask, ((0, grow), (0, 0)))
            if segment_ids is not None:
                segment_ids = jnp.pad(segment_ids, ((0, grow), (0, 0)))
        mb = b_pad // n
        lpp = cfg.num_layers // pp
        staged = jax.tree_util.tree_map(
            lambda a: a.reshape((pp, lpp) + a.shape[1:]), layers)
        xs = x.reshape(n, mb, t, d)
        coss = cos.reshape((n, mb) + cos.shape[1:])
        sins = sin.reshape((n, mb) + sin.shape[1:])
        valids = (attn_mask > 0).reshape(n, mb, t)
        segs = (segment_ids if segment_ids is not None
                else (attn_mask > 0).astype(jnp.int32)).reshape(n, mb, t)

        specs = jax.tree_util.tree_map(lambda _: P(PP), staged)
        if sp_ring:
            if t % sp != 0:
                raise ValueError(
                    f"sp_ring pipeline needs seq len {t} divisible by "
                    f"sp {sp}")
            # seq dim (index 2 after the [n, mb, ...] reshape) shards over
            # sp; params stay replicated over sp (their specs name only pp)

            def seq_spec(a):
                return P(*([None, None, SP] + [None] * (a.ndim - 3)))

            in_specs = (specs, seq_spec(xs), seq_spec(coss), seq_spec(sins),
                        P(None, None, SP), P(None, None, SP))
            out_spec = P(None, None, SP, None)
            manual = {PP, SP}
        else:
            in_specs = (specs, P(), P(), P(), P(), P())
            out_spec = P()
            manual = {PP}
        fn = jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs,
            out_specs=out_spec, axis_names=manual, check_vma=False)
        outs = fn(staged, xs, coss, sins, valids, segs)
        return outs.reshape(b_pad, t, d)[:b]

    return layers_fn
