"""Stream PPO/GRPO actor: per-ibatch fwd/bwd with gradient accumulation and
optimizer step at minibatch boundaries.

TPU-native equivalent of the reference's C8 ``StreamDataParallelPPOActor``
(``stream_dp_actor.py:58-231``): the input is already a sub-minibatch;
gradients accumulate across calls scaled by ``loss_scale_factor``; the
optimizer steps only when ``is_opt_step`` is set (reference :226-230, the
cumulative-minibatch-boundary logic lives in the trainer). Instead of
FSDP+NCCL, params/grads/opt-state shard over the (fsdp, tp) mesh axes and
GSPMD inserts the collectives.

Also provides ``compute_log_prob`` (the old/ref logprob pass, reference
stream_ray_trainer.py:425-439) and the ref-policy variant.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from polyrl_tpu import obs
from polyrl_tpu.models import decoder
from polyrl_tpu.ops import core_algos
from polyrl_tpu.parallel import mesh as meshlib


@dataclasses.dataclass(frozen=True)
class ActorConfig:
    policy_loss: str = "vanilla"          # vanilla | gpg | clip_cov
    clip_ratio: float = 0.2
    clip_ratio_low: float | None = None
    clip_ratio_high: float | None = None
    clip_ratio_c: float = 3.0
    entropy_coeff: float = 0.0
    use_kl_loss: bool = False             # GRPO-style in-loss KL
    kl_loss_coef: float = 0.001
    kl_loss_type: str = "low_var_kl"
    loss_agg_mode: str = "token-mean"
    lr: float = 1e-6
    lr_warmup_steps: int = 0
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    # host-offload optimizer state between steps: frees HBM for the rollout
    # phase in colocated time-slicing (the reference's FSDP optimizer CPU
    # offload, stream_fsdp_workers.py:308-316,386-389)
    offload_optimizer: bool = False
    # LoRA fine-tuning (models/lora.py; the reference exposes this through
    # verl's config but marks it untested, stream_fsdp_workers.py:224):
    # rank > 0 wraps attention + dense-MLP weights in adapters, freezes the
    # base, and the optimizer updates only a/b. Weight pushes merge.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Skip (don't apply) optimizer updates containing non-finite values: a
    # single poisoned minibatch (corrupt rollout data, overflowed loss) must
    # degrade one step, not NaN the params and cascade NaN logits into every
    # engine at the next weight sync. 0 disables the guard.
    max_nonfinite_skips: int = 100
    ppo_epochs: int = 1                   # reference guards ppo_epochs==1 (stream_dp_actor.py:145)
    remat: bool = True


def make_optimizer(cfg: ActorConfig, total_steps: int = 0) -> optax.GradientTransformation:
    """AdamW with grad clipping; warmup (+cosine decay when total_steps>0)."""
    if total_steps > 0:
        sched = optax.warmup_cosine_decay_schedule(
            0.0, cfg.lr, max(cfg.lr_warmup_steps, 1), total_steps
        )
    elif cfg.lr_warmup_steps > 0:
        sched = optax.linear_schedule(0.0, cfg.lr, cfg.lr_warmup_steps)
    else:
        sched = cfg.lr
    opt = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adamw(sched, b1=0.9, b2=0.999, eps=1e-8, weight_decay=cfg.weight_decay),
    )
    if cfg.max_nonfinite_skips > 0:
        opt = optax.apply_if_finite(opt, max_consecutive_errors=cfg.max_nonfinite_skips)
    return opt


def default_train_attention(mesh=None, packed: bool = False):
    """Default training attention: Pallas flash on TPU (O(T) memory — the
    reference's flash-attn varlen role), dense masked attention elsewhere.
    Under a mesh the call is shard_mapped over batch and heads
    (parallel/sequence.make_sharded_flash_attention): GSPMD cannot
    partition the Pallas kernel by itself. ``packed``: the segment-id
    variant for ``bind_packed_attention`` (None without a mesh — its own
    default is the single-logical-device segment-id flash kernel)."""
    if mesh is not None:
        from polyrl_tpu.parallel.sequence import make_sharded_flash_attention

        return make_sharded_flash_attention(mesh, packed=packed)
    if packed:
        return None
    from polyrl_tpu.ops import flash

    return flash.auto_train_attention()


def _model_logprobs_entropy(params, model_cfg, input_ids, positions, attn_mask,
                            responses, response_mask, remat, compute_entropy,
                            attn_fn=None, layers_fn=None):
    """Forward over [B, T_total]; logprobs of response tokens [B, T_resp].
    ``attn_fn``: optional sequence-parallel attention (Ulysses/ring) for
    long-context training (SURVEY §5.7). ``layers_fn``: optional
    pipeline-parallel layer stack (parallel.pipeline)."""
    logits, _ = decoder.forward(params, model_cfg, input_ids, positions,
                                attn_mask, remat=remat, attn_fn=attn_fn,
                                layers_fn=layers_fn)
    t_resp = responses.shape[1]
    # logits at position i predict token i+1: responses occupy the last
    # t_resp positions of input_ids, so their predictors are shifted one left.
    pred_logits = logits[:, -t_resp - 1 : -1, :]
    # Finiteness contract: padded positions must come out 0, not NaN/-inf —
    # downstream the PPO ratio is exp(lp - old_lp) and `inf * mask(=0)` is
    # NaN, so masking at the consumer cannot recover. The where goes on the
    # LOGITS, before logsumexp/take_along_axis (double-where pattern): a
    # where on the logprob output alone zeroes the forward value but its
    # VJP still computes 0 * softmax(NaN) = NaN, poisoning the shared
    # weight gradients for the whole batch.
    pred_logits = jnp.where(response_mask[..., None] > 0, pred_logits, 0.0)
    logprobs = jnp.where(
        response_mask > 0,
        core_algos.logprobs_from_logits(pred_logits, responses), 0.0)
    if compute_entropy:
        entropy = jnp.where(response_mask > 0,
                            core_algos.entropy_from_logits(pred_logits), 0.0)
    else:
        entropy = None
    return logprobs, entropy


def bind_packed_attention(attn_fn, layers_fn, segment_ids):
    """Bind a packed batch's segment ids into the attention machinery —
    ONE place for the dispatch shared by the actor's logprob pass and the
    critic's value pass. Returns ``(attn, lf)`` for ``decoder.forward``:

    - ``layers_fn`` set (packed × pipeline): the stage attention takes the
      segment ids; an SP attn_fn alongside it is rejected here too (not
      just in build_trainer) because decoder.forward would silently ignore
      it — the pipeline computes its own stage attention.
    - ``attn_fn`` set (packed × SP): the segment-aware Ulysses/ring fn.
    - neither: the single-logical-device segment-id flash kernel.
    """
    from polyrl_tpu.ops import flash

    if layers_fn is not None:
        if attn_fn is not None:
            raise ValueError(
                "packed pass got BOTH an SP attn_fn and a pipeline "
                "layers_fn; the pipeline computes its own stage attention")
        return None, (lambda layers, x, cos, sin, am: layers_fn(
            layers, x, cos, sin, am, segment_ids=segment_ids))
    if attn_fn is None:
        return (lambda q, k, v, am: flash.flash_attention_train(
            q, k, v, am, causal=True, segment_ids=segment_ids)), None
    return (lambda q, k, v, am: attn_fn(q, k, v, am, segment_ids)), None


def _packed_logprobs_entropy(params, model_cfg, input_ids, positions,
                             attn_mask, segment_ids, remat, compute_entropy,
                             loss_mask=None, attn_fn=None, layers_fn=None):
    """Packed-row (remove-padding) variant: rows hold several trajectories
    separated by segment ids (reference use_remove_padding + flash varlen,
    stream_dp_actor.py:41-47). Returns per-COLUMN logprobs [R, L]: column t
    holds the logprob of input_ids[:, t] predicted from column t-1 — response
    tokens are selected by the caller's loss_mask (never at column 0, since a
    segment always starts with >= 1 prompt token).

    ``loss_mask`` (optional, [R, L]) enables the same double-where finiteness
    guard as the padded path: logits at columns outside the mask are zeroed
    BEFORE the logprob computation so a NaN there (pack-padding columns)
    cannot reach the forward value or the gradient.

    ``attn_fn`` (optional): a segment-aware SP attention
    (parallel.sequence.make_sp_attention(packed=True)) — signature
    (q, k, v, token_mask, segment_ids) — so packed training composes with
    sp > 1 (the reference's default long-context configuration,
    stream_dp_actor.py:37-47,135); defaults to the single-logical-device
    segment-id flash kernel."""
    attn, lf = bind_packed_attention(attn_fn, layers_fn, segment_ids)
    logits, _ = decoder.forward(params, model_cfg, input_ids, positions,
                                attn_mask, remat=remat, attn_fn=attn,
                                layers_fn=lf)
    pred = logits[:, :-1, :]
    targets = input_ids[:, 1:]
    if loss_mask is not None:
        pred = jnp.where(loss_mask[:, 1:, None] > 0, pred, 0.0)
    lp = core_algos.logprobs_from_logits(pred, targets)
    lp = jnp.pad(lp, ((0, 0), (1, 0)))
    if compute_entropy:
        ent = jnp.pad(core_algos.entropy_from_logits(pred), ((0, 0), (1, 0)))
    else:
        ent = None
    if loss_mask is not None:
        lp = jnp.where(loss_mask > 0, lp, 0.0)
        if ent is not None:
            ent = jnp.where(loss_mask > 0, ent, 0.0)
    return lp, ent


class StreamActor:
    """Owns params + optimizer + accumulated grads; stream-update semantics."""

    def __init__(
        self,
        model_cfg: decoder.ModelConfig,
        cfg: ActorConfig,
        params: Any,
        mesh=None,
        attn_fn=None,
        layers_fn=None,
        packed_attn_fn=None,
    ):
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh
        self.attn_fn = (attn_fn if attn_fn is not None
                        else default_train_attention(mesh))
        self.layers_fn = layers_fn  # pipeline-parallel layer stack (pp > 1)
        # segment-aware attention for the packed (remove-padding) passes:
        # the SP variant when given, the mesh-sharded flash under a mesh
        # (the pipeline computes its own stage attention instead), else
        # None → the single-logical-device segment-id flash kernel
        if packed_attn_fn is None and layers_fn is None:
            packed_attn_fn = default_train_attention(mesh, packed=True)
        self.packed_attn_fn = packed_attn_fn
        self._lora = cfg.lora_rank > 0
        if self._lora:
            from polyrl_tpu.models import lora as lora_mod

            params = lora_mod.wrap_lora(
                params, jax.random.PRNGKey(7919 + cfg.lora_rank),
                cfg.lora_rank, cfg.lora_alpha)
        if mesh is not None:
            # GSPMD entry: params shard over (fsdp, tp) per decoder.param_specs
            # and every feed shards over the batch spec (see update_stream);
            # grads/opt state inherit the layout through jit propagation.
            # Works identically for single-host multi-chip and jax.distributed
            # multi-host (the mesh just spans more processes).
            specs = decoder.param_specs(model_cfg)
            if self._lora:
                from polyrl_tpu.models import lora as lora_mod

                specs = lora_mod.lora_param_specs(specs)
            params = meshlib.shard_params(mesh, params, specs)
        self.params = params
        self.optimizer = make_optimizer(cfg)
        if self._lora:
            # adapters are the ONLY trainable leaves: frozen leaves get
            # set_to_zero updates and no optimizer state
            from polyrl_tpu.models import lora as lora_mod

            self.optimizer = lora_mod.lora_optimizer(self.optimizer, params)
        self.opt_state = self.optimizer.init(params)
        if self._lora:
            from polyrl_tpu.models import lora as lora_mod

            self._labels = lora_mod.lora_labels(params)
        else:
            self._labels = None
        self.accum_grads = self._zero_accum(params)
        # sum of loss_scales accumulated since the last opt step: a tail
        # flush renormalizes by it so a partial minibatch sees the same
        # effective gradient scale as a full one (mean over actual micros,
        # not sum/G — reference loss_scale_factor semantics)
        self._accum_scale = 0.0
        self._update_fns: dict = {}
        self._logprob_fns: dict = {}
        self._opt_offloaded = False
        self._opt_shardings = None

    def export_params(self):
        """Params in the plain full-precision layout the rollout plane and
        transfer fabric expect: LoRA adapters merged into their bases; a
        plain tree passes through unchanged."""
        if not self._lora:
            return self.params
        from polyrl_tpu.models import lora as lora_mod

        if not hasattr(self, "_merge_fn"):
            self._merge_fn = jax.jit(lora_mod.merge_lora)
        return self._merge_fn(self.params)

    # -- optimizer host offload (reference FSDP opt CPU offload,
    # stream_fsdp_workers.py:308-316: load lazily, offload after step) ----

    def offload_opt_state(self) -> None:
        """Move optimizer state to host memory, freeing its HBM for the
        rollout phase. No-op unless cfg.offload_optimizer."""
        if not self.cfg.offload_optimizer or self._opt_offloaded:
            return
        self._opt_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding if isinstance(x, jax.Array) else None,
            self.opt_state)
        self.opt_state = jax.tree_util.tree_map(
            lambda x: np.asarray(x) if isinstance(x, jax.Array) else x,
            self.opt_state)
        self._opt_offloaded = True

    def load_opt_state(self) -> None:
        """Bring offloaded optimizer state back to the mesh."""
        if not self._opt_offloaded:
            return
        self.opt_state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            self.opt_state, self._opt_shardings)
        self._opt_offloaded = False

    # -- jitted kernels ---------------------------------------------------

    def _loss_fn(self, params, batch, loss_scale: float):
        cfg = self.cfg
        if "segment_ids" in batch:
            # packed rows: loss_mask plays response_mask; advantages /
            # old_log_probs already live in the packed [R, L] layout
            logprobs, entropy = _packed_logprobs_entropy(
                params, self.model_cfg,
                batch["input_ids"], batch["positions"],
                batch["attention_mask"], batch["segment_ids"],
                cfg.remat, cfg.entropy_coeff != 0.0,
                loss_mask=batch["loss_mask"], attn_fn=self.packed_attn_fn,
                layers_fn=self.layers_fn,
            )
            batch = dict(batch, response_mask=batch["loss_mask"])
        else:
            logprobs, entropy = _model_logprobs_entropy(
                params, self.model_cfg,
                batch["input_ids"], batch["positions"], batch["attention_mask"],
                batch["responses"], batch["response_mask"],
                cfg.remat, cfg.entropy_coeff != 0.0, attn_fn=self.attn_fn,
                layers_fn=self.layers_fn,
            )
        loss_fn = core_algos.get_policy_loss_fn(cfg.policy_loss)
        pg_loss, clipfrac, approx_kl, clipfrac_lower = loss_fn(
            batch["old_log_probs"], logprobs, batch["advantages"],
            batch["response_mask"],
            clip_ratio=cfg.clip_ratio, clip_ratio_low=cfg.clip_ratio_low,
            clip_ratio_high=cfg.clip_ratio_high, clip_ratio_c=cfg.clip_ratio_c,
            loss_agg_mode=cfg.loss_agg_mode,
        ) if cfg.policy_loss != "gpg" else loss_fn(
            batch["old_log_probs"], logprobs, batch["advantages"],
            batch["response_mask"], loss_agg_mode=cfg.loss_agg_mode,
        )
        loss = pg_loss
        metrics = {
            "actor/pg_loss": pg_loss,
            "actor/clipfrac": clipfrac,
            "actor/approx_kl": approx_kl,
            "actor/clipfrac_lower": clipfrac_lower,
        }
        if cfg.entropy_coeff != 0.0:
            ent = core_algos.agg_loss(entropy, batch["response_mask"], cfg.loss_agg_mode)
            loss = loss - cfg.entropy_coeff * ent
            metrics["actor/entropy"] = ent
        if cfg.use_kl_loss:
            kld = core_algos.kl_penalty(logprobs, batch["ref_log_probs"], cfg.kl_loss_type)
            kl_loss = core_algos.agg_loss(kld, batch["response_mask"], cfg.loss_agg_mode)
            loss = loss + cfg.kl_loss_coef * kl_loss
            metrics["actor/kl_loss"] = kl_loss
        return loss * loss_scale, metrics

    def _zero_accum(self, tree):
        """Gradient-accumulation buffers: full zeros_like normally; under
        LoRA the frozen leaves collapse to scalar placeholders — a second
        full model copy in HBM (plus full-size accumulate adds every
        micro) would give up most of LoRA's training-memory win."""
        if self._labels is None:
            return jax.tree_util.tree_map(jnp.zeros_like, tree)
        return jax.tree_util.tree_map(
            lambda x, l: (jnp.zeros((), x.dtype) if l == "freeze"
                          else jnp.zeros_like(x)), tree, self._labels)

    def _build_update(self, is_opt_step: bool):
        optimizer = self.optimizer
        labels = self._labels

        def actor_update(params, opt_state, accum_grads, batch, loss_scale):
            (loss, metrics), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
                params, batch, loss_scale
            )
            if labels is None:
                accum_grads = jax.tree_util.tree_map(jnp.add, accum_grads,
                                                     grads)
            else:
                # frozen leaves keep their scalar placeholder (their grads
                # are structurally zero via mm's stop_gradient anyway)
                accum_grads = jax.tree_util.tree_map(
                    lambda a, g, l: a if l == "freeze" else a + g,
                    accum_grads, grads, labels)
            if is_opt_step:
                updates, opt_state = optimizer.update(accum_grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                metrics = dict(metrics)
                metrics["actor/grad_norm"] = optax.global_norm(accum_grads)
                if hasattr(opt_state, "total_notfinite"):
                    metrics["actor/nonfinite_skips"] = opt_state.total_notfinite
                accum_grads = jax.tree_util.tree_map(jnp.zeros_like, accum_grads)
            return params, opt_state, accum_grads, loss, metrics

        return meshlib.under(
            self.mesh, jax.jit(actor_update, donate_argnums=(0, 1, 2)))

    def _shard_feed(self, batch: dict) -> dict:
        """Batch-shard a host-side feed over the mesh (no-op without one).
        Each process supplies the FULL array; device_put slices the local
        shards — the jax multi-host data path (per-host data sharding)."""
        if self.mesh is None:
            return batch
        return meshlib.shard_batch(self.mesh, batch)

    def update_stream(self, batch: dict, is_opt_step: bool, loss_scale: float = 1.0) -> dict:
        """One sub-minibatch fwd/bwd (+opt step at boundary). ``batch`` is a
        dict of arrays: input_ids, positions, attention_mask, responses,
        response_mask, advantages, old_log_probs [, ref_log_probs]."""
        batch = self._shard_feed(batch)
        self.load_opt_state()
        if is_opt_step not in self._update_fns:
            self._update_fns[is_opt_step] = self._build_update(is_opt_step)
        fn = self._update_fns[is_opt_step]
        self.params, self.opt_state, self.accum_grads, loss, metrics = fn(
            self.params, self.opt_state, self.accum_grads, batch,
            jnp.asarray(loss_scale, jnp.float32),
        )
        self._accum_scale = 0.0 if is_opt_step else self._accum_scale + loss_scale
        return metrics

    def flush_opt_step(self) -> dict:
        """Apply accumulated grads without new data — the stream trainer's
        final flush when a short batch (dropped groups) ends mid-minibatch.
        Accumulated grads are renormalized by the summed loss_scale so the
        partial minibatch's update has the same effective gradient scale
        (mean over its micros) as a full minibatch, not a sum/G fraction."""
        self.load_opt_state()
        if not hasattr(self, "_flush_fn"):
            optimizer = self.optimizer

            def actor_flush(params, opt_state, accum_grads, inv_scale):
                accum_grads = jax.tree_util.tree_map(
                    lambda g: g * inv_scale, accum_grads)
                updates, opt_state = optimizer.update(accum_grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                gn = optax.global_norm(accum_grads)
                accum_grads = jax.tree_util.tree_map(jnp.zeros_like, accum_grads)
                return params, opt_state, accum_grads, gn

            self._flush_fn = jax.jit(actor_flush, donate_argnums=(0, 1, 2))
        inv = 1.0 / self._accum_scale if self._accum_scale > 0 else 1.0
        self.params, self.opt_state, self.accum_grads, gn = self._flush_fn(
            self.params, self.opt_state, self.accum_grads,
            jnp.asarray(inv, jnp.float32))
        self._accum_scale = 0.0
        return {"actor/grad_norm": gn}

    def compute_log_prob(self, batch: dict, compute_entropy: bool = True):
        """Old-logprob pass (no grad). Returns (logprobs, entropy|None)."""
        batch = self._shard_feed(batch)
        if compute_entropy not in self._logprob_fns:
            self._logprob_fns[compute_entropy] = meshlib.under(
                self.mesh, jax.jit(
                    obs.named_program("actor_logprob", partial(
                        _model_logprobs_entropy, remat=False,
                        compute_entropy=compute_entropy,
                        attn_fn=self.attn_fn, layers_fn=self.layers_fn)),
                    static_argnums=(1,)))
        return self._logprob_fns[compute_entropy](
            self.params, self.model_cfg,
            batch["input_ids"], batch["positions"], batch["attention_mask"],
            batch["responses"], batch["response_mask"],
        )

    def compute_log_prob_packed(self, batch: dict, compute_entropy: bool = True,
                                params=None):
        """Packed-row logprob pass: [R, L] per-column logprobs aligned so
        loss_mask selects response tokens (see _packed_logprobs_entropy)."""
        batch = self._shard_feed(batch)
        key = ("packed", compute_entropy)
        if key not in self._logprob_fns:
            self._logprob_fns[key] = meshlib.under(self.mesh, jax.jit(
                obs.named_program("actor_logprob_packed", partial(
                    _packed_logprobs_entropy, remat=False,
                    compute_entropy=compute_entropy,
                    attn_fn=self.packed_attn_fn,
                    layers_fn=self.layers_fn)),
                static_argnums=(1,)))
        return self._logprob_fns[key](
            params if params is not None else self.params, self.model_cfg,
            batch["input_ids"], batch["positions"], batch["attention_mask"],
            batch["segment_ids"], loss_mask=batch.get("loss_mask"),
        )


class ReferencePolicy:
    """Frozen reference policy for KL (reference ref worker role).

    Owns a COPY of the params: the actor's update step donates its param
    buffers to XLA, so sharing the initial pytree would leave this policy
    holding deleted buffers after the first optimizer step.
    """

    def __init__(self, model_cfg: decoder.ModelConfig, params: Any, attn_fn=None):
        self.model_cfg = model_cfg
        self.params = jax.tree_util.tree_map(jnp.copy, params)
        if attn_fn is None:
            attn_fn = default_train_attention()
        self._fn = jax.jit(
            obs.named_program("ref_logprob", partial(
                _model_logprobs_entropy, remat=False, compute_entropy=False,
                attn_fn=attn_fn)),
            static_argnums=(1,),
        )
        self._packed_fn = jax.jit(
            obs.named_program("ref_logprob_packed", partial(
                _packed_logprobs_entropy, remat=False,
                compute_entropy=False)),
            static_argnums=(1,),
        )

    def compute_log_prob(self, batch: dict):
        lp, _ = self._fn(
            self.params, self.model_cfg,
            batch["input_ids"], batch["positions"], batch["attention_mask"],
            batch["responses"], batch["response_mask"],
        )
        return lp

    def compute_log_prob_packed(self, batch: dict):
        lp, _ = self._packed_fn(
            self.params, self.model_cfg,
            batch["input_ids"], batch["positions"], batch["attention_mask"],
            batch["segment_ids"], loss_mask=batch.get("loss_mask"),
        )
        return lp
