"""Stream PPO critic: token-value model with stream update semantics.

Equivalent of the reference's C9 ``StreamDataParallelPPOCritic``
(``stream_dp_critic.py:49-141``): value loss with clipping
(``compute_value_loss``), gradient accumulation scaled by loss_scale, opt
step on ``is_opt_step``. The value model is the decoder trunk with a scalar
head instead of the LM head.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax

from polyrl_tpu import obs
from polyrl_tpu.models import decoder
from polyrl_tpu.ops import core_algos
from polyrl_tpu.parallel import mesh as meshlib


@dataclasses.dataclass(frozen=True)
class CriticConfig:
    cliprange_value: float = 0.5
    loss_agg_mode: str = "token-mean"
    lr: float = 1e-5
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    remat: bool = True


def init_critic_params(rng: jax.Array, model_cfg: decoder.ModelConfig) -> dict:
    params = decoder.init_params(rng, model_cfg)
    params.pop("lm_head", None)
    params["value_head"] = (
        jax.random.normal(jax.random.fold_in(rng, 7), (model_cfg.hidden_size, 1), jnp.float32)
        * 0.01
    ).astype(model_cfg.dtype)
    return params


def critic_param_specs(model_cfg: decoder.ModelConfig) -> dict:
    from jax.sharding import PartitionSpec as P

    specs = decoder.param_specs(model_cfg)
    specs.pop("lm_head", None)
    specs["value_head"] = P(None, None)
    return specs


def forward_values(params, model_cfg, input_ids, positions, attn_mask, responses,
                   remat, attn_fn=None, layers_fn=None):
    """Token values for the response region [B, T_resp] (f32)."""
    # trunk forward: reuse decoder but skip the LM head by computing
    # hidden states via a value-head projection on the normed trunk output.
    value_params = dict(params)
    head = value_params.pop("value_head")
    # decoder.forward computes logits = x @ head; give it the value head as a
    # [D, 1] lm_head so XLA never materialises the [B, T, V] logits.
    value_params["lm_head"] = head
    cfg = dataclasses.replace(model_cfg, tie_word_embeddings=False)
    values, _ = decoder.forward(value_params, cfg, input_ids, positions,
                                attn_mask, remat=remat, attn_fn=attn_fn,
                                layers_fn=layers_fn)
    t_resp = responses.shape[1]
    return values[:, -t_resp - 1 : -1, 0].astype(jnp.float32)


def forward_values_packed(params, model_cfg, input_ids, positions, attn_mask,
                          segment_ids, remat, loss_mask=None, attn_fn=None,
                          layers_fn=None):
    """Per-column values [R, L] on the packed (remove-padding) layout
    (reference packed critic, stream_dp_critic.py:35,83): column t holds the
    value predicted from column t-1 — the same one-left shift as
    ``forward_values`` and the packed logprob pass, so the caller's
    loss_mask/gather spec selects response-token values directly.
    ``loss_mask`` zeroes columns outside the mask (finiteness guard, same
    double-where rationale as the actor's packed pass). ``attn_fn``:
    optional segment-aware SP attention (see the actor's packed pass)."""
    from polyrl_tpu.trainer.actor import bind_packed_attention

    attn, lf = bind_packed_attention(attn_fn, layers_fn, segment_ids)
    value_params = dict(params)
    head = value_params.pop("value_head")
    value_params["lm_head"] = head
    cfg = dataclasses.replace(model_cfg, tie_word_embeddings=False)
    values, _ = decoder.forward(value_params, cfg, input_ids, positions,
                                attn_mask, remat=remat, attn_fn=attn,
                                layers_fn=lf)
    v = values[:, :-1, 0].astype(jnp.float32)
    v = jnp.pad(v, ((0, 0), (1, 0)))
    if loss_mask is not None:
        v = jnp.where(loss_mask > 0, v, 0.0)
    return v


class StreamCritic:
    def __init__(self, model_cfg: decoder.ModelConfig, cfg: CriticConfig,
                 params: Any, mesh=None, attn_fn=None, layers_fn=None,
                 packed_attn_fn=None):
        from polyrl_tpu.trainer.actor import default_train_attention

        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh
        self.attn_fn = (attn_fn if attn_fn is not None
                        else default_train_attention(mesh))
        self.layers_fn = layers_fn  # pipeline-parallel layer stack (pp > 1)
        if packed_attn_fn is None and layers_fn is None:  # see StreamActor
            packed_attn_fn = default_train_attention(mesh, packed=True)
        self.packed_attn_fn = packed_attn_fn
        if mesh is not None:
            # backbone leaves follow decoder.param_specs; critic-only leaves
            # (the [D, 1] value head) fall back to replicated
            params = meshlib.shard_params(mesh, params,
                                          decoder.param_specs(model_cfg))
        self.params = params
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.adamw(cfg.lr, weight_decay=cfg.weight_decay),
        )
        self.opt_state = self.optimizer.init(params)
        self.accum_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        self._accum_scale = 0.0  # see StreamActor: tail-flush renormalization
        self._update_fns: dict = {}
        self._value_fn = None

    def _loss(self, params, batch, loss_scale):
        if "segment_ids" in batch:  # packed (remove-padding) layout
            vpreds = forward_values_packed(
                params, self.model_cfg, batch["input_ids"],
                batch["positions"], batch["attention_mask"],
                batch["segment_ids"], self.cfg.remat,
                loss_mask=batch["loss_mask"], attn_fn=self.packed_attn_fn,
                layers_fn=self.layers_fn,
            )
            mask = batch["loss_mask"]
        else:
            vpreds = forward_values(
                params, self.model_cfg, batch["input_ids"], batch["positions"],
                batch["attention_mask"], batch["responses"], self.cfg.remat,
                attn_fn=self.attn_fn, layers_fn=self.layers_fn,
            )
            mask = batch["response_mask"]
        vf_loss, clipfrac = core_algos.compute_value_loss(
            vpreds, batch["returns"], batch["values"], mask,
            cliprange_value=self.cfg.cliprange_value,
            loss_agg_mode=self.cfg.loss_agg_mode,
        )
        return vf_loss * loss_scale, {"critic/vf_loss": vf_loss, "critic/vf_clipfrac": clipfrac}

    def _build_update(self, is_opt_step: bool):
        optimizer = self.optimizer

        def critic_update(params, opt_state, accum, batch, loss_scale):
            (loss, metrics), grads = jax.value_and_grad(self._loss, has_aux=True)(
                params, batch, loss_scale
            )
            accum = jax.tree_util.tree_map(jnp.add, accum, grads)
            if is_opt_step:
                updates, opt_state = optimizer.update(accum, opt_state, params)
                params = optax.apply_updates(params, updates)
                metrics = dict(metrics)
                metrics["critic/grad_norm"] = optax.global_norm(accum)
                accum = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return params, opt_state, accum, loss, metrics

        return meshlib.under(
            self.mesh, jax.jit(critic_update, donate_argnums=(0, 1, 2)))

    def _shard_feed(self, batch: dict) -> dict:
        if self.mesh is None:
            return batch
        return meshlib.shard_batch(self.mesh, batch)

    def update_stream(self, batch: dict, is_opt_step: bool, loss_scale: float = 1.0) -> dict:
        batch = self._shard_feed(batch)
        if is_opt_step not in self._update_fns:
            self._update_fns[is_opt_step] = self._build_update(is_opt_step)
        self.params, self.opt_state, self.accum_grads, _, metrics = self._update_fns[is_opt_step](
            self.params, self.opt_state, self.accum_grads, batch,
            jnp.asarray(loss_scale, jnp.float32),
        )
        self._accum_scale = 0.0 if is_opt_step else self._accum_scale + loss_scale
        return metrics

    def flush_opt_step(self) -> dict:
        """Apply accumulated grads without new data (see StreamActor);
        renormalizes by the summed loss_scale so the partial minibatch's
        effective gradient scale matches a full one."""
        if not hasattr(self, "_flush_fn"):
            optimizer = self.optimizer

            def critic_flush(params, opt_state, accum, inv_scale):
                accum = jax.tree_util.tree_map(lambda g: g * inv_scale, accum)
                updates, opt_state = optimizer.update(accum, opt_state, params)
                params = optax.apply_updates(params, updates)
                gn = optax.global_norm(accum)
                accum = jax.tree_util.tree_map(jnp.zeros_like, accum)
                return params, opt_state, accum, gn

            self._flush_fn = jax.jit(critic_flush, donate_argnums=(0, 1, 2))
        inv = 1.0 / self._accum_scale if self._accum_scale > 0 else 1.0
        self.params, self.opt_state, self.accum_grads, gn = self._flush_fn(
            self.params, self.opt_state, self.accum_grads,
            jnp.asarray(inv, jnp.float32))
        self._accum_scale = 0.0
        return {"critic/grad_norm": gn}

    def compute_values(self, batch: dict) -> jnp.ndarray:
        batch = self._shard_feed(batch)
        if self._value_fn is None:
            self._value_fn = meshlib.under(
                self.mesh, jax.jit(obs.named_program(
                    "critic_value",
                    lambda p, b: forward_values(
                        p, self.model_cfg, b["input_ids"], b["positions"],
                        b["attention_mask"], b["responses"], False,
                        attn_fn=self.attn_fn, layers_fn=self.layers_fn,
                    ))))
        return self._value_fn(self.params, batch)

    def compute_values_packed(self, batch: dict) -> jnp.ndarray:
        """[R, L] per-column values on a packed feed (no grad)."""
        batch = self._shard_feed(batch)
        if not hasattr(self, "_value_fn_packed"):
            self._value_fn_packed = meshlib.under(
                self.mesh, jax.jit(obs.named_program(
                    "critic_value_packed",
                    lambda p, b: forward_values_packed(
                        p, self.model_cfg, b["input_ids"], b["positions"],
                        b["attention_mask"], b["segment_ids"], False,
                        loss_mask=b.get("loss_mask"),
                        attn_fn=self.packed_attn_fn,
                        layers_fn=self.layers_fn,
                    ))))
        return self._value_fn_packed(self.params, batch)
