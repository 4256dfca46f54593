"""Functional transformer decoder (Llama-3 / Qwen-3 families), TPU-first.

Replaces the reference's HF-transformers actor/critic modules wrapped in
FSDP (reference ``stream_fsdp_workers.py:284-302``) and SGLang's serving
model. One functional forward serves training (full-sequence, remat'd
scan-over-layers) and rollout (incremental decode against a KV cache).

Design choices (TPU rationale):
- Params are plain pytrees (nested dicts of jnp arrays); layer params are
  STACKED along a leading ``n_layers`` axis and the forward runs
  ``lax.scan`` over them — one compiled layer body regardless of depth
  (fast compile, XLA-friendly), with ``jax.checkpoint`` rematerialisation
  for the training path (HBM↔FLOPs trade, SURVEY.md §2.2 FSDP row).
- bf16 params/activations, f32 softmax/logits head.
- GQA + RoPE (llama3 frequency scaling supported), RMSNorm, SwiGLU,
  optional per-head QK-norm (Qwen3).
- One kind of layer, repeated, is this file's; a model whose layers
  differ in kind (``models/cache_spec.py::layer_plan``: KDA, MLA, a routed
  MLP behind dense layers) has its blocks and its layer loop in
  ``models/hybrid.py``, and every entry point here hands over to it.
- ``param_specs`` returns a matching PartitionSpec tree: params shard over
  (fsdp, tp) — GSPMD inserts the all-gathers the reference got from FSDP
  + NCCL (SURVEY.md §2.4 mapping).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from polyrl_tpu.models import cache_spec, hybrid
from polyrl_tpu.models.blocks import (EXPERT_KEYS, _head, _moe_mlp,  # noqa: F401
                                      _scatter_pages_kv, _scatter_token_kv,
                                      head_input, norm, rms_norm)
from polyrl_tpu.models.quant import LoraWeight, QuantWeight, mm
from polyrl_tpu.ops.attention import attention, causal_mask
from polyrl_tpu.parallel.mesh import DP, EP, FSDP, SP, TP


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Frequency scaling of the rope (frozen → ModelConfig stays hashable
    for use as a jit static argument). ``rope_type`` ``llama3``: NTK by
    parts between ``low_freq_factor`` and ``high_freq_factor``. ``yarn``
    (DeepSeek-V3's reading, ``mixers.base.yarn_inv_freq``): each
    frequency is divided by ``factor`` below the dimension at which the
    original length makes ``beta_slow`` turns, kept above the one at which
    it makes ``beta_fast``, blended linearly between; cos and sin are
    scaled by ``mscale / mscale_all_dim``'s ratio and the logits by the
    square of ``0.1 * mscale_all_dim * ln(factor) + 1``."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    rope_type: str = "llama3"
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # YaRN's factor on cos and sin as published (``attention_factor``);
    # 0: what ``mscale`` and ``mscale_all_dim`` give
    attention_factor: float = 0.0


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """The rope of one kind of layer (a published ``rope_parameters``
    block): its base, the share of a head's columns it turns (the first
    ones), its frequency scaling."""

    rope_theta: float
    partial_rotary_factor: float = 1.0
    scaling: RopeScaling | None = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int | None = None  # default hidden/heads
    rope_theta: float = 500000.0
    rope_scaling: RopeScaling | None = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    use_qk_norm: bool = False  # Qwen3
    attention_bias: bool = False  # Qwen2/2.5 family (qkv projection bias)
    max_position_embeddings: int = 131072
    # MoE (Qwen3-MoE / Mixtral-class): num_experts > 0 replaces every
    # layer's dense MLP with a routed mixture (softmax-over-all-experts
    # top-k routing, HF Qwen3MoeSparseMoeBlock semantics), dropless: every
    # (token, expert) choice is computed (``_moe_mlp``).
    num_experts: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # the router (DeepSeek-V3's ``noaux_tc`` when ``sigmoid``): scores,
    # groups of consecutive experts of which the best ``topk_group`` are
    # kept, a bias that enters the choice and not the weights, a factor
    # on the k weights, one shared expert of this width beside the routed
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    moe_shared_expert_intermediate_size: int = 0
    # (first, count) of the experts this chip holds out of ``num_experts``
    # (a chip's share of an expert-parallel deployment: the router keeps
    # its width, choices that fall elsewhere are left out); None: all
    experts_held: tuple | None = None
    # layers of several kinds (``models/cache_spec.py::layer_plan``):
    # ``layer_group_size`` > 0 makes published layer i MLA where (i + 1) %
    # size == 0 and KDA otherwise; the first ``first_k_dense_replace``
    # published layers keep the dense MLP; ``kept_layers`` are the
    # published layers run here (a depth cut; ``num_layers`` of them)
    layer_group_size: int = 0
    first_k_dense_replace: int = 0
    kept_layers: tuple | None = None
    # multi-head latent attention: ``kv_lora_rank`` > 0 without a
    # ``layer_group_size`` is MLA in every layer (DeepSeek-V3's decoder);
    # ``q_lora_rank`` > 0 puts a normed latent between the input and the
    # queries; ``mla_head_gate``: sigmoid(x Wgate)_h on each head's output
    # (Ling's ``gated_attention_proj_granularity_type`` head_wise)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    mla_head_gate: bool = False
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    short_conv_kernel_size: int = 0
    kda_lower_bound: float = -5.0
    # compressed convolutional attention (ZAYA1's decoder, ``models/
    # hybrid.py``): ``cca_time0`` > 0 makes every layer ``cca``: the taps of
    # the depthwise convolution over the query and key latents and of the
    # head-wise one after it; rope turns the first ``partial_rotary_factor``
    # of a head's columns (read by ``cca`` layers alone)
    cca_time0: int = 0
    cca_time1: int = 0
    partial_rotary_factor: float = 1.0
    # > 0: the routed MLP's router is an MLP on a latent of this width that
    # each layer hands to the next (``blocks._latent_route``)
    router_hidden_size: int = 0
    # the SambaY family (``phi4flash``; ``models/hybrid.py``):
    # ``mb_per_layer`` > 0 makes the six kinds of ``cache_spec.layer_plan``
    # (a Mamba-1 scan every ``mb_per_layer`` layers of the lower half with
    # attention over the last ``sliding_window`` keys between, then one
    # full-attention layer whose K/V the cross layers of the upper half
    # read, gated memory units between those), differential attention
    # without positions in every attention layer, LayerNorm with a bias
    # (``rms_norm_eps`` is its epsilon). The Mamba sizes are the family's
    # (no published key): ``ssm_dt_rank`` 0 is a sixteenth of the hidden size
    mb_per_layer: int = 0
    sliding_window: int = 0
    ssm_state_size: int = 16
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    # rope'd softmax attention of two kinds in one model (``laguna``;
    # ``models/mixers/gqa.py``): ``layer_types`` names each published layer
    # ``full_attention`` (keys and values in pages) or ``sliding_attention``
    # (the last ``sliding_window`` of them in a ring of the slot's),
    # ``num_heads_per_layer`` its query heads (``num_heads`` without),
    # ``rope_parameters`` pairs (layer type, ``RopeParameters``): a rope a
    # KIND; ``attn_head_gate``: sigmoid(x Wg)_h on each head's output
    # (the published ``gating``)
    layer_types: tuple | None = None
    num_heads_per_layer: tuple | None = None
    rope_parameters: tuple | None = None
    attn_head_gate: bool = False
    # a looped model (``ouro``; ``models/hybrid.py``): the one stack of
    # ``num_layers`` layers runs ``ut_steps`` times a token, the final norm
    # between passes, and each pass keeps keys and values of its own
    # (``cache_spec.passes``); ``sandwich_norm``: a second RMSNorm on each
    # sublayer's OUTPUT before it joins the residual stream
    ut_steps: int = 1
    sandwich_norm: bool = False
    # block-sparse softmax attention beside linear attention
    # (``minicpm_sala``; ``models/mixers/sparse.py``, ``lightning.py``):
    # ``mixer_types`` names each published layer ``minicpm4`` (InfLLM-V2:
    # a token attends the ``sparse_topk`` blocks of ``sparse_block_size``
    # keys a K/V head that its queries score highest against pooled keys,
    # the mean of ``sparse_kernel_size`` keys every ``sparse_kernel_stride``;
    # the first ``sparse_init_blocks`` and the last ``sparse_window_size``
    # keys' blocks always; every block up to ``sparse_dense_len`` keys) or
    # ``lightning-attn`` (``lightning_heads`` heads of ``lightning_head_dim``,
    # the model's own where 0, a float32 state a head under a constant
    # decay). muP: the embedding times ``scale_emb``, a sublayer's output
    # times ``scale_depth / sqrt(published depth)`` where ``scale_depth`` >
    # 0, the head's input over ``hidden_size / dim_model_base`` where the
    # latter > 0
    mixer_types: tuple | None = None
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    # layers of ONE sublayer (``nemotron_h``; ``cache_spec.PATTERN_KINDS``):
    # ``hybrid_override_pattern`` names each published layer ``M`` (a
    # Mamba-2 mixer, ``models/mixers/mamba2.py``: ``mamba_num_heads`` heads
    # of ``mamba_head_dim``, B and C shared by ``mamba_n_groups`` groups of
    # them, a state of ``ssm_state_size`` a head column, ``ssm_conv_kernel``
    # taps, prefill in chunks of ``ssd_chunk_size``), ``*`` (a ``gqa``
    # mixer; ``attn_no_rope``: without positions) or ``E`` (the routed MLP;
    # ``mlp_hidden_act`` relu2: an expert and the shared expert are two
    # matrices, ``relu(x W_up)^2 W_down``, no gate)
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    ssd_chunk_size: int = 128
    attn_no_rope: bool = False
    mlp_hidden_act: str = "silu"
    dtype: Any = jnp.bfloat16

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def rope_of(self, layer_type: str | None) -> RopeParameters:
        """The rope of the layers of a published layer type: its
        ``rope_parameters`` block, the model's own rope without."""
        if self.rope_parameters:
            return dict(self.rope_parameters)[layer_type]
        return RopeParameters(self.rope_theta, self.partial_rotary_factor,
                              self.rope_scaling)


# -- presets ----------------------------------------------------------------

PRESETS: dict[str, ModelConfig] = {
    # test-size model for unit tests / CPU mesh dry runs
    "tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, rope_theta=10000.0, max_position_embeddings=512,
    ),
    # Llama-3.1-8B (HF config: meta-llama/Llama-3.1-8B)
    "llama3-8b": ModelConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
        rope_scaling=RopeScaling(factor=8.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0,
                                 original_max_position_embeddings=8192),
    ),
    # Llama-3.2-1B (HF config: meta-llama/Llama-3.2-1B)
    "llama3.2-1b": ModelConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=500000.0, tie_word_embeddings=True,
        rope_scaling=RopeScaling(factor=32.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0,
                                 original_max_position_embeddings=8192),
    ),
    # Llama-3.2-3B (HF config: meta-llama/Llama-3.2-3B)
    "llama3.2-3b": ModelConfig(
        vocab_size=128256, hidden_size=3072, intermediate_size=8192,
        num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, tie_word_embeddings=True,
        rope_scaling=RopeScaling(factor=32.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0,
                                 original_max_position_embeddings=8192),
    ),
    # Qwen3-1.7B (the reference recipe model, run_async_grpo_pipeline.sh:17)
    "qwen3-1.7b": ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=28, num_heads=16, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, use_qk_norm=True,
        tie_word_embeddings=True,
    ),
    # Qwen3-8B
    "qwen3-8b": ModelConfig(
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, use_qk_norm=True,
    ),
    # Qwen2.5-0.5B (BASELINE config 1: GRPO on GSM8K)
    "qwen2.5-0.5b": ModelConfig(
        vocab_size=151936, hidden_size=896, intermediate_size=4864,
        num_layers=24, num_heads=14, num_kv_heads=2, rope_theta=1000000.0,
        attention_bias=True, tie_word_embeddings=True,
        max_position_embeddings=32768,
    ),
    # Qwen2.5-7B (BASELINE config 3's R1-Distill-Qwen-7B derives from the
    # MATH variant — see the distill preset below for its rope difference)
    "qwen2.5-7b": ModelConfig(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, rope_theta=1000000.0,
        attention_bias=True, max_position_embeddings=131072,
    ),
    # Qwen2.5-32B (BASELINE config 4: TP-sharded RLHF)
    "qwen2.5-32b": ModelConfig(
        vocab_size=152064, hidden_size=5120, intermediate_size=27648,
        num_layers=64, num_heads=40, num_kv_heads=8, rope_theta=1000000.0,
        attention_bias=True, max_position_embeddings=131072,
    ),
    # Llama-3.1-70B (BASELINE config 5: disaggregated multi-slice PPO)
    "llama3-70b": ModelConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, rope_theta=500000.0,
        rope_scaling=RopeScaling(factor=8.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0,
                                 original_max_position_embeddings=8192),
    ),
    # test-size MoE model (Qwen3-MoE architecture)
    "moe-tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, rope_theta=10000.0,
        max_position_embeddings=512, use_qk_norm=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=96,
    ),
    # Qwen3-30B-A3B (HF config: Qwen/Qwen3-30B-A3B — 128 experts, top-8)
    "qwen3-30b-a3b": ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, use_qk_norm=True,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
    ),
    # Mixtral-8x7B (HF config: mistralai/Mixtral-8x7B-v0.1 — 8 experts,
    # top-2; Mixtral routing == softmax-all→top-k→renorm, see hf_loader)
    "mixtral-8x7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1000000.0,
        rms_norm_eps=1e-5, max_position_embeddings=32768,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=14336,
    ),
}

# DeepSeek-R1-Distill presets (BASELINE config 3 runs long-CoT GRPO on
# R1-Distill-Qwen-7B). The 32B/Llama-8B distills reuse their base
# architectures verbatim; the 7B is based on Qwen2.5-MATH-7B (rope_theta
# 10000, unlike base Qwen2.5-7B's 1e6) with the released distill config
# raising max positions to 131072 for its ~32k-token CoT traces.
PRESETS["deepseek-r1-distill-qwen-7b"] = dataclasses.replace(
    PRESETS["qwen2.5-7b"], rope_theta=10000.0)
PRESETS["deepseek-r1-distill-qwen-32b"] = PRESETS["qwen2.5-32b"]
PRESETS["deepseek-r1-distill-llama-8b"] = PRESETS["llama3-8b"]




def cut_to_share(cfg: ModelConfig, kept_layers: tuple, chips: int,
                 vocabulary_shares: int | None = None) -> ModelConfig:
    """One chip's view of a deployment in which ``chips`` chips share each
    layer and the layers not in ``kept_layers`` (published indices) lie on
    further chips as pipeline stages: this chip's experts (the first
    ``num_experts / chips``; the router keeps its width) and its slice of
    the vocabulary (the first ``vocab_size / vocabulary_shares`` rows; as
    many slices as chips unless told apart). Attention and a shared expert
    are whole on every chip. ``first_k_dense_replace`` stays the published
    count: ``cache_spec.layer_plan`` reads it against published indices."""
    held = cfg.num_experts // chips
    return dataclasses.replace(
        cfg, num_layers=len(kept_layers), kept_layers=tuple(kept_layers),
        experts_held=(0, held) if cfg.num_experts else None,
        vocab_size=cfg.vocab_size // (vocabulary_shares or chips))


# Ling-3.0-flash (HF config: inclusionAI/Ling-3.0-flash, model_type
# bailing_hybrid): KDA layers with every sixth an MLA layer, two leading
# dense layers, 512 routed experts of width 768 behind a sigmoid router
# with 8 groups, one shared expert. The multi-token-prediction layer is
# not part of the decoder. ``num_kv_heads`` is the published key (32) and
# unused: no layer keeps a K/V pair.
PRESETS["ling-3.0-flash"] = ModelConfig(
    vocab_size=157184, hidden_size=2560, intermediate_size=6144,
    num_layers=42, num_heads=32, num_kv_heads=32, head_dim=128,
    rope_theta=6000000.0, rms_norm_eps=1e-6, max_position_embeddings=262144,
    num_experts=512, num_experts_per_tok=8, moe_intermediate_size=768,
    scoring_func="sigmoid", n_group=8, topk_group=4,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=768,
    layer_group_size=6, first_k_dense_replace=2, kv_lora_rank=512,
    mla_head_gate=True, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    short_conv_kernel_size=4, kda_lower_bound=-5.0,
)
# one chip of four that share each layer, the leading dense layer once and
# one whole period of six sparse layers (benchmark/configs/ling-3.0-flash.json)
PRESETS["ling-3.0-flash-share4"] = cut_to_share(
    PRESETS["ling-3.0-flash"], (0, 2, 3, 4, 5, 6, 7), 4)
# test-size model of the same family: 2 KDA layers and 1 MLA layer, the
# first dense, 16 experts in 4 groups of which 4 are held
PRESETS["hybrid-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=4, num_kv_heads=4, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-6, max_position_embeddings=512,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    scoring_func="sigmoid", n_group=4, topk_group=2,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=32,
    experts_held=(0, 4), layer_group_size=3, first_k_dense_replace=1,
    kv_lora_rank=32, mla_head_gate=True, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5.0,
)

# dots.vlm1.inst's language model (HF config: rednote-hilab/dots.vlm1.inst,
# model_type dots_vlm): DeepSeek-V3's decoder key for key. Latent attention
# in every layer at 128 heads with a query latent and YaRN, three leading
# dense layers, then 256 routed experts of width 2048 behind the
# ``noaux_tc`` router, one shared expert, an untied head. The
# multi-token-prediction layer and the vision tower are not part of the
# decoder. ``num_kv_heads`` and ``head_dim`` are unused: no layer keeps a
# K/V pair.
PRESETS["dots.vlm1"] = ModelConfig(
    vocab_size=129280, hidden_size=7168, intermediate_size=18432,
    num_layers=61, num_heads=128, num_kv_heads=128, rope_theta=10000.0,
    rope_scaling=RopeScaling(
        rope_type="yarn", factor=40.0, beta_fast=32.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=1.0,
        original_max_position_embeddings=4096),
    rms_norm_eps=1e-6, max_position_embeddings=163840,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
    scoring_func="sigmoid", n_group=8, topk_group=4,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=2048,
    first_k_dense_replace=3, kv_lora_rank=512, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
)
# one chip of sixteen that share each layer (the vocabulary in eight
# slices), the three dense layers once and four sparse layers
# (benchmark/configs/dots.vlm1.json)
PRESETS["dots.vlm1-share16"] = cut_to_share(
    PRESETS["dots.vlm1"], (0, 3, 4, 5, 6), 16, vocabulary_shares=8)
# test-size model of the same family: 3 MLA layers, the first dense, a
# query latent, YaRN, 16 experts in 4 groups of which 4 are held
PRESETS["mla-moe-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=4, num_kv_heads=4, rope_theta=10000.0,
    rope_scaling=RopeScaling(
        rope_type="yarn", factor=40.0, beta_fast=32.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=1.0,
        original_max_position_embeddings=64),
    rms_norm_eps=1e-6, max_position_embeddings=2560,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    scoring_func="sigmoid", n_group=4, topk_group=2,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=32,
    experts_held=(0, 4), first_k_dense_replace=1, kv_lora_rank=32,
    q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
)


# ZAYA1-8B (HF config: Zyphra/ZAYA1-8B, model_type zaya): every layer a
# compressed-convolutional-attention sublayer (8 query heads over 2 K/V
# heads of 128 in a latent of half the hidden size) and a top-1 routed MLP
# of 16 whole-width experts behind a router MLP whose 256-wide latent is
# carried from layer to layer; both sublayers' residuals scaled; a tied
# head over 262,272 rows. ``intermediate_size`` is unused: no dense MLP.
PRESETS["zaya1-8b"] = ModelConfig(
    vocab_size=262272, hidden_size=2048, intermediate_size=2048,
    num_layers=40, num_heads=8, num_kv_heads=2, head_dim=128,
    rope_theta=5000000.0, rms_norm_eps=1e-5, tie_word_embeddings=True,
    max_position_embeddings=131072, partial_rotary_factor=0.5,
    num_experts=16, num_experts_per_tok=1, moe_intermediate_size=2048,
    norm_topk_prob=False, cca_time0=2, cca_time1=2, router_hidden_size=256,
)
# published layers 0-11, every expert and the whole vocabulary: the first
# of the pipeline stages one chip holds (benchmark/configs/zaya1-8b.json)
PRESETS["zaya1-8b-depth12"] = dataclasses.replace(
    PRESETS["zaya1-8b"], num_layers=12, kept_layers=tuple(range(12)))
# test-size model of the same family: 3 layers, 4 query heads over 2 K/V
# heads of 16, 4 experts at top-1, a router latent of 8
PRESETS["cca-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=64, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-5, tie_word_embeddings=True,
    max_position_embeddings=512, partial_rotary_factor=0.5,
    num_experts=4, num_experts_per_tok=1, moe_intermediate_size=32,
    norm_topk_prob=False, cca_time0=2, cca_time1=2, router_hidden_size=8,
)


# Phi-4-mini-flash-reasoning (HF config: microsoft/Phi-4-mini-flash-
# reasoning, model_type phi4flash; ``hf_loader.phi4flash_config`` of the
# published keys gives this, tested): SambaY. 9 Mamba-1 layers (0, 2, ...,
# 16), 8 layers of attention over the last 512 keys (1, 3, ..., 15), one
# full-attention layer (17) whose K/V the 7 cross layers (19, ..., 31) read,
# 7 gated memory units (18, ..., 30) on layer 16's scan output;
# differential attention at 40 query heads over 20 K/V heads of 64, paired;
# no rope (``rope_theta`` unused); a tied head over 200,064 rows
PRESETS["phi-4-mini-flash-reasoning"] = ModelConfig(
    vocab_size=200064, hidden_size=2560, intermediate_size=10240,
    num_layers=32, num_heads=40, num_kv_heads=20, rms_norm_eps=1e-5,
    tie_word_embeddings=True, max_position_embeddings=262144,
    mb_per_layer=2, sliding_window=512,
)
# test-size model of the same family: 12 layers in the same pattern (3
# scans and 3 window layers of 8 keys, the scan that feeds 2 gated memory
# units, the K/V layer that 2 cross layers read), 8 query heads over 4
PRESETS["sambay-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=12,
    num_heads=8, num_kv_heads=4, rms_norm_eps=1e-5,
    tie_word_embeddings=True, max_position_embeddings=512,
    mb_per_layer=2, sliding_window=8, ssm_state_size=4,
)


# Laguna-XS.2 (HF config: poolside/Laguna-XS.2, model_type laguna;
# ``hf_loader.laguna_config`` of the published keys gives this, tested):
# 40 layers of rope'd softmax GQA over 8 K/V heads of 128, every fourth
# (0, 4, ..., 36) full attention at 48 query heads under YaRN on half of a
# head's columns, the rest attention over the last 512 keys at 64 query
# heads under a plain rope on all of them; a sigmoid gate a head; a dense
# MLP in layer 0, then 256 experts of width 512 behind a softmax router at
# top-8 whose renormalised weights are scaled by 2.5, beside one shared
# expert; an untied head over 100,352 rows. ``rope_theta`` and
# ``partial_rotary_factor`` are the full layers' (the published top-level
# keys) and unused: ``rope_parameters`` says each kind's.
_LAGUNA_ROPE = (
    ("full_attention", RopeParameters(
        500000.0, 0.5, RopeScaling(
            rope_type="yarn", factor=64.0, beta_fast=64.0, beta_slow=1.0,
            original_max_position_embeddings=4096,
            attention_factor=1.4158883083359672))),
    ("sliding_attention", RopeParameters(10000.0, 1.0)))
PRESETS["laguna-xs.2"] = ModelConfig(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_layers=40, num_heads=48, num_kv_heads=8, head_dim=128,
    rope_theta=500000.0, partial_rotary_factor=0.5, rms_norm_eps=1e-6,
    max_position_embeddings=262144,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=512,
    first_k_dense_replace=1, sliding_window=512, attn_head_gate=True,
    layer_types=("full_attention", *["sliding_attention"] * 3) * 10,
    num_heads_per_layer=(48, 64, 64, 64) * 10,
    rope_parameters=_LAGUNA_ROPE,
)
# one chip of eight that share each layer, the whole vocabulary: the
# leading dense layer and two whole periods of window, window, window,
# full (benchmark/configs/laguna-xs.2.json)
PRESETS["laguna-xs.2-share8"] = cut_to_share(
    PRESETS["laguna-xs.2"], tuple(range(9)), 8, vocabulary_shares=1)
# test-size model of the same family: 5 layers (full, three of a window
# of 8 keys, full), 6 and 8 query heads over 2 K/V heads of 16, the first
# MLP dense, 16 experts at top-4 of which 4 are held, a shared expert
PRESETS["mixed-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=5,
    num_heads=6, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
    partial_rotary_factor=0.5, rms_norm_eps=1e-6,
    max_position_embeddings=2048,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=32,
    experts_held=(0, 4), first_k_dense_replace=1, sliding_window=8,
    attn_head_gate=True,
    layer_types=("full_attention", *["sliding_attention"] * 3,
                 "full_attention"),
    num_heads_per_layer=(6, 8, 8, 8, 6),
    rope_parameters=(
        ("full_attention", RopeParameters(
            10000.0, 0.5, RopeScaling(
                rope_type="yarn", factor=16.0, beta_fast=64.0, beta_slow=1.0,
                original_max_position_embeddings=32,
                attention_factor=1.2772588722239782))),
        ("sliding_attention", RopeParameters(100.0, 1.0))),
)


# Ouro-2.6B (HF config: ByteDance/Ouro-2.6B, model_type ouro;
# ``hf_loader.ouro_config`` of the published keys gives this, tested): a
# looped language model. ONE stack of 48 layers of plain multi-head
# attention (16 heads of 128, a K/V head a query head, rope at 1e6 on all
# of a head's columns) and a dense SwiGLU MLP of 5,632, four RMSNorms a
# layer (before and after each sublayer), run ``total_ut_steps`` = 4 times a
# token with the final norm between passes; pass t's layer l attends the
# keys pass t's layer l kept, so a token keeps 4 x 48 K/V pairs; an exit
# gate a pass (``exit_gate``) that at the published threshold 1.0 enters
# no output; an untied head over 49,152 rows
PRESETS["ouro-2.6b"] = ModelConfig(
    vocab_size=49152, hidden_size=2048, intermediate_size=5632,
    num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
    rope_theta=1000000.0, rms_norm_eps=1e-6, max_position_embeddings=65536,
    ut_steps=4, sandwich_norm=True,
)
# test-size model of the same family: 3 layers run 3 times, 4 heads of 16
PRESETS["ouro-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=4, num_kv_heads=4, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-6, max_position_embeddings=2048,
    ut_steps=3, sandwich_norm=True,
)


# MiniCPM-SALA (HF config: openbmb/MiniCPM-SALA, model_type minicpm_sala;
# ``hf_loader.minicpm_sala_config`` of the published keys gives this,
# tested): 32 layers of hidden 4096 with a SwiGLU MLP of 16384, 8 of them
# ``minicpm4`` (InfLLM-V2 block-sparse softmax attention: 32 query heads
# over 2 K/V heads of 128, no rope, q/k norms, an output gate) among 24
# ``lightning-attn`` (linear attention: 32 heads of 128, rope, q/k norms, a
# constant decay a head, an output norm and gate); MiniCPM's muP scalings;
# an untied head over 73,448 rows. The sparse sizes are the family's
# (openbmb/MiniCPM4.1-8B's ``sparse_config``: no key of this model's
# catalog row names them).
# Published layers 9-20 run here (S L L L L L L S S L L L), every width and
# the whole vocabulary: the middle of three pipeline stages, which here
# holds the embedding and the head too (benchmark/configs/minicpm-sala.json)
PRESETS["minicpm-sala"] = ModelConfig(
    vocab_size=73448, hidden_size=4096, intermediate_size=16384,
    num_layers=12, num_heads=32, num_kv_heads=2, head_dim=128,
    rope_theta=10000.0, rms_norm_eps=1e-6, use_qk_norm=True,
    max_position_embeddings=524288,
    mixer_types=(
        "minicpm4", *["lightning-attn"] * 8, "minicpm4",
        *["lightning-attn"] * 6, "minicpm4", "minicpm4",
        *["lightning-attn"] * 4, "minicpm4", *["lightning-attn"] * 6,
        "minicpm4", "minicpm4", "minicpm4"),
    kept_layers=tuple(range(9, 21)),
    lightning_heads=32, lightning_head_dim=128,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=256,
)
# test-size model of the same family: published layers 1-6 of 8 (S L L S S
# L kept), 4 query heads over 2 K/V heads of 16, 4 lightning heads of 16,
# pooled keys of 8 tokens every 4, blocks of 8, the best 4 blocks of which
# the first and the last 2 are forced, every block up to 32 keys
PRESETS["minicpm-sala-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=6,
    num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-6, use_qk_norm=True, max_position_embeddings=2048,
    mixer_types=("lightning-attn", "minicpm4", "lightning-attn",
                 "lightning-attn", "minicpm4", "minicpm4", "lightning-attn",
                 "minicpm4"),
    kept_layers=tuple(range(1, 7)),
    sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=8,
    sparse_topk=4, sparse_init_blocks=1, sparse_window_size=16,
    sparse_dense_len=32, lightning_heads=4, lightning_head_dim=16,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=16,
)


# NVIDIA-Nemotron-3-Nano-30B-A3B (HF config: nvidia/NVIDIA-Nemotron-3-Nano-
# 30B-A3B-BF16, model_type nemotron_h; ``hf_loader.nemotron_h_config`` of
# the published keys gives this, tested): 52 layers of ONE sublayer each by
# the published pattern: 23 Mamba-2 mixers (64 heads of 64, 8 groups, state
# 128, 4 taps), 6 attention mixers (32 query heads over 2 K/V heads of 128,
# no positions) and 23 routed MLPs (128 experts of width 1856 at top-6
# behind DeepSeek-V3's sigmoid router, scaled 2.5, one shared expert of
# 3712; an expert is two matrices under relu(.)^2); hidden 2688, an untied
# head over 131,072 rows; 31,577,940,288 parameters
PRESETS["nemotron-3-nano-30b-a3b"] = ModelConfig(
    vocab_size=131072, hidden_size=2688, intermediate_size=1856,
    num_layers=52, num_heads=32, num_kv_heads=2, head_dim=128,
    rope_theta=10000.0, rms_norm_eps=1e-5, max_position_embeddings=262144,
    num_experts=128, num_experts_per_tok=6, moe_intermediate_size=1856,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=3712,
    hybrid_override_pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
    mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
    ssm_state_size=128, ssm_conv_kernel=4, ssd_chunk_size=128,
    attn_no_rope=True, mlp_hidden_act="relu2",
)
# one chip of eight that share EVERY layer: all 52 layers, experts 0-15 of
# each routed layer, rows 0-16383 of the vocabulary; mixers, shared experts
# and routers whole (benchmark/configs/nemotron-3-nano-30b-a3b.json)
PRESETS["nemotron-3-nano-30b-a3b-share8"] = cut_to_share(
    PRESETS["nemotron-3-nano-30b-a3b"], tuple(range(52)), 8)
# test-size model of the same family: M E * E M, 4 Mamba-2 heads of 16 in 2
# groups at state 16, 4 query heads over 2 K/V heads of 16 without
# positions, 8 experts of width 24 at top-2 of which 4 are held, a shared
# expert of 48
PRESETS["nemotron-h-tiny"] = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=24, num_layers=5,
    num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-5, max_position_embeddings=2048,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    routed_scaling_factor=2.5, moe_shared_expert_intermediate_size=48,
    experts_held=(0, 4), hybrid_override_pattern="ME*EM",
    mamba_num_heads=4, mamba_head_dim=16, mamba_n_groups=2,
    ssm_state_size=16, ssm_conv_kernel=4, ssd_chunk_size=8,
    attn_no_rope=True, mlp_hidden_act="relu2",
)


def get_config(name: str, **overrides) -> ModelConfig:
    return dataclasses.replace(PRESETS[name], **overrides)


# -- init -------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: ModelConfig) -> dict:
    """Initialise stacked-layer params. Normal(0.02) like the HF default."""
    if not cache_spec.is_uniform(cfg):
        return hybrid.init_params(rng, cfg)
    hd = cfg.head_dim_
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(rng, 8)
    std = 0.02

    def norm(key, *shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(cfg.dtype)

    if cfg.num_experts:
        fe = cfg.moe_intermediate_size
        mlp = {
            "router": norm(keys[5], L, d, cfg.num_experts),
            "we_gate": norm(keys[6], L, cfg.num_experts, d, fe),
            "we_up": norm(jax.random.fold_in(keys[6], 1), L,
                          cfg.num_experts, d, fe),
            "we_down": norm(keys[7], L, cfg.num_experts, fe, d),
        }
    else:
        mlp = {
            "w_gate": norm(keys[5], L, d, f),
            "w_up": norm(keys[6], L, d, f),
            "w_down": norm(keys[7], L, f, d),
        }
    params = {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "final_norm": jnp.ones((d,), dtype=cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype=cfg.dtype),
            "mlp_norm": jnp.ones((L, d), dtype=cfg.dtype),
            "wq": norm(keys[1], L, d, hq * hd),
            "wk": norm(keys[2], L, d, hkv * hd),
            "wv": norm(keys[3], L, d, hkv * hd),
            "wo": norm(keys[4], L, hq * hd, d),
            **mlp,
        },
    }
    if cfg.use_qk_norm:
        params["layers"]["q_norm"] = jnp.ones((L, hd), dtype=cfg.dtype)
        params["layers"]["k_norm"] = jnp.ones((L, hd), dtype=cfg.dtype)
    if cfg.attention_bias:
        params["layers"]["bq"] = jnp.zeros((L, hq * hd), dtype=cfg.dtype)
        params["layers"]["bk"] = jnp.zeros((L, hkv * hd), dtype=cfg.dtype)
        params["layers"]["bv"] = jnp.zeros((L, hkv * hd), dtype=cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(jax.random.fold_in(rng, 99), d, cfg.vocab_size)
    return params


def param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpec tree matching ``init_params`` (fsdp × tp sharding)."""
    if not cache_spec.is_uniform(cfg):
        return hybrid.param_specs(cfg)
    layer = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "wq": P(None, FSDP, TP),
        "wk": P(None, FSDP, TP),
        "wv": P(None, FSDP, TP),
        "wo": P(None, TP, FSDP),
    }
    if cfg.num_experts:
        # experts shard over ep (the REAL expert axis — beyond the
        # reference's stubbed EP config, SURVEY.md §2.3); within each
        # expert the FFN shards like the dense MLP (fsdp × tp). With the
        # mesh set (``parallel.mesh.under``) each ep rank computes its own
        # experts' rows (``_expert_mix_sharded``).
        layer.update({
            "router": P(None, FSDP, None),
            "we_gate": P(None, EP, FSDP, TP),
            "we_up": P(None, EP, FSDP, TP),
            "we_down": P(None, EP, TP, FSDP),
        })
    else:
        layer.update({
            "w_gate": P(None, FSDP, TP),
            "w_up": P(None, FSDP, TP),
            "w_down": P(None, TP, FSDP),
        })
    if cfg.use_qk_norm:
        layer["q_norm"] = P(None, None)
        layer["k_norm"] = P(None, None)
    if cfg.attention_bias:
        layer["bq"] = P(None, TP)
        layer["bk"] = P(None, TP)
        layer["bv"] = P(None, TP)
    specs = {
        "embed": P(TP, FSDP),
        "final_norm": P(None),
        "layers": layer,
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(FSDP, TP)
    return specs


# -- building blocks --------------------------------------------------------


def _rope_freqs(cfg: ModelConfig) -> np.ndarray:
    hd = cfg.head_dim_
    freqs = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    if cfg.rope_scaling:
        if cfg.rope_scaling.rope_type != "llama3":
            raise NotImplementedError(
                f"rope scaling {cfg.rope_scaling.rope_type!r} on a GQA "
                "layer (models/hybrid.py has YaRN for latent attention)")
        # llama3 NTK-by-parts frequency scaling (HF rope_scaling type="llama3")
        s = cfg.rope_scaling
        factor = s.factor
        low, high = s.low_freq_factor, s.high_freq_factor
        old_len = s.original_max_position_embeddings
        wavelen = 2 * np.pi / freqs
        ratio = old_len / wavelen
        smooth = np.clip((ratio - low) / (high - low), 0.0, 1.0)
        scaled = np.where(
            wavelen > old_len / low,  # low-frequency: fully scale
            freqs / factor,
            np.where(
                wavelen < old_len / high,  # high-frequency: keep
                freqs,
                (1 - smooth) * freqs / factor + smooth * freqs,
            ),
        )
        freqs = scaled
    return freqs.astype(np.float32)


def rope_cos_sin(cfg: ModelConfig, positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """positions [B, T] → (cos, sin) [B, T, hd/2] in f32."""
    freqs = jnp.asarray(_rope_freqs(cfg))
    angles = positions.astype(jnp.float32)[..., None] * freqs[None, None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [B, T, H, D]; rotate-half convention (HF Llama/Qwen)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, :, None, :].astype(jnp.float32)
    sin = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = x1f * cos - x2f * sin
    out2 = x2f * cos + x1f * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# -- MoE MLP ----------------------------------------------------------------

def _unrolled_layer(cfg: ModelConfig, layers: dict, l: int) -> dict:
    """Layer ``l``'s weights out of the stacked tree, for a loop unrolled
    over static layer indices. A slice of a stack fuses into the matmul
    that reads it; the experts' grouped matmul is a custom call and a
    slice would be copied first, so the expert stacks stay whole and the
    caller hands ``l`` on as ``_attn_out_mlp``'s ``layer``
    (``quant.moe_mm``)."""
    if not cfg.num_experts:
        return jax.tree_util.tree_map(lambda a: a[l], layers)
    return {k: v if k in EXPERT_KEYS
            else jax.tree_util.tree_map(lambda a: a[l], v)
            for k, v in layers.items()}


def _mlp_block(cfg: ModelConfig, h: jnp.ndarray, lp: dict,
               valid: jnp.ndarray | None = None, layer: int | None = None):
    """Post-norm MLP: dense SwiGLU, or the routed mixture when the config
    is MoE. ``h`` is [..., d]; MoE flattens leading dims into one token
    axis (routing is per-token, layout-independent). ``valid`` matches
    ``h``'s leading dims: padding and inactive tokens route nowhere.
    ``layer`` as ``_moe_mlp`` takes it. Returns (output, the MoE block's
    load or None)."""
    if cfg.num_experts:
        shape = h.shape
        v = valid.reshape(-1) if valid is not None else None
        out, load = _moe_mlp(cfg, h.reshape(-1, shape[-1]), lp, v, layer)
        return out.reshape(shape), load
    with jax.named_scope("mlp_dense"):
        gate = jax.nn.silu(
            mm(h, lp["w_gate"]).astype(jnp.float32)).astype(h.dtype)
        return mm(gate * mm(h, lp["w_up"]), lp["w_down"]), None


# -- forward ----------------------------------------------------------------


# Scopes of the forward pass (``jax.named_scope``: metadata only, the
# compiled program is the same with and without them; ``models/scopes.py``
# declares them all). They are the path by which a device trace tells one
# layer's parts apart, so every forward path names the same: ``embed``,
# ``attn_qkv`` (norm, q/k/v projections, rope and its angles),
# ``attn_core`` (the KV write and where it goes, the attention, and every
# gather, convert or reshape between them), ``attn_out``, ``mlp`` (a
# container: the dense products under ``mlp_dense`` or the routed block's
# ``moe_*``, its norm and residual under ``glue``), ``head`` (final norm
# and the output matmul). The engine adds ``sample``.


def _attn_qkv(cfg, x, lp, cos, sin, lead: tuple):
    """Pre-attention of one layer: norm, q/k/v projections (bias, qk-norm)
    and rope. ``x`` [..., d] -> q [*lead, Hq, D], k and v [*lead, Hkv, D]."""
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
        if cfg.attention_bias:  # Qwen2/2.5 family
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(*lead, hq, hd)
        k = k.reshape(*lead, hkv, hd)
        v = v.reshape(*lead, hkv, hd)
        if cfg.use_qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _attn_out_mlp(cfg, x, attn_out, lp, token_valid, layer=None):
    """Post-attention of one layer: output projection and the MLP block,
    each with its residual. ``attn_out`` [..., Hq·D] -> (x [..., d], the
    MoE block's load or None). ``layer``: ``lp`` is ``_unrolled_layer``'s,
    of that layer."""
    with jax.named_scope("attn_out"):
        x = x + mm(attn_out, lp["wo"])
    with jax.named_scope("mlp"):
        with jax.named_scope("glue"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        out, load = _mlp_block(cfg, h, lp, token_valid, layer)
        with jax.named_scope("glue"):
            return x + out, load


def samples_in_head(cfg, params, use_filters: bool, many_chips: bool) -> bool:
    """Whether a decode step can draw its token inside the output matmul
    (``head_and_sample``), from what its program is built on: no top-p or
    top-k row (those need the sorted logits), a plain array for a head (a
    ``QuantWeight`` or LoRA head keeps ``_head``), one chip (a head sharded
    over the vocabulary would need the running values combined across
    shards) and a TPU."""
    head = params["embed" if cfg.tie_word_embeddings else "lm_head"]
    return (not use_filters and not many_chips
            and not isinstance(head, (QuantWeight, LoraWeight))
            and jax.default_backend() == "tpu")


def head_and_sample(cfg, params, x, rng, temps):
    """Final norm, then output matmul and sampler as one kernel
    (``ops/fused_sample.py``) for decode rows ``x`` [S, d]: ``(token [S],
    logp [S])``, drawn and scored as ``sampling.sample_token_vec`` without
    filters, the [S, V] logits never written. A tied head is read as the
    embedding's [V, d] rows. The kernel runs interpreted off a TPU (tests
    alone get here then: see ``samples_in_head``)."""
    from polyrl_tpu.ops.fused_sample import head_sample_pallas

    with jax.named_scope("head"):
        x = head_input(cfg, norm(params, "final_norm", x, cfg.rms_norm_eps))
        tied = cfg.tie_word_embeddings
        head = params["embed" if tied else "lm_head"]
        if not tied and cfg.vocab_size % 128:
            # a head [d, V] whose V is no whole number of lane tiles lies
            # on the chip with d minor (the device's own layout for the
            # shape): read as the [V, d] rows a tied head is, it is taken
            # where it lies; as [d, V] the program copies all of it before
            # every dispatch's steps (0.6 GB at 73,448 rows of 4096, which
            # a chip filled to 14.9 GB has no room for)
            head, tied = head.T, True
        return head_sample_pallas(
            x, head, rng, temps, tied=tied,
            interpret=jax.default_backend() != "tpu")


def _layer_forward(cfg, x, lp, cos, sin, mask, layer_cache, attn_fn=None,
                   token_valid=None):
    """One decoder layer. layer_cache: None or (k_cache, v_cache) [B, S, Hkv, D]
    already containing past KV; this layer writes its new KV at write_idx.
    ``attn_fn``: optional sequence-parallel attention (Ulysses/ring,
    polyrl_tpu.parallel.sequence) used on the no-cache (training) path."""
    b, t, d = x.shape
    q, k, v = _attn_qkv(cfg, x, lp, cos, sin, (b, t))

    with jax.named_scope("attn_core"):
        if layer_cache is not None:
            k_cache, v_cache, write_idx = layer_cache
            k_full = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, write_idx, 0, 0))
            v_full = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, write_idx, 0, 0))
            attn_out = attention(q, k_full, v_full, mask=mask)
            new_cache = (k_full, v_full)
        elif attn_fn is not None:
            attn_out = attn_fn(q, k, v)  # SP impl applies causal+pad internally
            new_cache = None
        else:
            attn_out = attention(q, k, v, mask=mask)
            new_cache = None
        attn_out = attn_out.reshape(b, t, -1)

    x, _load = _attn_out_mlp(cfg, x, attn_out, lp, token_valid)
    return x, new_cache


def forward(
    params: dict,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,          # [B, T]
    positions: jnp.ndarray,          # [B, T] absolute positions (left-pad aware)
    attn_mask: jnp.ndarray,          # [B, Tk] 1=valid token (Tk = T, or cache len when cache given)
    cache: tuple | None = None,      # (k, v) each [L, B, S, Hkv, D]
    write_idx: int | jnp.ndarray = 0,
    remat: bool = False,
    attn_fn=None,                    # SP attention (parallel.sequence), no-cache path only
    logits_for: jnp.ndarray | None = None,  # [B] int32 — unembed only this position
    layers_fn=None,                  # pipeline-parallel layer stack (parallel.pipeline)
) -> tuple[jnp.ndarray, tuple | None]:
    """Returns (logits [B, T, V] float32 — or [B, V] when ``logits_for`` is
    given — and new_cache or None).

    Without cache: full-sequence causal forward (training / prefill-scoring).
    With cache: attends over the cache buffer [B, S]; the current chunk's KV
    is written at ``write_idx``; ``attn_mask`` must be [B, S] marking valid
    cache slots INCLUDING the chunk being written.
    """
    if not cache_spec.is_uniform(cfg):
        if cache is not None or attn_fn is not None or layers_fn is not None:
            raise NotImplementedError(
                "a model of several kinds of layer runs without a dense "
                "cache, sequence parallelism or a pipeline: CBEngine serves "
                "it (models/hybrid.py)")
        return hybrid.forward(params, cfg, input_ids, positions, attn_mask,
                              remat=remat, logits_for=logits_for), None
    b, t = input_ids.shape
    with jax.named_scope("embed"):
        x = params["embed"][input_ids]  # gather; sharded over tp on vocab dim

    with jax.named_scope("attn_qkv"):
        cos, sin = rope_cos_sin(cfg, positions)

    if cache is None:
        if attn_fn is not None or layers_fn is not None:
            # SP attention / the pipeline build causal+pad masks internally
            mask = None
        else:
            # causal within the chunk + padding mask
            with jax.named_scope("attn_core"):
                cm = causal_mask(t, t)  # [T, T]
                mask = (cm[None, None, :, :]
                        & (attn_mask[:, None, None, :] > 0))
    else:
        # left-padded layout: cache slot order == temporal order, so the
        # causal constraint is expressed in slot indices, not positions.
        with jax.named_scope("attn_core"):
            s = cache[0].shape[2]
            kv_pos = jnp.arange(s)[None, None, None, :]
            # slots at/below the chunk
            slot_written = kv_pos <= (write_idx + t - 1)
            causal = kv_pos <= (write_idx
                                + jnp.arange(t)[None, None, :, None])
            mask = causal & slot_written & (attn_mask[:, None, None, :] > 0)

    layers = params["layers"]

    if cache is None:
        if layers_fn is not None:
            # pipeline-parallel stack (parallel.pipeline): the pipeline owns
            # microbatching, masking, and remat for the layer loop
            x = layers_fn(layers, x, cos, sin, attn_mask)
            new_cache = None
        else:
            layer_attn = None
            if attn_fn is not None:
                layer_attn = lambda q, k, v: attn_fn(q, k, v, attn_mask)  # noqa: E731
            with jax.named_scope("glue"):
                tok_valid = attn_mask > 0  # [B, T]: MoE routing skips pads

            def body(x, lp):
                x, _ = _layer_forward(cfg, x, lp, cos, sin, mask, None,
                                      attn_fn=layer_attn,
                                      token_valid=tok_valid)
                return x, None
            if remat:
                body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, layers)
            new_cache = None
    else:
        # UNROLLED layer loop with single-token in-place cache writes.
        # A scan would force the cache through xs/ys (fresh stacked
        # allocations: full [L, B, S] rewrite per decode step) or through
        # the carry with dynamic layer indexing (full layer-slice copy per
        # layer). Static layer indices turn the write into a [B, T]-token
        # dynamic-update-slice and the read into a lazily-fused view —
        # decode becomes weights+KV-read bound, the HBM floor.
        k_cache, v_cache = cache
        n_layers = k_cache.shape[0]
        b = x.shape[0]
        t_chunk = x.shape[1]
        # chunk validity from the cache-slot mask (the chunk occupies slots
        # [write_idx, write_idx+t)): keeps MoE routing off padded tokens
        with jax.named_scope("glue"):
            chunk_valid = jax.lax.dynamic_slice_in_dim(
                attn_mask, write_idx, t_chunk, axis=1) > 0
        for l in range(n_layers):
            with jax.named_scope("glue"):
                lp = _unrolled_layer(cfg, layers, l)
            q, k, v = _attn_qkv(cfg, x, lp, cos, sin, (b, t_chunk))
            with jax.named_scope("attn_core"):
                k_cache = jax.lax.dynamic_update_slice(
                    k_cache, k[None].astype(k_cache.dtype), (l, 0, write_idx, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(
                    v_cache, v[None].astype(v_cache.dtype), (l, 0, write_idx, 0, 0))
                attn_out = attention(q, k_cache[l], v_cache[l], mask=mask)
                attn_out = attn_out.reshape(b, t_chunk, -1)
            x, _load = _attn_out_mlp(cfg, x, attn_out, lp, chunk_valid, l)
        new_cache = (k_cache, v_cache)

    return _head(cfg, params, x, logits_for), new_cache


# -- paged KV (continuous batching) -----------------------------------------


def make_paged_pools(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None, slots: int = 0) -> tuple:
    """Paged KV pool: (k, v), each a PER-LAYER tuple of
    [Hkv, num_pages, page_size, D] arrays. What a layer keeps comes from
    ``models/cache_spec.py``; for a model of several kinds of layer the
    pair is (latent pools, state rows of ``slots`` slots) instead
    (``cache_spec.make_pools``).

    Head-major layout: each layer's pool is exactly the
    [num_kv_heads, total_pages, page_size, head_dim] shape the TPU paged
    decode kernel streams (one (kv_head, page-block) DMA per grid step), so
    the hot loop never transposes the multi-GB pool. Per-layer arrays, not
    one stacked [L, ...]: the decode step's KV scatter prefers a physical
    layout the stacked form lets XLA actually pick — which then forces a
    full-pool copy per scan iteration to satisfy the attention kernel's
    standard-layout operand (observed: 2×3.5 GB temps, OOM at 128 slots).
    Separate 4-D buffers keep scatter and kernel in layout agreement.

    Page 0 is reserved as the null page — inactive slots and padding scatter
    their garbage KV there so every decode step has uniform static shapes
    (the TPU answer to SGLang's paged allocator, SURVEY.md §2.2 row 1)."""
    return cache_spec.make_pools(cfg, num_pages, page_size, slots, dtype)


def forward_paged_decode(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [S] int32 — one new token per slot
    positions: jnp.ndarray,   # [S] int32 — absolute position of that token
    pools: tuple,             # (k, v): per-layer tuples of [Hkv, N, page, D]
    page_table: jnp.ndarray,  # [S, P] int32
    seq_lens: jnp.ndarray,    # [S] int32 tokens already in cache (== positions)
    attn_fn=None,
    active: jnp.ndarray | None = None,  # [S] bool — mask KV writes
    kv_write_fn=None,  # TP override (ops.paged_attention.make_tp_paged_kv_write)
    head_fn=None,  # (cfg, params, x [S, d]) -> first result; default _head
) -> tuple[jnp.ndarray, tuple, jnp.ndarray | None]:
    """One decode step for every slot at once: write the new token's KV into
    each slot's current page, then paged-attend over [0, seq_len]. Returns
    (logits [S, V] f32, updated pools, the MoE blocks' load summed over the
    layers as ``_moe_mlp`` counts it, None for a dense model). Static shapes
    regardless of the mix of live requests — the continuous-batching hot
    loop. ``head_fn`` takes ``_head``'s place on the last layer's output and
    its result the logits' (the engine's step hands ``head_and_sample``).

    ``active`` routes INACTIVE slots' writes to the null page 0: a finished
    slot's pages return to the allocator while its device page_table row is
    still stale, so an unmasked write would corrupt whichever request
    reuses those pages (one garbage KV token per later dispatch).

    ``attn_fn(q, k_pool, v_pool, page_table, lens)`` is the decode
    attention seam: the TP engine shard_maps the Pallas kernel through it,
    and the shared-prefix grouped decode path (CBEngine with live GRPO
    groups) passes a closure over the dispatch's group tables that routes
    into ``ops.paged_attention.grouped_paged_attention`` — this forward
    stays group-agnostic; the per-slot ``page_table`` contract is
    unchanged (grouping only changes the kernel's HBM read pattern)."""
    from polyrl_tpu.ops.paged_attention import paged_attention, paged_kv_write

    if not cache_spec.is_uniform(cfg):
        if attn_fn is not None or kv_write_fn is not None:
            raise NotImplementedError(
                "grouped or mesh-sharded decode attention for a model of "
                "several kinds of layer")
        return hybrid.paged_decode(params, cfg, tokens, positions, pools,
                                   page_table, seq_lens, active, head_fn)
    attn_fn = attn_fn or paged_attention
    kv_write_fn = kv_write_fn or paged_kv_write
    s = tokens.shape[0]
    page_size = pools[0][0].shape[2]

    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # [S, d]
    with jax.named_scope("attn_qkv"):
        cos, sin = rope_cos_sin(cfg, positions[:, None])  # [S, 1, hd/2]
    with jax.named_scope("glue"):   # where the step's KV goes
        write_page = page_table[jnp.arange(s), seq_lens // page_size]  # [S]
        write_off = seq_lens % page_size
        attn_lens = seq_lens + 1  # include the token written this step
        if active is not None:
            write_page = jnp.where(active, write_page, 0)
            write_off = jnp.where(active, write_off, 0)
            # a row without a request attends nothing (the TPU kernel then
            # does no work for it); the engine discards its token and
            # log-prob
            attn_lens = jnp.where(active, attn_lens, 0)

    layers = params["layers"]

    # UNROLLED layer loop, static layer indices: pool writes are per-token
    # scatters and pool reads are the per-layer buffers directly. A scan
    # would copy entire pool layers per step (ys restacking or dynamic layer
    # slicing) — catastrophic when the pool IS the whole KV memory.
    k_pools, v_pools = list(pools[0]), list(pools[1])
    n_layers = len(k_pools)
    moe_load = None
    for l in range(n_layers):
        with jax.named_scope("glue"):   # slices that fuse into their readers
            lp = _unrolled_layer(cfg, layers, l)
        q, k, v = _attn_qkv(cfg, x, lp, cos, sin, (s, 1))
        with jax.named_scope("attn_core"):
            # fused K+V Pallas write on TPU (XLA row-scatter elsewhere):
            # the scatter lowers to a serialized per-row loop on TPU and
            # was the dominant cost of the whole decode step (2 x n_layers
            # x k fused steps of S*Hkv-row scatters per dispatch)
            k_pools[l], v_pools[l] = kv_write_fn(
                k_pools[l], v_pools[l], write_page, write_off, k[:, 0],
                v[:, 0])
            attn_out = attn_fn(q[:, 0], k_pools[l], v_pools[l], page_table,
                               attn_lens)  # [S, Hq, D]
            attn_out = attn_out.reshape(s, -1)
        # inactive slots route nowhere and count in no expert's load
        x, load = _attn_out_mlp(cfg, x, attn_out, lp, active, l)
        if load is not None:
            with jax.named_scope("glue"):
                moe_load = load if moe_load is None else moe_load + load
    return ((head_fn or _head)(cfg, params, x),
            (tuple(k_pools), tuple(v_pools)), moe_load)


def prefill_into_pages(
    params: dict,
    cfg: ModelConfig,
    ids: jnp.ndarray,         # [pb] int32 right-padded prompt
    prompt_len: jnp.ndarray,  # scalar int32
    pools: tuple,
    page_ids: jnp.ndarray,    # [pb // page_size] int32 (0-padded past prompt)
    slot: jnp.ndarray | None = None,  # the engine's slot (a recurrent state's row)
) -> tuple[tuple, jnp.ndarray]:
    """Prefill one prompt and scatter its KV into the slot's pages. Returns
    (updated pools, last-token logits [V] f32). Padding positions write into
    the null page / the tail of the last real page — never attended (masking
    is by seq_len everywhere)."""
    if not cache_spec.is_uniform(cfg):
        pools, logits = hybrid.prefill(
            params, cfg, ids[None], prompt_len[None], jnp.int32(0), pools,
            jnp.zeros((1, 0), jnp.int32), page_ids[None], slot[None])
        return pools, logits[0]
    page_size = pools[0][0].shape[2]
    pb = ids.shape[0]
    n_pg = pb // page_size
    layers = cfg.num_layers
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_

    mask = (jnp.arange(pb) < prompt_len).astype(jnp.float32)[None]
    positions = jnp.arange(pb, dtype=jnp.int32)[None]
    cache = make_cache(cfg, 1, pb, dtype=pools[0][0].dtype)
    last_logits, (k_new, v_new) = forward(
        params, cfg, ids[None], positions, mask, cache=cache, write_idx=0,
        logits_for=jnp.maximum(prompt_len - 1, 0)[None])

    with jax.named_scope("attn_core"):
        # [L, pb, hkv, hd] → per layer [hkv, n_pg, page, hd] (head-major pools)
        k_r = k_new[:, 0].reshape(layers, n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        v_r = v_new[:, 0].reshape(layers, n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        k_pools = tuple(_scatter_pages_kv(pools[0][l], page_ids, k_r[l])
                        for l in range(layers))
        v_pools = tuple(_scatter_pages_kv(pools[1][l], page_ids, v_r[l])
                        for l in range(layers))
    return (k_pools, v_pools), last_logits[0]


def prefill_batch_into_pages(
    params: dict,
    cfg: ModelConfig,
    ids: jnp.ndarray,          # [B, pb] int32 right-padded prompts
    prompt_lens: jnp.ndarray,  # [B] int32
    pools: tuple,
    page_ids: jnp.ndarray,     # [B, pb // page_size] int32
    slots: jnp.ndarray | None = None,  # [B] the engine's slots
) -> tuple[tuple, jnp.ndarray]:
    """Batched admission prefill: B prompts in ONE dispatch. Dispatch count
    is the admission bottleneck on dispatch-latency-bound links (and still
    wins on real hardware: one [B, pb] forward beats B serialized [pb]
    forwards). Returns (updated pools, last-token logits [B, V] f32).
    Duplicate page rows (wave padding repeats a real request) write the
    same content twice — benign."""
    if not cache_spec.is_uniform(cfg):
        return hybrid.prefill(
            params, cfg, ids, prompt_lens, jnp.int32(0), pools,
            jnp.zeros((ids.shape[0], 0), jnp.int32), page_ids, slots)
    page_size = pools[0][0].shape[2]
    b, pb = ids.shape
    n_pg = pb // page_size
    layers = cfg.num_layers
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_

    mask = (jnp.arange(pb)[None, :] < prompt_lens[:, None]).astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(pb, dtype=jnp.int32), (b, pb))
    cache = make_cache(cfg, b, pb, dtype=pools[0][0].dtype)
    last_logits, (k_new, v_new) = forward(
        params, cfg, ids, positions, mask, cache=cache, write_idx=0,
        logits_for=jnp.maximum(prompt_lens - 1, 0))

    with jax.named_scope("attn_core"):
        # [L, B, pb, hkv, hd] → per layer [hkv, B·n_pg, page, hd]
        k_r = k_new.reshape(layers, b * n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        v_r = v_new.reshape(layers, b * n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        flat_pages = page_ids.reshape(-1)
        k_pools = tuple(_scatter_pages_kv(pools[0][l], flat_pages, k_r[l])
                        for l in range(layers))
        v_pools = tuple(_scatter_pages_kv(pools[1][l], flat_pages, v_r[l])
                        for l in range(layers))
    return (k_pools, v_pools), last_logits


def prefill_suffix_into_pages(
    params: dict,
    cfg: ModelConfig,
    ids: jnp.ndarray,             # [pb] int32 right-padded suffix tokens
    suffix_len: jnp.ndarray,      # scalar int32 — real suffix tokens
    prefix_len: jnp.ndarray,      # scalar int32 — cached tokens (whole pages)
    pools: tuple,
    prefix_page_ids: jnp.ndarray, # [n_prefix_pg] int32 (0/null-padded tail)
    page_ids: jnp.ndarray,        # [pb // page_size] int32 suffix pages
    slot: jnp.ndarray | None = None,  # the engine's slot (a recurrent state's row)
) -> tuple[tuple, jnp.ndarray]:
    """Prefix-cache prefill: compute KV only for the suffix while attending
    over the cached prefix pages (the compute-skip that makes page-granular
    prefix reuse worthwhile — the TPU analogue of SGLang RadixAttention
    prefix hits, SURVEY.md §2.2 native-census row 1).

    The prefix occupies whole pages (``prefix_len`` ≤
    ``n_prefix_pg·page_size``, padded entries null); suffix KV is scattered
    into ``page_ids``. Returns (updated pools, last-token logits [V] f32).

    For a model with a recurrent state the prefix is what chunked prefill
    itself filled: the state row ``slot`` holds the state after it
    (``hybrid.prefill``; there is no prefix-cache hit for such a model).
    """
    if not cache_spec.is_uniform(cfg):
        pools, logits = hybrid.prefill(
            params, cfg, ids[None], suffix_len[None], prefix_len, pools,
            prefix_page_ids[None], page_ids[None], slot[None])
        return pools, logits[0]
    page_size = pools[0][0].shape[2]
    pb = ids.shape[0]
    n_pg = pb // page_size
    n_prefix_pg = prefix_page_ids.shape[0]
    prefix_cap = n_prefix_pg * page_size
    layers = cfg.num_layers
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_

    # dense scratch cache: [prefix_cap | suffix chunk]
    s_total = prefix_cap + pb
    cache = make_cache(cfg, 1, s_total, dtype=pools[0][0].dtype)
    with jax.named_scope("attn_core"):
        # per layer [hkv, n_pre, page, hd] → dense [L, prefix_cap, hkv, hd]
        k_pre = jnp.stack([pools[0][l][:, prefix_page_ids] for l in range(layers)])
        v_pre = jnp.stack([pools[1][l][:, prefix_page_ids] for l in range(layers)])
        k_pre = k_pre.transpose(0, 2, 3, 1, 4)
        v_pre = v_pre.transpose(0, 2, 3, 1, 4)
        cache = (
            cache[0].at[:, 0, :prefix_cap].set(
                k_pre.reshape(layers, prefix_cap, hkv, hd)),
            cache[1].at[:, 0, :prefix_cap].set(
                v_pre.reshape(layers, prefix_cap, hkv, hd)),
        )
    # slot layout: prefix occupies [0, prefix_len); the chunk writes at
    # write_idx=prefix_len so slot order stays temporal (padded prefix tail
    # slots get overwritten by the chunk — they were masked anyway)
    positions = (prefix_len + jnp.arange(pb, dtype=jnp.int32))[None]
    slot_idx = jnp.arange(s_total)
    valid = ((slot_idx < prefix_len)
             | ((slot_idx >= prefix_len) & (slot_idx < prefix_len + suffix_len)))
    last_logits, (k_all, v_all) = forward(
        params, cfg, ids[None], positions, valid[None].astype(jnp.float32),
        cache=cache, write_idx=prefix_len,
        logits_for=jnp.maximum(suffix_len - 1, 0)[None])

    with jax.named_scope("attn_core"):
        k_sfx = jax.lax.dynamic_slice_in_dim(k_all[:, 0], prefix_len, pb, axis=1)
        v_sfx = jax.lax.dynamic_slice_in_dim(v_all[:, 0], prefix_len, pb, axis=1)
        k_r = k_sfx.reshape(layers, n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        v_r = v_sfx.reshape(layers, n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        k_pools = tuple(_scatter_pages_kv(pools[0][l], page_ids, k_r[l])
                        for l in range(layers))
        v_pools = tuple(_scatter_pages_kv(pools[1][l], page_ids, v_r[l])
                        for l in range(layers))
    return (k_pools, v_pools), last_logits[0]


def prefill_suffix_batch_into_pages(
    params: dict,
    cfg: ModelConfig,
    ids: jnp.ndarray,             # [B, pb] int32 right-padded suffix tokens
    suffix_lens: jnp.ndarray,     # [B] int32 — real suffix tokens per row
    prefix_len: jnp.ndarray,      # scalar int32 — cached tokens, UNIFORM
    pools: tuple,
    prefix_page_ids: jnp.ndarray, # [B, n_prefix_pg] int32 (null-padded tail)
    page_ids: jnp.ndarray,        # [B, pb // page_size] int32 suffix pages
    slots: jnp.ndarray | None = None,  # [B] the engine's slots
) -> tuple[tuple, jnp.ndarray]:
    """Batched prefix-cache prefill: B suffixes in ONE dispatch, each
    attending over its own cached prefix pages — the group-shared-prefill
    sibling attach. GRPO's G-samples-per-prompt means the G−1 siblings of a
    published prompt arrive together with IDENTICAL prefix length; admitting
    them as G−1 serialized singleton suffix dispatches made the admission
    dispatch count linear in the rollout count (DualKV's exact target
    workload). Requires a UNIFORM ``prefix_len`` across rows (the scratch
    cache's write offset is one traced scalar); rows may differ in suffix
    content/length and prefix pages. Returns (updated pools, last-token
    logits [B, V] f32)."""
    if not cache_spec.is_uniform(cfg):
        return hybrid.prefill(params, cfg, ids, suffix_lens, prefix_len,
                              pools, prefix_page_ids, page_ids, slots)
    page_size = pools[0][0].shape[2]
    b, pb = ids.shape
    n_pg = pb // page_size
    n_prefix_pg = prefix_page_ids.shape[1]
    prefix_cap = n_prefix_pg * page_size
    layers = cfg.num_layers
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_

    # dense scratch cache per row: [prefix_cap | suffix chunk]
    s_total = prefix_cap + pb
    cache = make_cache(cfg, b, s_total, dtype=pools[0][0].dtype)
    with jax.named_scope("attn_core"):
        # per layer [hkv, B, n_pre, page, hd] → dense [L, B, prefix_cap, hkv, hd]
        k_pre = jnp.stack([pools[0][l][:, prefix_page_ids] for l in range(layers)])
        v_pre = jnp.stack([pools[1][l][:, prefix_page_ids] for l in range(layers)])
        k_pre = k_pre.transpose(0, 2, 3, 4, 1, 5)
        v_pre = v_pre.transpose(0, 2, 3, 4, 1, 5)
        cache = (
            cache[0].at[:, :, :prefix_cap].set(
                k_pre.reshape(layers, b, prefix_cap, hkv, hd)),
            cache[1].at[:, :, :prefix_cap].set(
                v_pre.reshape(layers, b, prefix_cap, hkv, hd)),
        )
    positions = jnp.broadcast_to(
        prefix_len + jnp.arange(pb, dtype=jnp.int32), (b, pb))
    slot_idx = jnp.arange(s_total)
    valid = ((slot_idx[None, :] < prefix_len)
             | ((slot_idx[None, :] >= prefix_len)
                & (slot_idx[None, :] < prefix_len + suffix_lens[:, None])))
    last_logits, (k_all, v_all) = forward(
        params, cfg, ids, positions, valid.astype(jnp.float32),
        cache=cache, write_idx=prefix_len,
        logits_for=jnp.maximum(suffix_lens - 1, 0))

    with jax.named_scope("attn_core"):
        k_sfx = jax.lax.dynamic_slice_in_dim(k_all, prefix_len, pb, axis=2)
        v_sfx = jax.lax.dynamic_slice_in_dim(v_all, prefix_len, pb, axis=2)
        # [L, B, pb, hkv, hd] → per layer [hkv, B·n_pg, page, hd]
        k_r = k_sfx.reshape(layers, b * n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        v_r = v_sfx.reshape(layers, b * n_pg, page_size, hkv, hd).transpose(0, 3, 1, 2, 4)
        flat_pages = page_ids.reshape(-1)
        k_pools = tuple(_scatter_pages_kv(pools[0][l], flat_pages, k_r[l])
                        for l in range(layers))
        v_pools = tuple(_scatter_pages_kv(pools[1][l], flat_pages, v_r[l])
                        for l in range(layers))
    return (k_pools, v_pools), last_logits


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> tuple:
    """Allocate a zeroed KV cache: (k, v) each [L, B, S, Hkv, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim_)
    return (jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype))


def cache_specs(cfg: ModelConfig) -> P:
    """KV cache sharding: batch over (dp, fsdp), heads over tp."""
    return P(None, (DP, FSDP), None, TP, None)
