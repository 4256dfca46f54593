"""Load HuggingFace checkpoints (safetensors) into the decoder's pytree.

The reference never loads weights itself — verl/SGLang consume HF
checkpoints directly (reference recipe `run_async_grpo_pipeline.sh:17`
points at Qwen/Qwen3-1.7B). A standalone framework needs its own loader:
this maps the HF llama/qwen parameter naming onto ``decoder.init_params``'s
STACKED-layer pytree, so `get_config(preset) + load_hf_params(ckpt_dir)`
drops pretrained weights straight into training and serving.

Mapping (HF name → pytree path):
- model.embed_tokens.weight            → embed
- model.norm.weight                    → final_norm
- lm_head.weight                       → lm_head (transposed [D, V]; absent
                                         when tie_word_embeddings)
- model.layers.{i}.input_layernorm     → layers.attn_norm[i]
- model.layers.{i}.post_attention_layernorm → layers.mlp_norm[i]
- model.layers.{i}.self_attn.{q,k,v,o}_proj → layers.w{q,k,v,o}[i]
  (transposed: HF Linear stores [out, in], the decoder matmuls x @ W)
- model.layers.{i}.mlp.{gate,up,down}_proj  → layers.w_{gate,up,down}[i]
- model.layers.{i}.self_attn.{q,k}_norm     → layers.{q,k}_norm[i] (Qwen3)
- model.layers.{i}.mlp.gate.weight          → layers.router[i] (Qwen3-MoE)
- model.layers.{i}.mlp.experts.{j}.{gate,up,down}_proj
                                            → layers.we_{gate,up,down}[i, j]

Per-layer tensors are stacked along a leading L axis to match the scan
layout. Loading streams one safetensors shard at a time (file mmap via
``safetensors.safe_open``), so peak host memory ≈ params + one shard.
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import cache_spec, decoder

_LAYER_MAP = {
    "input_layernorm.weight": "attn_norm",
    "post_attention_layernorm.weight": "mlp_norm",
    "self_attn.q_proj.weight": "wq",
    "self_attn.k_proj.weight": "wk",
    "self_attn.v_proj.weight": "wv",
    "self_attn.o_proj.weight": "wo",
    "mlp.gate_proj.weight": "w_gate",
    "mlp.up_proj.weight": "w_up",
    "mlp.down_proj.weight": "w_down",
    "self_attn.q_norm.weight": "q_norm",
    "self_attn.k_norm.weight": "k_norm",
    "self_attn.q_proj.bias": "bq",  # Qwen2/2.5 attention bias
    "self_attn.k_proj.bias": "bk",
    "self_attn.v_proj.bias": "bv",
}
_TRANSPOSED = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
# MoE expert tensors: Qwen3-MoE model.layers.{i}.mlp.experts.{j}.<proj>;
# Mixtral model.layers.{i}.block_sparse_moe.experts.{j}.{w1,w3,w2}
_EXPERT_MAP = {
    "gate_proj.weight": "we_gate",
    "up_proj.weight": "we_up",
    "down_proj.weight": "we_down",
    "w1.weight": "we_gate",
    "w3.weight": "we_up",
    "w2.weight": "we_down",
}


def _shard_files(ckpt_dir: str) -> list[str]:
    index = os.path.join(ckpt_dir, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        return sorted({os.path.join(ckpt_dir, v) for v in weight_map.values()})
    single = os.path.join(ckpt_dir, "model.safetensors")
    if os.path.exists(single):
        return [single]
    raise FileNotFoundError(f"no safetensors checkpoint under {ckpt_dir}")


def rope_scaling_from_hf(rs: dict | None) -> decoder.RopeScaling | None:
    """A config.json's ``rope_scaling`` entry as ``decoder.RopeScaling``:
    ``llama3`` and ``yarn`` (DeepSeek-V3's keys; a key the entry leaves
    out takes HF's default)."""
    rs = rs or {}
    rs_type = rs.get("rope_type", rs.get("type"))
    if rs_type == "llama3":
        return decoder.RopeScaling(
            factor=rs["factor"], low_freq_factor=rs["low_freq_factor"],
            high_freq_factor=rs["high_freq_factor"],
            original_max_position_embeddings=rs["original_max_position_embeddings"])
    if rs_type == "yarn":
        return decoder.RopeScaling(
            rope_type="yarn", factor=float(rs["factor"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            attention_factor=float(rs.get("attention_factor") or 0.0),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]))
    if rs_type not in (None, "default"):
        # silently running linear/dynamic checkpoints with UNSCALED
        # frequencies would be quietly wrong at long context
        raise NotImplementedError(
            f"rope_scaling type {rs_type!r} is not supported (llama3 and "
            "yarn only)")
    return None


def zaya_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from a ``zaya`` config.json (Zyphra/ZAYA1-8B):
    every key the published file has that the decoder reads. A sliding
    window (ZAYA1-74B's ``hybrid_sliding`` layers) is not supported. The
    checkpoint's tensors have no key map yet (``load_hf_params`` says
    so)."""
    if hf.get("sliding_window") or set(hf.get("layer_types", ())) - {"hybrid"}:
        raise NotImplementedError(
            "zaya layers with a sliding window (layer_types other than "
            "'hybrid'): only full CCA attention is written")
    rope = (hf.get("rope_parameters") or {}).get("hybrid") or {}
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["moe_intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        partial_rotary_factor=float(rope.get(
            "partial_rotary_factor", hf.get("partial_rotary_factor", 1.0))),
        rms_norm_eps=float(hf["rms_norm_eps"]),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        attention_bias=bool(hf.get("attention_bias", False)),
        max_position_embeddings=hf["max_position_embeddings"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=False, cca_time0=hf["cca_time0"],
        cca_time1=hf["cca_time1"],
        router_hidden_size=hf["router_hidden_size"], dtype=dtype)


def phi4flash_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from a ``phi4flash`` config.json (microsoft/
    Phi-4-mini-flash-reasoning): every key the published file has that the
    decoder reads. The Mamba sizes have no published key and stay the
    family's (``ModelConfig``'s defaults); a bias on the MLP or the head is
    not written. The checkpoint's tensors have no key map yet
    (``load_hf_params`` says so)."""
    if hf.get("mlp_bias") or hf.get("lm_head_bias"):
        raise NotImplementedError(
            "phi4flash with mlp_bias or lm_head_bias: neither is written")
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        rms_norm_eps=float(hf["layer_norm_eps"]),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        max_position_embeddings=hf["max_position_embeddings"],
        mb_per_layer=hf["mb_per_layer"],
        sliding_window=hf["sliding_window"], dtype=dtype)


def laguna_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from a ``laguna`` config.json (poolside/
    Laguna-XS.2): every key the published file has that the decoder reads.
    ``mlp_layer_types`` has to be dense layers and then sparse ones
    (``first_k_dense_replace`` is how many lead); the router is the
    softmax one with its chosen weights renormalised (no published key
    says otherwise). The checkpoint's tensors have no key map yet
    (``load_hf_params`` says so)."""
    n = hf["num_hidden_layers"]
    mlps = list(hf.get("mlp_layer_types") or ["sparse"] * n)
    dense = mlps.index("sparse") if "sparse" in mlps else n
    if mlps != ["dense"] * dense + ["sparse"] * (n - dense):
        raise NotImplementedError(
            "laguna with dense MLPs among the sparse ones: "
            f"mlp_layer_types {mlps}")
    if hf.get("attention_bias") or hf.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError(
            "laguna with attention_bias or moe_apply_router_weight_on_"
            "input: neither is written")
    ropes = tuple(
        (kind, decoder.RopeParameters(
            float(block["rope_theta"]),
            float(block.get("partial_rotary_factor", 1.0)),
            rope_scaling_from_hf(block)))
        for kind, block in hf["rope_parameters"].items()
        if isinstance(block, dict))
    full = dict(ropes)["full_attention"]
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_layers=n,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=full.rope_theta,
        partial_rotary_factor=float(hf.get("partial_rotary_factor",
                                           full.partial_rotary_factor)),
        rms_norm_eps=float(hf["rms_norm_eps"]),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position_embeddings=hf["max_position_embeddings"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        moe_shared_expert_intermediate_size=hf.get(
            "shared_expert_intermediate_size", 0),
        first_k_dense_replace=dense, sliding_window=hf["sliding_window"],
        attn_head_gate=bool(hf.get("gating")),
        layer_types=tuple(hf["layer_types"]),
        num_heads_per_layer=tuple(hf["num_attention_heads_per_layer"]),
        rope_parameters=ropes, dtype=dtype)


def ouro_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from an ``ouro`` config.json (ByteDance/Ouro-2.6B):
    every key the published file has that the decoder reads. The stack runs
    ``total_ut_steps`` times a token and the LAST pass is served, which is
    what ``early_exit_threshold`` 1.0 says; every layer is full attention
    (``layer_types``, ``sliding_window`` null); the four norms a layer have
    no published key and are the family's. The checkpoint's tensors have
    no key map yet (``load_hf_params`` says so)."""
    steps = int(hf["total_ut_steps"])
    if steps < 1:
        raise ValueError(f"total_ut_steps {steps}: a token runs the stack at "
                         "least once")
    if float(hf.get("early_exit_threshold", 1.0)) < 1.0:
        raise NotImplementedError(
            f"early_exit_threshold {hf['early_exit_threshold']} < 1: rows of "
            "one step that leave the loop at different passes are not built "
            "(every token is served by the last pass; ROADMAP.md Queue 2)")
    kinds = set(hf.get("layer_types") or ["full_attention"])
    if (kinds != {"full_attention"} or hf.get("use_sliding_window")
            or hf.get("sliding_window") or hf.get("rope_scaling")):
        raise NotImplementedError(
            "ouro with window layers or a scaled rope: neither is written "
            f"(layer_types {sorted(kinds)})")
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=float(hf["rope_theta"]),
        rms_norm_eps=float(hf["rms_norm_eps"]),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position_embeddings=hf["max_position_embeddings"],
        ut_steps=steps, sandwich_norm=True, dtype=dtype)


# the family's sparse sizes where a config.json has no ``sparse_config``
# (openbmb/MiniCPM4.1-8B's; benchmark/configs/minicpm-sala.json, assumed)
_SALA_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                "topk": 64, "init_blocks": 1, "window_size": 2048,
                "dense_len": 8192}


def minicpm_sala_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from a ``minicpm_sala`` config.json
    (openbmb/MiniCPM-SALA): every key the published file has that the
    decoder reads. ``mixer_types`` names each layer ``minicpm4`` or
    ``lightning-attn``; a file cut in depth (``published_mixer_types`` and
    ``published_layers_kept`` [first, last] beside a shorter
    ``mixer_types``: the benchmark's) gives the published list with the
    kept layers named. The checkpoint's tensors have no key map yet
    (``load_hf_params`` says so)."""
    kinds = tuple(hf["mixer_types"])
    if set(kinds) - set(cache_spec.MIXER_TYPES):
        raise NotImplementedError(f"mixer_types {sorted(set(kinds))}")
    if hf.get("attn_use_rope") or not hf.get("lightning_use_rope", True) \
            or not hf.get("qk_norm") or hf.get("attention_bias"):
        raise NotImplementedError(
            "minicpm_sala with rope in its softmax layers, without it in "
            "its lightning layers, without q/k norms or with projection "
            "biases: none is written")
    if not (hf.get("use_output_gate") and hf.get("use_output_norm")
            and hf.get("attn_use_output_gate")):
        raise NotImplementedError(
            "minicpm_sala without its output gates or output norm")
    if hf.get("lightning_nkv", hf["lightning_nh"]) != hf["lightning_nh"]:
        raise NotImplementedError("lightning layers with grouped K/V heads")
    kept = None
    if "published_mixer_types" in hf:
        first, last = hf["published_layers_kept"]
        kept = tuple(range(first, last + 1))
        whole = tuple(hf["published_mixer_types"])
        if (len(whole) != hf["published_num_hidden_layers"]
                or whole[first:last + 1] != kinds):
            raise ValueError("a depth cut that is no run of the published "
                             "mixer_types")
        kinds = whole
    if len(kept or kinds) != hf["num_hidden_layers"]:
        raise ValueError(f"{len(kept or kinds)} mixer_types for "
                         f"{hf['num_hidden_layers']} layers")
    sp = {**_SALA_SPARSE, **(hf.get("sparse_config") or {})}
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=float(hf["rope_theta"]),
        rms_norm_eps=float(hf["rms_norm_eps"]), use_qk_norm=True,
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position_embeddings=hf["max_position_embeddings"],
        mixer_types=kinds, kept_layers=kept,
        sparse_kernel_size=sp["kernel_size"],
        sparse_kernel_stride=sp["kernel_stride"],
        sparse_block_size=sp["block_size"], sparse_topk=sp["topk"],
        sparse_init_blocks=sp["init_blocks"],
        sparse_window_size=sp["window_size"],
        sparse_dense_len=sp["dense_len"],
        lightning_heads=hf["lightning_nh"],
        lightning_head_dim=hf["lightning_head_dim"],
        scale_emb=float(hf["scale_emb"]), scale_depth=float(hf["scale_depth"]),
        dim_model_base=int(hf["dim_model_base"]), dtype=dtype)


def nemotron_h_config(hf: dict, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """A ``ModelConfig`` from a ``nemotron_h`` config.json
    (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): every key the published
    file has that the decoder reads. ``hybrid_override_pattern`` names each
    layer's ONE sublayer (``cache_spec.PATTERN_KINDS``); a file that states
    one chip's share (``experts_held`` [first, count] and ``published``
    beside ``n_routed_experts`` and ``vocab_size``: the benchmark's) gives
    the router its published width and the chip its experts. What the
    family does that is not written here is refused by name. The
    checkpoint's tensors have no key map yet (``load_hf_params`` says
    so)."""
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(f"a pattern of {len(pattern)} layers for "
                         f"{hf['num_hidden_layers']}")
    unknown = sorted(set(pattern) - set(cache_spec.PATTERN_KINDS))
    if unknown:
        raise NotImplementedError(
            f"hybrid_override_pattern characters {unknown}")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias"):
        if hf.get(key):
            raise NotImplementedError(f"nemotron_h with {key}")
    if hf.get("mlp_hidden_act") != "relu2" \
            or hf.get("mamba_hidden_act", "silu") != "silu" \
            or not hf.get("use_conv_bias", True):
        raise NotImplementedError(
            "nemotron_h with another MLP activation than relu2, another "
            "Mamba activation than silu or no convolution bias")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1 \
            or hf.get("n_shared_experts", 1) != 1:
        raise NotImplementedError(
            "nemotron_h with a group-limited router or several shared "
            "experts")
    if hf.get("time_step_limit") or hf.get("sliding_window"):
        raise NotImplementedError(
            "nemotron_h with a clamp on dt or a sliding window")
    if hf["mamba_num_heads"] % hf["n_groups"]:
        raise ValueError("Mamba-2 heads that are no whole groups")
    published = hf.get("published") or {}
    held = hf.get("experts_held")
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=float(hf["rope_theta"]),
        rms_norm_eps=float(hf["layer_norm_epsilon"]),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position_embeddings=hf["max_position_embeddings"],
        num_experts=published.get("n_routed_experts", hf["n_routed_experts"]),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        scoring_func="sigmoid", n_group=1, topk_group=1,
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        moe_shared_expert_intermediate_size=hf[
            "moe_shared_expert_intermediate_size"],
        experts_held=tuple(held) if held else None,
        kept_layers=(tuple(range(len(pattern))) if held else None),
        hybrid_override_pattern=pattern,
        mamba_num_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"], mamba_n_groups=hf["n_groups"],
        ssm_state_size=hf["ssm_state_size"],
        ssm_conv_kernel=hf["conv_kernel"], ssd_chunk_size=hf["chunk_size"],
        attn_no_rope=True, mlp_hidden_act="relu2", dtype=dtype)


def config_from_hf(ckpt_dir: str, dtype=jnp.bfloat16) -> decoder.ModelConfig:
    """Build a ModelConfig from the checkpoint's config.json (llama/qwen2/
    qwen3 architectures; ``zaya``: ``zaya_config``; ``phi4flash``:
    ``phi4flash_config``; ``laguna``: ``laguna_config``; ``ouro``:
    ``ouro_config``; ``minicpm_sala``: ``minicpm_sala_config``;
    ``nemotron_h``: ``nemotron_h_config``)."""
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hf = json.load(f)
    if hf.get("model_type") == "ouro":
        return ouro_config(hf, dtype)
    if hf.get("model_type") == "minicpm_sala":
        return minicpm_sala_config(hf, dtype)
    if hf.get("model_type") == "nemotron_h":
        return nemotron_h_config(hf, dtype)
    if hf.get("model_type") == "laguna":
        return laguna_config(hf, dtype)
    if hf.get("model_type") == "zaya":
        return zaya_config(hf, dtype)
    if hf.get("model_type") == "phi4flash":
        return phi4flash_config(hf, dtype)
    rope_scaling = rope_scaling_from_hf(hf.get("rope_scaling"))
    moe: dict = {}
    if hf.get("num_experts"):  # Qwen3-MoE family
        if hf.get("mlp_only_layers") or (hf.get("decoder_sparse_step", 1) != 1):
            raise NotImplementedError(
                "mixed dense/MoE layer stacks are not supported (uniform "
                "MoE keeps the scan-over-layers body a single trace)")
        moe = dict(
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf.get("num_experts_per_tok", 8),
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )
    elif hf.get("num_local_experts"):  # Mixtral family
        # Mixtral routes softmax(top_k(logits)) — numerically identical to
        # softmax-all → top-k → renormalize (top-k is monotone under
        # softmax and restricting a softmax IS the renormalization), i.e.
        # norm_topk_prob=True; experts use the dense intermediate size
        moe = dict(
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            moe_intermediate_size=hf["intermediate_size"],
            norm_topk_prob=True,
        )
    return decoder.ModelConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        **moe,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim"),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        use_qk_norm="qwen3" in hf.get("model_type", ""),
        attention_bias=bool(hf.get("attention_bias",
                                   hf.get("model_type") == "qwen2")),
        max_position_embeddings=hf.get("max_position_embeddings", 131072),
        dtype=dtype,
    )


def load_hf_params(ckpt_dir: str, cfg: decoder.ModelConfig | None = None,
                   dtype=None, quantize: str = "",
                   to_device: bool = True) -> dict:
    """Load a safetensors checkpoint into the decoder pytree. ``cfg``
    defaults to ``config_from_hf(ckpt_dir)``; ``dtype`` defaults to
    ``cfg.dtype``.

    ``quantize="int8"``: matmul weights are quantized ON HOST (numpy) and
    only the int8 tensors + scales are transferred — the full-precision
    tree never exists on device, so an 8B checkpoint loads onto a 16 GiB
    chip (models/quant.py).

    ``to_device=False`` keeps every leaf host-side (numpy): callers that
    shard over a mesh device_put leaf-by-leaf straight into the sharded
    layout, so the unsharded tree never stages through one chip's HBM."""
    from safetensors import safe_open

    from polyrl_tpu.models.quant import (
        QUANTIZED_LAYER_KEYS, QuantWeight, quantize_tensor,
    )

    if quantize not in ("", "int8"):
        raise ValueError(f"unknown quantize mode {quantize!r}")
    cfg = cfg or config_from_hf(ckpt_dir)
    if cfg.cca_time0:
        # the published checkpoint's tensor names have not been seen from
        # here: a key map written without them would be a guess
        raise NotImplementedError(
            "no key map for a zaya (CCA) checkpoint yet: write it from the "
            "published model.safetensors.index.json (ROADMAP.md Queue 2)")
    if cfg.mb_per_layer:
        raise NotImplementedError(
            "no key map for a phi4flash (SambaY) checkpoint yet: write it "
            "from the published model.safetensors.index.json (ROADMAP.md "
            "Queue 2)")
    if cfg.layer_types:
        raise NotImplementedError(
            "no key map for a laguna checkpoint yet: write it from the "
            "published model.safetensors.index.json (ROADMAP.md Queue 2)")
    if cfg.ut_steps > 1 or cfg.sandwich_norm:
        raise NotImplementedError(
            "no key map for an ouro (looped) checkpoint yet: write it from "
            "the published model.safetensors.index.json (ROADMAP.md Queue 2)")
    if cfg.mixer_types:
        raise NotImplementedError(
            "no key map for a minicpm_sala checkpoint yet: write it from "
            "the published model.safetensors.index.json (ROADMAP.md Queue 2)")
    if cfg.hybrid_override_pattern:
        raise NotImplementedError(
            "no key map for a nemotron_h checkpoint yet: write it from the "
            "published model.safetensors.index.json (ROADMAP.md Queue 2)")
    dtype = dtype or cfg.dtype
    np_dtype = jnp.dtype(dtype)

    def _dev(x, dt=None):
        if to_device:
            return jnp.asarray(x, dt) if dt is not None else jnp.asarray(x)
        x = np.asarray(x)
        if dt is not None:
            x = x.astype(jnp.dtype(dt))  # ml_dtypes covers bf16 numpy
        return np.ascontiguousarray(x)
    L = cfg.num_layers

    E = cfg.num_experts
    flat: dict[str, np.ndarray] = {}
    layer_parts: dict[str, list] = {}
    expert_parts: dict[str, list] = {}  # key → [L][E] grid
    for path in _shard_files(ckpt_dir):
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                t = f.get_tensor(name)
                if name == "model.embed_tokens.weight":
                    flat["embed"] = t
                elif name == "model.norm.weight":
                    flat["final_norm"] = t
                elif name == "lm_head.weight":
                    flat["lm_head"] = t.T  # [V, D] → [D, V]
                elif name.startswith("model.layers."):
                    rest = name.split(".", 2)[2]          # "{i}.suffix"
                    idx_s, suffix = rest.split(".", 1)
                    if suffix in ("mlp.gate.weight",
                                  "block_sparse_moe.gate.weight"):  # router
                        layer_parts.setdefault("router", [None] * L)[
                            int(idx_s)] = t.T             # [E, D] → [D, E]
                    elif (suffix.startswith("mlp.experts.")
                          or suffix.startswith("block_sparse_moe.experts.")):
                        j_s, proj = suffix.split(".", 3)[2:]
                        key = _EXPERT_MAP.get(proj)
                        if key is None:
                            raise KeyError(f"unmapped HF expert tensor {name}")
                        grid = expert_parts.setdefault(
                            key, [[None] * E for _ in range(L)])
                        grid[int(idx_s)][int(j_s)] = t.T  # [out,in] → [in,out]
                    else:
                        key = _LAYER_MAP.get(suffix)
                        if key is None:
                            raise KeyError(f"unmapped HF layer tensor {name}")
                        if key in _TRANSPOSED:
                            t = t.T                        # [out,in] → [in,out]
                        layer_parts.setdefault(key, [None] * L)[int(idx_s)] = t
                else:
                    raise KeyError(f"unmapped HF tensor {name}")

    layers = {}
    for key in list(layer_parts):
        parts = layer_parts.pop(key)  # free numpy refs as we convert
        missing = [i for i, p in enumerate(parts) if p is None]
        if missing:
            raise ValueError(f"layer tensors missing for {key}: {missing}")
        stacked = np.stack(parts)
        if quantize == "int8" and key in QUANTIZED_LAYER_KEYS:
            qw = quantize_tensor(stacked, contract_axis=-2)  # host-side
            layers[key] = QuantWeight(q=_dev(qw.q), scale=_dev(qw.scale))
        else:
            layers[key] = _dev(stacked, np_dtype)
    for key in list(expert_parts):
        grid = expert_parts.pop(key)  # [L][E] → [L, E, in, out]
        missing = [(i, j) for i in range(L) for j in range(E)
                   if grid[i][j] is None]
        if missing:
            raise ValueError(f"expert tensors missing for {key}: "
                             f"{missing[:8]}")
        if quantize == "int8":  # experts are the bulk of MoE params
            # quantize PER LAYER before stacking: the f32 transient inside
            # quantize_tensor stays one layer's experts, not the whole
            # [L, E, in, out] stack (which would be ~2× checkpoint size on
            # exactly the large MoE models int8 targets)
            qs, ss = [], []
            for row in grid:
                qw = quantize_tensor(np.stack(row), contract_axis=-2)
                qs.append(qw.q)
                ss.append(qw.scale)
            layers[key] = QuantWeight(q=_dev(np.stack(qs)),
                                      scale=_dev(np.stack(ss)))
        else:
            layers[key] = _dev(
                np.stack([np.stack(row) for row in grid]), np_dtype)

    params = {
        "embed": _dev(flat["embed"], np_dtype),
        "final_norm": _dev(flat["final_norm"], np_dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        if "lm_head" not in flat:
            raise ValueError("checkpoint has no lm_head but config does not "
                             "tie word embeddings")
        if quantize == "int8":
            qw = quantize_tensor(np.ascontiguousarray(flat["lm_head"]),
                                 contract_axis=0)
            params["lm_head"] = QuantWeight(q=_dev(qw.q),
                                            scale=_dev(qw.scale))
        else:
            params["lm_head"] = _dev(flat["lm_head"], np_dtype)
    # structural + shape validation against the config: catches both
    # preset/checkpoint mixups and structurally mismatched checkpoints (a
    # missing q_norm would otherwise surface as an opaque KeyError in jit;
    # an extra bias tensor would be silently ignored at forward time)
    import jax

    if quantize == "int8":
        from polyrl_tpu.models.quant import quantize_params

        shapes = jax.eval_shape(
            lambda: quantize_params(
                decoder.init_params(jax.random.PRNGKey(0), cfg)))
    else:
        shapes = jax.eval_shape(
            lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    got = {jax.tree_util.keystr(p): tuple(l.shape)
           for p, l in jax.tree_util.tree_leaves_with_path(params)}
    want = {jax.tree_util.keystr(p): tuple(l.shape)
            for p, l in jax.tree_util.tree_leaves_with_path(shapes)}
    if set(got) != set(want):
        raise ValueError(
            f"checkpoint structure != config: missing {sorted(set(want) - set(got))},"
            f" unexpected {sorted(set(got) - set(want))}")
    for k in got:
        if got[k] != want[k]:
            raise ValueError(
                f"{k}: checkpoint shape {got[k]} != config shape {want[k]}")
    return params


def build_from_hf(ckpt_dir: str, dtype=jnp.bfloat16,
                  overrides: dict | None = None, quantize: str = ""):
    """One-stop: (ModelConfig, params) from a local HF checkpoint dir —
    the shared recipe for the train and serve entry points."""
    import dataclasses

    cfg = config_from_hf(ckpt_dir, dtype=dtype)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg, load_hf_params(ckpt_dir, cfg, quantize=quantize)
