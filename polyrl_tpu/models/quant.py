"""Int8 weight-only quantization for the serving path.

The reference serves quantized models by delegating to SGLang's
quantization support (SGLang ``--quantization`` flag; PolyRL itself adds
nothing — the capability lives in the external engine, SURVEY.md §2.2
native-census row 1). Here the engine is first-party, so quantization is
first-party too: symmetric per-output-channel int8 weights with an f32
scale, dequantized inside the matmul epilogue.

Why this design on TPU:
- Decode is weight-HBM-bound (the whole param set streams through the MXU
  once per token). int8 storage halves that traffic → up to ~2× decode
  throughput before any kernel work.
- The int8→bf16 cast + per-channel scale multiply fuse into the XLA matmul
  as a prologue/epilogue — no separate dequantized copy of the weights
  ever materializes in HBM.
- Integer values in [-127, 127] are exactly representable in bf16 (8-bit
  mantissa covers ±256), so the cast itself is lossless; the only error is
  the quantization rounding, bounded by scale/2 per weight.
- It makes the 8B north-star model (Llama-3.1-8B, 16.06 GiB bf16) fit a
  16 GiB-HBM chip: int8 matmul weights + bf16 embeddings ≈ 8.6 GiB.

``QuantWeight`` is a registered pytree node, so quantized param trees flow
through ``jax.jit``, ``tree_map`` layer slicing, ``lax.scan``, device_put
sharding trees, and the engine's atomic weight swap exactly like plain
arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.ops.grouped_matmul import expert_rows, grouped_matmul

# layer-stacked matmul weights that get quantized ([L, in, out]);
# embed stays bf16 (it is a gather, not a matmul), norms/biases are tiny
QUANTIZED_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# MoE expert weights ([L, E, in, out]) — the bulk of a MoE model's params;
# the tiny router ([L, d, E]) stays full precision (routing decisions are
# precision-sensitive and it is negligible HBM)
QUANTIZED_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantWeight:
    """int8 weight + per-output-channel f32 scale.

    ``q``: int8, same shape as the original weight ([in, out] or stacked
    [L, in, out]). ``scale``: f32 with the contraction (input) axis
    reduced away ([out] or [L, out]); ``w ≈ q * scale`` broadcast over the
    input axis.
    """

    q: Any
    scale: Any

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):  # duck-type for code that sizes buffers off weights
        return self.q.shape


def quantize_tensor(w, contract_axis: int = -2) -> QuantWeight:
    """Symmetric per-output-channel int8: scale_j = max_i |w_ij| / 127.

    Works on numpy or jax arrays (dispatches on input type so host-side
    quantization of a received weight push never touches the device).
    ``contract_axis`` is the input/contraction axis that the scale reduces
    over (default -2: weights are [..., in, out]).
    """
    if isinstance(w, np.ndarray):
        wf = w.astype(np.float32)
        amax = np.max(np.abs(wf), axis=contract_axis)
        scale = (amax / 127.0 + 1e-12).astype(np.float32)
        q = np.clip(np.rint(wf / np.expand_dims(scale, contract_axis)),
                    -127, 127).astype(np.int8)
        return QuantWeight(q=q, scale=scale)
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axis)
    scale = (amax / 127.0 + 1e-12).astype(jnp.float32)
    q = jnp.clip(jnp.round(wf / jnp.expand_dims(scale, contract_axis)),
                 -127, 127).astype(jnp.int8)
    return QuantWeight(q=q, scale=scale)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LoraWeight:
    """Low-rank adapter around a frozen base weight: ``w ≈ base +
    (alpha/r)·a@b`` (models/lora.py builds/merges these). ``base`` may
    itself be a QuantWeight — that composition IS QLoRA (int8 frozen base,
    trainable bf16 adapters). ``mm`` stops gradients at the base, so only
    a/b train; ``alpha`` rides the pytree aux data (static)."""

    base: Any
    a: Any  # [..., in, r]
    b: Any  # [..., r, out]
    alpha: float = 16.0

    def tree_flatten(self):
        return (self.base, self.a, self.b), self.alpha

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux)

    @property
    def shape(self):
        return self.base.shape


def mm(x, w):
    """``x @ w`` with transparent QuantWeight/LoraWeight dispatch
    (trace-time only — the isinstance checks cost nothing at runtime). The
    dequant epilogue runs in f32 and casts back to the activation dtype;
    XLA fuses it into the matmul."""
    if isinstance(w, LoraWeight):
        rank = w.a.shape[-1]
        base = jax.lax.stop_gradient(w.base)  # LoRA contract: base frozen
        delta = (x @ w.a.astype(x.dtype)) @ w.b.astype(x.dtype)
        return mm(x, base) + delta * (w.alpha / rank)
    if isinstance(w, QuantWeight):
        y = x @ w.q.astype(x.dtype)
        return (y.astype(jnp.float32) * w.scale).astype(x.dtype)
    return x @ w


def moe_mm(x, ws: tuple, lay, layer: int | None = None, act: str = ""):
    """Grouped matmul of the MoE expert projections, the analogue of
    ``mm``: ``x`` [M, in] holds each expert's rows in whole tiles
    (``ops.grouped_matmul.tiled_layout``: ``lay``), and every row is
    multiplied with its own expert's matrix of ``ws[0]`` [E, in, out]
    alone; with two weights, gate and up, the result is SwiGLU's
    ``silu(x @ gate) * (x @ up)``, with one under ``act`` ``relu2``
    ``relu(x @ up)^2`` (``ops.grouped_matmul.grouped_matmul``:
    a Pallas kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere). A
    QuantWeight's int8 experts are read as int8 and cast in the kernel
    (exact); the per-expert, per-output-channel ``scale`` [E, out]
    multiplies each row's product by its expert's.

    With ``layer``, each weight is the whole stack [L, E, in, out] and the
    experts are that layer's. The kernel is a custom call, whose operand
    XLA cannot slice in place: ``w[layer]`` would copy the layer's experts
    (1.2 GB at qwen3-30b-a3b's widths) every step. So the stack goes in
    whole, as L*E groups of which only this layer's have rows; a group
    without rows is not read."""
    ws, scales, lay = _layer_groups(ws, lay, layer)
    return grouped_matmul(x, ws, scales, lay, act)


def _layer_groups(ws: tuple, lay, layer: int | None):
    """``moe_mm``'s weights as the kernels take them: (the stacks, their
    scales or None, ``lay``), QuantWeights apart into both, and with
    ``layer`` the stacks [L, E, ..] as L*E groups, ``lay``'s tiles on that
    layer's (``lay``: a ``TiledLayout`` or a ``RowTables``)."""
    scales = None
    if isinstance(ws[0], QuantWeight):
        ws, scales = tuple(w.q for w in ws), tuple(w.scale for w in ws)
    if layer is not None:
        n_layers, e = ws[0].shape[:2]
        ws = tuple(w.reshape(-1, *w.shape[2:]) for w in ws)
        if scales is not None:
            scales = tuple(s.reshape(-1, s.shape[-1]) for s in scales)
        lay = lay._replace(
            tile_group=lay.tile_group + layer * e,
            padded_sizes=jnp.pad(lay.padded_sizes,
                                 (layer * e, (n_layers - 1 - layer) * e)))
    return ws, scales, lay


def expert_in(experts: dict) -> tuple:
    """The matrices an expert's hidden row is made from, and the
    activation that makes it (``ops.grouped_matmul._activate``): SwiGLU's
    gate and up, or ``we_up`` alone under ``relu2`` where the tree holds no
    gate (an expert of two matrices)."""
    if "we_gate" in experts:
        return (experts["we_gate"], experts["we_up"]), ""
    return (experts["we_up"],), "relu2"


def moe_rows(x, experts: dict, tab, weight, tile: int,
             layer: int | None = None):
    """The routed experts of the tokens ``x`` [N, d] at a decode step's
    shapes, [N, d] float32 (``ops.grouped_matmul.expert_rows``): SwiGLU of
    ``we_gate`` and ``we_up`` and ``we_down`` over the rows ``tab`` names
    (``row_tables``), each times its ``weight`` and summed into its token;
    QuantWeights and ``layer`` as ``moe_mm`` takes them."""
    ws_in, act = expert_in(experts)
    ws, scales, tab = _layer_groups((*ws_in, experts["we_down"]), tab, layer)
    return expert_rows(x, ws[:-1], ws[-1:], scales and scales[:-1],
                       scales and scales[-1:], tab, weight, tile, act)


def unembed(x, head, eq: str):
    """Logits head matmul (``jnp.einsum(eq, x, head)`` in f32) with
    QuantWeight dispatch; the per-vocab-channel scale multiplies the f32
    logits directly."""
    if isinstance(head, QuantWeight):
        logits = jnp.einsum(eq, x, head.q.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return logits * head.scale
    return jnp.einsum(eq, x, head, preferred_element_type=jnp.float32)


def quantize_params(params: dict) -> dict:
    """Quantize a decoder param tree's matmul weights: layer-stacked QKVO,
    dense MLP or MoE expert projections, and the untied lm_head;
    embed/norms/biases/router stay in model dtype. Accepts device (jax) or
    host (numpy) trees — each leaf quantizes with its own backend."""
    out = dict(params)
    layers = dict(params["layers"])
    for k in QUANTIZED_LAYER_KEYS + QUANTIZED_EXPERT_KEYS:
        if k in layers:  # dense vs MoE trees carry different MLP keys
            layers[k] = quantize_tensor(layers[k], contract_axis=-2)
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = quantize_tensor(params["lm_head"], contract_axis=0)
    return out


def quant_param_specs(specs: dict) -> dict:
    """PartitionSpec tree matching ``quantize_params`` output: ``q`` keeps
    the weight's spec; ``scale`` keeps the spec with the contraction axis
    dropped (per-output-channel ⇒ sharded like the output dim)."""
    from jax.sharding import PartitionSpec as P

    out = dict(specs)
    layer = dict(specs["layers"])
    for k in QUANTIZED_LAYER_KEYS:
        if k not in layer:  # dense MLP keys absent on MoE models
            continue
        s = layer[k]  # P(layer, in, out)
        layer[k] = QuantWeight(q=s, scale=P(s[0], s[2]))
    for k in QUANTIZED_EXPERT_KEYS:
        if k not in layer:
            continue
        s = layer[k]  # P(layer, expert, in, out)
        layer[k] = QuantWeight(q=s, scale=P(s[0], s[1], s[3]))
    out["layers"] = layer
    if "lm_head" in specs:
        s = specs["lm_head"]  # P(in, out)
        out["lm_head"] = QuantWeight(q=s, scale=P(s[1]))
    return out


def init_quantized_params(rng: jax.Array, cfg) -> dict:
    """Random-init a decoder param tree directly in quantized form, leaf by
    leaf ON DEVICE — the bf16 8B tree (16 GiB) never exists anywhere, so an
    8B-int8 model can be built on a 16 GiB chip (the random-init path; real serving
    quantizes loaded checkpoints instead). Peak transient = one bf16 leaf
    (≤3.8 GiB for llama3-8b w_gate) + its int8 copy. Mirrors the structure
    of ``decoder.init_params`` (dense models only)."""
    if getattr(cfg, "num_experts", 0):
        raise NotImplementedError(
            "init_quantized_params supports dense models only; load a MoE "
            "checkpoint with quantize='int8' or quantize_params a loaded "
            "tree (experts quantize per-output-channel like the dense MLP)")
    hd = cfg.head_dim_
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(rng, 8)
    std = 0.02

    def _plain(key, shape):
        @jax.jit
        def make(k):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * std
                    ).astype(cfg.dtype)
        return make(key)

    def _quant(key, *shape):
        @jax.jit
        def make(k):
            w = jax.random.normal(k, shape, dtype=jnp.float32) * std
            return quantize_tensor(w.astype(cfg.dtype), contract_axis=-2)
        qw = make(key)
        jax.block_until_ready(qw.q)
        return qw

    params = {
        "embed": _plain(keys[0], (cfg.vocab_size, d)),
        "final_norm": jnp.ones((d,), dtype=cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype=cfg.dtype),
            "mlp_norm": jnp.ones((L, d), dtype=cfg.dtype),
            "wq": _quant(keys[1], L, d, hq * hd),
            "wk": _quant(keys[2], L, d, hkv * hd),
            "wv": _quant(keys[3], L, d, hkv * hd),
            "wo": _quant(keys[4], L, hq * hd, d),
            "w_gate": _quant(keys[5], L, d, f),
            "w_up": _quant(keys[6], L, d, f),
            "w_down": _quant(keys[7], L, f, d),
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
        @jax.jit
        def make_head(k):  # lm_head quantizes over the hidden (in) axis
            w = jax.random.normal(k, (d, cfg.vocab_size),
                                  dtype=jnp.float32) * std
            return quantize_tensor(w.astype(cfg.dtype), contract_axis=0)
        params["lm_head"] = make_head(jax.random.fold_in(rng, 99))
    return params
