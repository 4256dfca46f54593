"""FLOPs accounting + MFU (reference verl ``FlopsCounter``, consumed at
``stream_fsdp_workers.py:63`` and surfaced as ``perf/throughput_all_gpus``-
style metrics, stream_ray_trainer.py:656-663).

Per-token transformer FLOPs use the standard decomposition: ~6·P for the
dense path (fwd 2·P, bwd 4·P) plus the attention quadratic term
12·L·H·s per token at context length s (fwd+bwd; halve both for
inference-only). The peak a utilization is taken against comes from
``CHIP_PEAKS``, keyed by the device's ``device_kind``; a device that is not
in the table (the CPU backend included) has no peak, and no ``mfu`` key is
emitted for it.
"""

from __future__ import annotations

from typing import Any

# Published per-chip peaks by ``jax.Device.device_kind``: (bf16 TFLOP/s,
# HBM GB/s). Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v4, v5e, v5p, v6e).
CHIP_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),    # v5e
    "TPU v5e": (197.0, 819.0),
    "TPU v5": (459.0, 2765.0),        # v5p
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),   # v6e
    "TPU v6e": (918.0, 1640.0),
}


def peak_tflops(device_kind: str) -> float | None:
    """bf16 peak of one chip of this kind; None when the kind is unknown."""
    peaks = CHIP_PEAKS.get(device_kind)
    return peaks[0] if peaks else None


def param_count(cfg: Any) -> int:
    """Decoder parameter count from the ModelConfig (embed + L·(attn+mlp+
    norms) + final norm + head). MoE configs count router + ALL experts."""
    d, L = cfg.hidden_size, cfg.num_layers
    hd = cfg.head_dim_
    q = d * cfg.num_heads * hd
    kv = 2 * d * cfg.num_kv_heads * hd
    o = cfg.num_heads * hd * d
    if getattr(cfg, "num_experts", 0):
        mlp = (d * cfg.num_experts                       # router
               + cfg.num_experts * 3 * d * cfg.moe_intermediate_size)
    else:
        mlp = 3 * d * cfg.intermediate_size              # gate, up, down
    norms = 2 * d
    embed = cfg.vocab_size * d
    head = 0 if cfg.tie_word_embeddings else cfg.vocab_size * d
    return embed + L * (q + kv + o + mlp + norms) + d + head


def _active_matmul_params(cfg: Any) -> int:
    """Matmul params a TOKEN actually touches: for MoE only the top-k
    routed experts (+ router) do work, so MFU against total params would
    be wildly understated (e.g. Qwen3-30B-A3B activates ~3B of 30B)."""
    d, L = cfg.hidden_size, cfg.num_layers
    hd = cfg.head_dim_
    attn = (d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
            + cfg.num_heads * hd * d)
    if getattr(cfg, "num_experts", 0):
        mlp = (d * cfg.num_experts
               + cfg.num_experts_per_tok * 3 * d * cfg.moe_intermediate_size)
    else:
        mlp = 3 * d * cfg.intermediate_size
    head = 0 if cfg.tie_word_embeddings else cfg.vocab_size * d
    return L * (attn + mlp) + head


def flops_per_token(cfg: Any, context_len: int, *, training: bool = True,
                    include_embed: bool = False) -> float:
    """FLOPs for one token at the given mean context length (MoE: only the
    routed top-k experts compute)."""
    p = _active_matmul_params(cfg)
    if include_embed:
        p += cfg.vocab_size * cfg.hidden_size
        if cfg.tie_word_embeddings:
            p += cfg.vocab_size * cfg.hidden_size  # the tied head matmul
    elif cfg.tie_word_embeddings:
        p += cfg.vocab_size * cfg.hidden_size  # head matmul always runs
    dense = 2.0 * p
    attn = 4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim_ * context_len
    fwd = dense + attn
    return 3.0 * fwd if training else fwd     # bwd ≈ 2× fwd


class FlopsCounter:
    """Achieved TFLOP/s from token counts + wall time, and MFU where the
    chip's peak is known (``peak_tflops=None``: no ``mfu`` key)."""

    def __init__(self, model_cfg: Any, peak_tflops: float | None = None,
                 n_chips: int = 1):
        self.cfg = model_cfg
        self.peak_tflops = peak_tflops
        self.n_chips = max(n_chips, 1)
        self.params = param_count(model_cfg)

    def estimate_flops(self, n_tokens: int, mean_context_len: float,
                       *, training: bool = True) -> float:
        return n_tokens * flops_per_token(self.cfg, mean_context_len,
                                          training=training)

    def step_metrics(self, n_tokens: int, mean_context_len: float,
                     step_time_s: float, *, training: bool = True,
                     prefix: str = "perf") -> dict:
        if step_time_s <= 0 or n_tokens <= 0:
            return {}
        flops = self.estimate_flops(n_tokens, mean_context_len,
                                    training=training)
        achieved_tflops = flops / step_time_s / 1e12
        per_chip = achieved_tflops / self.n_chips
        out = {
            f"{prefix}/tflops_all_chips": achieved_tflops,
            f"{prefix}/tflops_per_chip": per_chip,
        }
        if self.peak_tflops:
            out[f"{prefix}/mfu"] = per_chip / self.peak_tflops
        return out
