"""Plain reference forward of a GQA decoder whose MLP is a routed mixture
of experts (Qwen3-MoE family): ``dense_gqa.py``'s decoder, float32
``jax.numpy`` at the highest matmul precision, with each layer's MLP
replaced by the published sparse block (HF ``Qwen3MoeSparseMoeBlock``):

    p = softmax(h @ router)             over ALL experts, float32
    top-k of p, renormalised to sum 1   when ``norm_topk_prob``
    out = sum over the k chosen experts e of
          p_e * (silu(h @ gate_e) * (h @ up_e)) @ down_e

Computed the plain way: EVERY expert is applied to EVERY position, one
expert at a time (``lax.scan`` over the experts with a running sum, so
that [T, E, width] never exists at once), weighted by the position's
routing weight for that expert, which is zero unless the expert is among
its k. No sort, no capacity, no grouped matmul, no kernel: nothing of the
code under test.

The weights are the program's parameter tree, read as they are:
``router`` [L, d, E], ``we_gate`` and ``we_up`` [L, E, d, width],
``we_down`` [L, E, width, d]. The configuration file gives
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size`` and
``norm_topk_prob``; a tree of other shapes is an error. With
``num_experts`` absent or 0 the MLP is the dense SwiGLU (``w_gate``,
``w_up``, ``w_down``), as a CPU rehearsal's tiny dense model needs.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp


def _dense_gqa():
    """``dense_gqa.py`` beside this file, under the name the harness
    loads it by."""
    name = "benchmark_references_dense_gqa"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "dense_gqa.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def moe_mlp(h, lp, top_k: int, norm_topk: bool):
    """The published sparse block on ``h`` [T, d] float32. ``lp`` holds
    one layer's ``router`` [d, E] and stacked experts in any float type."""
    f32 = jnp.float32
    t = h.shape[0]
    probs = jax.nn.softmax(h @ lp["router"].astype(f32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(t)[:, None], top_i].set(top_p)            # [T, E]

    def one_expert(acc, ex):
        gate, up, down, w = ex
        y = (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) \
            @ down.astype(f32)
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["we_gate"], lp["we_up"], lp["we_down"], weight.T))
    return out


# ``score`` pads a sequence to a multiple of this many tokens, so that a
# cell which scores every request compiles one program a bucket and not
# one a length. The pad comes after the sequence and attention is causal:
# no scored position sees it.
_BUCKET = 256


@functools.partial(jax.jit, static_argnames=("sizes", "n_score", "all_logits"))
def _score(params, tokens, n_real, sizes, n_score, all_logits=False):
    """``tokens`` [T], of which the first ``n_real`` (traced) are the
    sequence."""
    (hq, hkv, hd, theta, eps, qk_norm, bias, tied, n_experts, top_k,
     norm_topk) = sizes
    ref = _dense_gqa()
    f32 = jnp.float32
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(f32)
    experts = ("we_gate", "we_up", "we_down")

    def layer(x, lp):
        # the experts stay as they are stored and are cast one at a time
        small = {k: v.astype(f32) for k, v in lp.items() if k not in experts}
        h = ref._rms(x, small["attn_norm"], eps)
        q, k, v = h @ small["wq"], h @ small["wk"], h @ small["wv"]
        if bias:
            q, k, v = q + small["bq"], k + small["bk"], v + small["bv"]
        q = q.reshape(t, hq, hd)
        k = k.reshape(t, hkv, hd)
        v = v.reshape(t, hkv, hd)
        if qk_norm:
            q = ref._rms(q, small["q_norm"], eps)
            k = ref._rms(k, small["k_norm"], eps)
        q, k = ref._rope(q, pos, theta), ref._rope(k, pos, theta)
        x = x + ref._attention(q, k, v).reshape(t, hq * hd) @ small["wo"]
        h = ref._rms(x, small["mlp_norm"], eps)
        if n_experts:
            x = x + moe_mlp(h, lp, top_k, norm_topk)
        else:
            x = x + (jax.nn.silu(h @ small["w_gate"]) * (h @ small["w_up"])) \
                @ small["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = ref._rms(x, params["final_norm"].astype(f32), eps)
    head = (params["embed"].T if tied else params["lm_head"]).astype(f32)
    if all_logits:
        return x @ head
    # position i predicts token i + 1
    pred = jax.lax.dynamic_slice_in_dim(x, n_real - n_score - 1, n_score, 0)
    logp = jax.nn.log_softmax(pred @ head, axis=-1)
    tgt = jax.lax.dynamic_slice_in_dim(tokens, n_real - n_score, n_score, 0)
    lp_tok = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return lp_tok, ent


def _sizes(params, c: dict) -> tuple:
    hd = int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])
    n_experts = int(c.get("num_experts") or 0)
    if n_experts:
        want = {"router": (c["hidden_size"], n_experts),
                "we_gate": (n_experts, c["hidden_size"],
                            c["moe_intermediate_size"]),
                "we_down": (n_experts, c["moe_intermediate_size"],
                            c["hidden_size"])}
        for key, shape in want.items():
            got = tuple(params["layers"][key].shape[1:])
            if got != tuple(int(s) for s in shape):
                raise ValueError(f"{key} is {got} a layer; the configuration "
                                 f"file says {shape}")
    return (int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
            hd, float(c["rope_theta"]), float(c["rms_norm_eps"]),
            bool(c.get("qk_norm", False)),
            bool(c.get("attention_bias", False)),
            bool(c.get("tie_word_embeddings", False)), n_experts,
            int(c.get("num_experts_per_tok") or 0),
            bool(c.get("norm_topk_prob", True)))


def score(params, c: dict, tokens, n_score: int):
    """(log-probabilities, entropies), each [n_score] float32 on the host,
    of the last ``n_score`` tokens of ``tokens``. ``c`` is the
    configuration's ``config`` dict (published key names)."""
    import numpy as np

    n = len(tokens)
    padded = np.zeros(-(-n // _BUCKET) * _BUCKET, np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        lp, ent = _score(params, jnp.asarray(padded), jnp.int32(n),
                         _sizes(params, c), int(n_score))
    return np.asarray(lp), np.asarray(ent)


def logits(params, c: dict, tokens):
    """Logits [T, V] float32 of every position of one sequence."""
    with jax.default_matmul_precision("highest"):
        return _score(params, jnp.asarray(tokens, jnp.int32), len(tokens),
                      _sizes(params, c), 0, all_logits=True)
