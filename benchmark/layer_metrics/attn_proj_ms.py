"""Device milliseconds a fused decode step spends on an attention layer's
products around its core, in every layer of a model of rope'd GQA layers
beside other kinds (``mixers/gqa.py``): the traced operations under the
scopes ``attn_qkv`` (the q | k | v product, the gate's logits, the rope)
and ``attn_out`` (the gate times the heads, ``W_o``) inside whole
``jit_step`` programs, over the steps those programs fuse. None where no
operation carries both scopes or the configuration lacks the family's
keys (a SambaY program opens the same scopes around differential heads:
its cell has ``diff_mix_ms``). Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_mixed, xspans


def read(obs):
    if not costs_mixed.is_mixed(obs["config"]["config"]):
        return None
    spans = xspans.load()
    found = [xspans.scope_seconds(spans, scope, "jit_step")
             for scope in ("attn_qkv", "attn_out")]
    if None in found:
        return None
    k = int(obs["mix"]["engine"]["steps_per_dispatch"])
    return sum(1e3 * seconds / (programs * k) for seconds, programs in found)
