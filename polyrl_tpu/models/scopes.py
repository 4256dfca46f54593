"""THE declaration of the scopes a decode step's operations lie under
(``jax.named_scope``: metadata only, the compiled program is the same with
and without them). A device trace tells one part of a step from another by
the scope on an operation's path, so every operation that the step's own
code emits lies under exactly one LEAF scope, and a leaf may sit inside
the containers named here and inside nothing else that is declared.

The modules that open a scope keep doing so by its name (``with
jax.named_scope("attn_core")``); what is declared here is what
``tests/test_trace_names.py`` holds every family's lowered step to, what a
mixer's record (``mixers/base.Mixer``) may name as its ``pages_scope`` and
``slot_scope``, and what the benchmark's account of a traced window
(``benchmark/lib/account.py``) partitions a program's device time by. A
new mixer adds its scopes here.
"""

from __future__ import annotations

# what a leaf holds, in the order a step runs them
LEAF_SCOPES = (
    # the embedding rows of the step's tokens
    "embed",
    # softmax attention: the products before the core (norm where the
    # layer loop does not own it, q/k/v, rope, a gate's logits), the KV
    # write with the attention over pages (``swa_core``: over a window's
    # ring), the products after it
    "attn_qkv", "attn_core", "swa_core", "attn_out",
    # latent attention, KDA, CCA, the Mamba scans with their gated memory
    # units, differential heads' recombination: each family's products
    # (``*_proj``, ``cca_mix``) and its recurrence or attention
    "mla_proj", "mla_core", "kda_proj", "kda_core", "cca_proj", "cca_mix",
    "ssm_proj", "ssm_core", "gmu", "diff_mix",
    # block-sparse attention's choice of a row's blocks (the pooled key a
    # token completes, the scores against the pooled keys, the chosen
    # pages' table); linear attention's products and its state's step
    "sparse_select", "lightning_proj", "lightning_core",
    # Mamba-2 (SSD): the in-projection, convolution, gated grouped norm and
    # out-projection, and the state's step
    "ssd_proj", "ssd_core",
    # inside ``mlp``: the router, the routed experts, the shared expert,
    # and the gate, up and down products of a dense MLP
    "moe_route", "moe_experts", "moe_shared", "mlp_dense",
    # what stands between the sublayers and around the layers: the norms
    # and residuals that no mixer owns, the load a step counts, the
    # engine's tables of the fused step
    "glue",
    # the final norm with the output matmul (and the kernel that samples
    # in it), the sampler and the step's token bookkeeping
    "head", "sample",
    # a looped model's norm between two passes of its stack
    "ut_norm",
)

# a container -> the leaves that may sit in it (None: any but the step's
# own, which stand outside every layer)
STEP_ONLY = ("embed", "head", "sample", "ut_norm")
CONTAINER_SCOPES = {
    "mlp": ("moe_route", "moe_experts", "moe_shared", "mlp_dense", "glue"),
    "ut_pass": tuple(s for s in LEAF_SCOPES if s not in STEP_ONLY),
}


def declared(name: str) -> str:
    """``name``, which a record or a call site hands on as a scope: raises
    for one that is not a declared leaf."""
    if name not in LEAF_SCOPES:
        raise ValueError(f"scope {name!r} is not declared in "
                         f"models/scopes.py: {LEAF_SCOPES}")
    return name
