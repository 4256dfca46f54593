"""Least bytes of single kernels and scopes of the forward pass, beside
``costs.py``'s whole-step count and kept here for the same reason: a change
to the program cannot move a roofline share. Dense GQA decoders only.

No count for ``mlp`` or ``head`` yet: the ledger's breakdown puts them at
99-100% of the published bandwidth with ``costs.matmul_params``' bytes,
inside the 105% that refuses a run, so their count is settled first.
"""

from __future__ import annotations

from benchmark.lib import costs


def attn_core_decode_bytes(c: dict, kv_tokens_read: float,
                           dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the scope ``attn_core`` in one decode step:
    the keys and values of every context, once, in every layer. The new
    token's own KV write and the queries are a context's 1/length of it
    and are left out, so the share reads low by that, never high."""
    return kv_tokens_read * costs.kv_bytes_per_token(c, dtype_bytes)
