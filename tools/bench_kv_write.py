"""Microbench: paged K/V token write — Pallas DMA kernel vs XLA scatter.

The write runs 2 (K+V) x n_layers x steps_per_dispatch times per decode
dispatch, so its per-call cost directly moves the CB serving number
(ops/paged_attention.paged_kv_write). Run EXCLUSIVELY on the TPU chip:

    python tools/bench_kv_write.py                 # flagship-like geometry
    POLYRL_KVW_SLOTS=129 POLYRL_KVW_REPEAT=200 python tools/bench_kv_write.py

Prints one JSON line per impl with per-call microseconds, plus the
projected per-dispatch cost at the bench's geometry (28 layers x 8 fused
steps) so wins are attributable before re-running the full bench.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models.decoder import _scatter_token_kv
    from polyrl_tpu.ops.paged_attention import paged_kv_write_pallas

    slots = int(os.environ.get("POLYRL_KVW_SLOTS", "65"))   # S+1 w/ sink
    hkv = int(os.environ.get("POLYRL_KVW_HKV", "8"))
    d = int(os.environ.get("POLYRL_KVW_D", "128"))
    page = int(os.environ.get("POLYRL_KVW_PAGE", "64"))
    n_pages = int(os.environ.get("POLYRL_KVW_NPAGES", "512"))
    repeat = int(os.environ.get("POLYRL_KVW_REPEAT", "100"))
    layers = int(os.environ.get("POLYRL_KVW_LAYERS", "28"))
    k_steps = int(os.environ.get("POLYRL_KVW_STEPS", "8"))

    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.standard_normal((hkv, n_pages, page, d)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((hkv, n_pages, page, d)),
                     jnp.bfloat16)
    upd = jnp.asarray(rng.standard_normal((slots, hkv, d)), jnp.bfloat16)
    pages = jnp.asarray(rng.integers(1, n_pages, slots), jnp.int32)
    offs = jnp.asarray(rng.integers(0, page, slots), jnp.int32)

    def scatter_impl(kp, vp):
        return (_scatter_token_kv(kp, pages, offs, upd),
                _scatter_token_kv(vp, pages, offs, upd))

    def pallas_impl(kp, vp):
        return paged_kv_write_pallas(kp, vp, pages, offs, upd, upd)

    impls = {"scatter": jax.jit(scatter_impl, donate_argnums=(0, 1)),
             "pallas_dma": jax.jit(pallas_impl, donate_argnums=(0, 1))}

    for name, fn in impls.items():
        a, b = kp, vp
        a, b = fn(a, b)          # compile
        jax.block_until_ready(b)
        t0 = time.monotonic()
        for _ in range(repeat):
            a, b = fn(a, b)
        jax.block_until_ready(b)
        us = (time.monotonic() - t0) / repeat * 1e6
        print(json.dumps({
            "impl": name, "per_call_us": round(us, 1),
            "per_dispatch_ms": round(us * layers * k_steps / 1e3, 2),
            "geometry": {"slots": slots, "hkv": hkv, "d": d, "page": page,
                         "n_pages": n_pages},
        }), flush=True)
        kp, vp = a, b  # keep donation chains valid


if __name__ == "__main__":
    main()
