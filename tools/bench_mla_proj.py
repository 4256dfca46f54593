"""The two ``wkv_b`` products of a decode step's latent attention alone, on
the chip, at a benchmark cell's shapes: ``ops/mla_proj.py``'s ``absorb`` and
``unabsorb`` over every layer of one stacked ``wkv_b``, in one program as a
decode step runs them, timed by the device's own clock (a ``jax.profiler``
trace of the calls) beside the oracle (``mixers.mla.mla_absorb``'s and
``mla_unabsorb``'s einsum on the layer's slice), and checked against
the oracle.

    chiprun -- python tools/bench_mla_proj.py \
        [--rows 65 --heads 128 --layers 5] [--blocks "16,8,4"]

The defaults are ``dots.vlm1.rollout-long-latent``'s (``--rows 129 --heads
32 --layers 1`` for ``ling-3.0-flash.rollout-long-wide``). ``--blocks``
times the kernels at other heads a grid step than
``mla_proj._heads_per_block`` returns for the shapes: the sweep behind
``_BLOCK_BYTES``. The least a layer's two products can read is ``wkv_b``
once, each half by the product that multiplies it, at the chip's 819 GB/s.
Prints one JSON line a variant; fails without a TPU."""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

KERNELS = ("mla_absorb", "mla_unabsorb")
HBM_BYTES_S = 819e9     # one TPU v5e chip (Google Cloud, "TPU v5e")


def inputs(rows: int, heads: int, rank: int, size: int, layers: int,
           seed: int, dtype=jnp.bfloat16):
    """The stacked ``wkv_b`` at N(0, 0.02), and a layer's operands: the
    queries' ``nope`` part and the attention's output over the latent
    rows."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    stack = (0.02 * jax.random.normal(
        ks[0], (layers, rank, heads * 2 * size))).astype(dtype)
    q = jax.random.normal(ks[1], (layers, rows, heads, size)).astype(dtype)
    o = jax.random.normal(ks[2], (layers, rows, heads, rank)).astype(dtype)
    return stack, tuple(q), tuple(o)     # a layer's operands are its own


def oracle(q_nope, o_latent, stack, layer: int):
    """The einsums of ``mixers.mla.mla_absorb`` and ``mla_unabsorb`` on
    ``stack[layer]``."""
    rank, heads, size = stack.shape[1], q_nope.shape[1], q_nope.shape[2]
    w = stack[layer].reshape(rank, heads, -1)
    q_abs = jnp.einsum("shd,rhd->shr", q_nope, w[..., :size],
                       preferred_element_type=jnp.float32)
    return (q_abs.astype(q_nope.dtype),
            jnp.einsum("shr,rhd->shd", o_latent, w[..., size:],
                       preferred_element_type=jnp.float32))


def device_ms(trace_dir: str, program: str):
    """(summed durations of each kernel's events by name, durations of the
    whole ``program``'s runs) on the first device in the newest trace, ms."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    kernels, programs = dict.fromkeys(KERNELS, 0.0), []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    for k in KERNELS:
                        if e.name.lstrip("%").startswith(k):
                            kernels[k] += e.duration_ns / 1e6
            elif line.name == "XLA Modules":
                programs += [e.duration_ns / 1e6 for e in line.events
                             if e.name.startswith(f"jit_{program}")]
    return kernels, programs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--size", type=int, default=128,
                    help="a head's nope and value size")
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_mla_proj"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.ops import mla_proj

    stack, qs, os_ = inputs(args.rows, args.heads, args.rank, args.size,
                            args.layers, args.seed)

    def kernel(q, o, stack, layer, hb=None):
        return (mla_proj.absorb(q, stack, layer=layer, hb=hb),
                mla_proj.unabsorb(o, stack, layer=layer, hb=hb))

    def program(products):
        def step(stack, qs, os_):
            return [products(qs[l], os_[l], stack, l)
                    for l in range(args.layers)]

        step.__name__ = "bench_step"
        return jax.jit(step)

    hb0 = mla_proj._heads_per_block(args.heads, args.rows, args.rank,
                                    args.size, stack.dtype.itemsize)
    variants = [("kernel", hb0, kernel)]
    variants += [(f"kernel hb={b}", int(b),
                  functools.partial(kernel, hb=int(b)))
                 for b in args.blocks.split(",") if b]
    variants.append(("oracle", None, oracle))
    want = jax.block_until_ready(program(oracle)(stack, qs, os_))
    least_ms = 1e3 * stack.size * stack.dtype.itemsize / HBM_BYTES_S
    os.makedirs(args.out, exist_ok=True)
    for n, (name, hb, products) in enumerate(variants):
        fn = program(products)
        try:
            got = jax.block_until_ready(fn(stack, qs, os_))
        except Exception as e:  # a block the compiler refuses: say so, go on
            print(json.dumps({"variant": name, "error": str(e)[:300]}),
                  flush=True)
            continue
        err = max(float(jnp.abs(g.astype(jnp.float32)
                                - w.astype(jnp.float32)).max())
                  for g, w in zip(jax.tree_util.tree_leaves(got),
                                  jax.tree_util.tree_leaves(want)))
        trace_dir = os.path.join(args.out, f"trace{n}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                got = fn(stack, qs, os_)
            jax.block_until_ready(got)
        kernels, programs = device_ms(trace_dir, "bench_step")
        med = statistics.median(programs)
        # the products' own time: the kernels' events where there are any
        # (the program around them also lays the operands out), else the
        # whole program (the einsum's copy and products bear no name)
        own = sum(kernels.values()) / len(programs) or med
        line = {
            "variant": name, "heads_per_block": hb,
            "device": jax.devices()[0].device_kind,
            "wkv_b": list(stack.shape), "rows": args.rows,
            "program_ms_median": med, "program_ms_min": min(programs),
            "program_ms_max": max(programs), "programs": len(programs),
            "products_ms": own, "least_ms": least_ms,
            "gb_s": 1e-9 * least_ms * HBM_BYTES_S / own,
            "roofline_share": 100 * least_ms / own,
            "max_abs_err_vs_oracle": err}
        for k, ms in kernels.items():   # a kernel's events, a program's worth
            if ms:
                line[f"{k}_ms_a_program"] = ms / len(programs)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
