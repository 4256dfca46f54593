"""The KDA state-update kernel alone, on the chip, at a benchmark cell's
shapes: ``kda_state_pallas`` over as many state stacks as the cell's share
has KDA layers, updated in place by one donated program as a decode step
does, timed by the device's own clock (a ``jax.profiler`` trace of the
calls) beside the oracle (``mixers.kda.kda_recurrent_step`` with the
``where`` and the write-back the decode step wrapped it in), and checked
against the oracle on four rows.

    chiprun -- python tools/bench_kda_state.py \
        [--rows 129 --heads 32 --size 128 --layers 6] [--blocks "32,16,8"]

``--blocks`` times the kernel at other heads a grid step than
``kda_state._heads_per_block`` returns for the shapes: the sweep behind
``_BLOCK_BYTES``. The least a call can cost is each visited row's state
read once and written once (``benchmark/lib/costs_hybrid.py::
kda_core_bytes`` counts the live ones) at the chip's 819 GB/s. Prints one
JSON line a variant; fails without a TPU."""

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

KERNEL = "kda_state"
HBM_BYTES_S = 819e9     # one TPU v5e chip (Google Cloud, "TPU v5e")


def inputs(rows: int, heads: int, size: int, layers: int, seed: int):
    """A decode step's operands a layer (q and k normed, g inside the
    bound of -5, the last row dead as the engine's spare slot is) and the
    layers' state stacks."""
    from polyrl_tpu.models.mixers import base

    def layer(key):
        ks = jax.random.split(key, 6)
        live = jnp.arange(rows) < rows - 1
        q = base.l2norm(jax.random.normal(ks[0], (rows, heads, size)))
        k = base.l2norm(jax.random.normal(ks[1], (rows, heads, size)))
        v = jax.random.normal(ks[2], (rows, heads, size))
        g = -5 * jax.nn.sigmoid(
            jax.random.normal(ks[3], (rows, heads, size)) * 3)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads)))
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        state = 0.1 * jax.random.normal(ks[5], (rows, heads, size, size))
        return state, (q * size ** -0.5, k, v, g, beta)

    made = [layer(k) for k in jax.random.split(jax.random.PRNGKey(seed),
                                               layers)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def device_ms(trace_dir: str, program: str,
              kernel: str = KERNEL) -> tuple[list[float], list[float]]:
    """(durations of the events of ``kernel``, durations of the whole
    ``program``'s runs) on the first device in the newest trace, ms."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    kernels, programs = [], []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                kernels += [e.duration_ns / 1e6 for e in line.events
                            if e.name.lstrip("%").startswith(kernel)]
            elif line.name == "XLA Modules":
                programs += [e.duration_ns / 1e6 for e in line.events
                             if e.name.startswith(f"jit_{program}")]
    return kernels, programs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=129)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_kda_state"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.models.mixers import kda
    from polyrl_tpu.ops import kda_state

    states, operands = inputs(args.rows, args.heads, args.size, args.layers,
                              args.seed)
    check = jnp.asarray([0, args.rows // 2, args.rows - 2, args.rows - 1])
    want = [kda.kda_recurrent_step(s[check], *(a[check] for a in ops))
            for s, ops in zip(states, operands)]

    def oracle(state, q, k, v, g, beta):
        """The parent's decode step: the recurrence, the rows kept where
        no request lives, the write-back."""
        new, o = kda.kda_recurrent_step(state, q, k, v, g, beta)
        return jnp.where((beta > 0).any(-1)[:, None, None, None], new,
                         state), o

    def program(update):
        def step(states, operands):
            return tuple(zip(*(update(s, *ops)
                               for s, ops in zip(states, operands))))

        step.__name__ = "bench_step"
        return jax.jit(step, donate_argnums=(0,))

    hb0 = kda_state._heads_per_block(args.heads, args.size, args.size)
    variants = [("kernel", hb0, kda_state.kda_state_pallas)]
    variants += [(f"kernel hb={b}", int(b), functools.partial(
        kda_state.kda_state_pallas, hb=int(b)))
        for b in args.blocks.split(",") if b]
    variants.append(("oracle", None, oracle))
    least_ms = (1e3 * 2 * 4 * args.layers * args.rows * args.heads
                * args.size ** 2 / HBM_BYTES_S)
    os.makedirs(args.out, exist_ok=True)
    for n, (name, hb, update) in enumerate(variants):
        fn = program(update)
        held = jax.tree_util.tree_map(jnp.copy, states)
        try:
            held, outs = jax.block_until_ready(fn(held, operands))
        except Exception as e:  # a block the compiler refuses: say so, go on
            print(json.dumps({"variant": name, "error": str(e)[:300]}),
                  flush=True)
            continue
        err = max(
            max(float(jnp.abs(new[check] - w[0]).max()),
                float(jnp.abs(o[check] - w[1]).max()))
            for new, o, w in zip(held, outs, want))
        trace_dir = os.path.join(args.out, f"trace{n}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                held, outs = fn(held, operands)
            jax.block_until_ready(outs)
        kernels, programs = device_ms(trace_dir, "bench_step")
        med = statistics.median(programs)
        line = {
            "variant": name, "heads_per_block": hb,
            "device": jax.devices()[0].device_kind,
            "state": [args.rows, args.heads, args.size, args.size],
            "layers": args.layers, "program_ms_median": med,
            "program_ms_min": min(programs), "program_ms_max": max(programs),
            "programs": len(programs), "least_ms": least_ms,
            "gb_s": 1e-6 * least_ms * HBM_BYTES_S / med,
            "roofline_share": 100 * least_ms / med,
            "max_abs_err_vs_oracle": err}
        if kernels:   # the kernel's events alone, a program's worth
            line["kernel_ms_a_program"] = sum(kernels) / len(programs)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
