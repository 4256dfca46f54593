"""The Mamba-2 (SSD) state-update kernel alone, on the chip, at a benchmark
cell's shapes: ``ssd_state_pallas`` over as many state stacks as the cell
has Mamba-2 layers (``[65, 8, 128, 512]`` float32 x 23: the published ``[65,
64, 64, 128]`` a layer, a group's heads side by side), updated in place by
one donated program as a decode step does, timed by the device's own clock
(a ``jax.profiler`` trace of the calls) beside the oracle
(``ssd_state.ssd_recurrent_step`` with the ``where`` and the write-back the
dispatcher wraps it in), and checked against the oracle on four rows. What
the kernel reaches alone, against ``ssd_core_roofline`` inside the step.

    chiprun -- python tools/bench_ssd_state.py \
        [--rows 65 --groups 8 --state 128 --width 512 --layers 23] \
        [--blocks "8"]

``--blocks`` times the kernel at other groups a grid step than
``ssd_state._groups_per_block`` returns for the shapes (whole sublane tiles
of the ``[G, W]`` rows, or all of G). The least a call can cost is each
visited row's state read once and written once
(``benchmark/lib/costs_nemotron_h.py::ssd_core_bytes`` counts the live
ones) at the chip's 819 GB/s. Prints one JSON line a variant; fails without
a TPU."""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tools.bench_kda_state import HBM_BYTES_S, device_ms  # noqa: E402

KERNEL = "ssd_state"


def inputs(rows: int, groups: int, n: int, w: int, layers: int, seed: int):
    """A decode step's operands a layer (dt x of order 0.01, decays of
    0.85-0.999, the last row dead as the engine's spare slot is) and the
    layers' state stacks."""
    def layer(key):
        ks = jax.random.split(key, 5)
        live = (jnp.arange(rows) < rows - 1)[:, None, None]
        x = 0.01 * jax.random.normal(ks[0], (rows, groups, w))
        a = jnp.exp(-jax.random.uniform(ks[1], (rows, groups, w),
                                        minval=0.001, maxval=0.16))
        b = jax.random.normal(ks[2], (rows, groups, n))
        c = jax.random.normal(ks[3], (rows, groups, n))
        state = 0.1 * jax.random.normal(ks[4], (rows, groups, n, w))
        return state, (jnp.where(live, x, 0.0), jnp.where(live, a, 1.0), b, c)

    made = [layer(k) for k in jax.random.split(jax.random.PRNGKey(seed),
                                               layers)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--layers", type=int, default=23)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_ssd_state"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.ops import ssd_state

    states, operands = inputs(args.rows, args.groups, args.state, args.width,
                              args.layers, args.seed)
    check = jnp.asarray([0, args.rows // 2, args.rows - 2, args.rows - 1])
    want = [ssd_state.ssd_recurrent_step(s[check], *(v[check] for v in ops))
            for s, ops in zip(states, operands)]

    def oracle(state, x, a, b, c):
        """The dispatcher's form off a TPU: the recurrence, the rows kept
        where no request lives, the write-back."""
        new, y = ssd_state.ssd_recurrent_step(state, x, a, b, c)
        live = (a != 1.0).any((-2, -1))[:, None, None, None]
        return jnp.where(live, new, state), y

    def program(update):
        def step(states, operands):
            return tuple(zip(*(update(s, *ops)
                               for s, ops in zip(states, operands))))

        step.__name__ = "bench_step"
        return jax.jit(step, donate_argnums=(0,))

    gb0 = ssd_state._groups_per_block(args.groups, args.state, args.width)
    variants = [("kernel", gb0, ssd_state.ssd_state_pallas)]
    variants += [(f"kernel gb={b}", int(b), functools.partial(
        ssd_state.ssd_state_pallas, gb=int(b)))
        for b in args.blocks.split(",") if b]
    variants.append(("oracle", None, oracle))
    least_ms = (1e3 * 2 * 4 * args.layers * args.rows * args.groups
                * args.state * args.width / HBM_BYTES_S)
    os.makedirs(args.out, exist_ok=True)
    for n, (name, gb, update) in enumerate(variants):
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
                float(jnp.abs(y[check] - w[1]).max()))
            for new, y, w in zip(held, outs, want))
        trace_dir = os.path.join(args.out, f"trace{n}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                held, outs = fn(held, operands)
            jax.block_until_ready(outs)
        kernels, programs = device_ms(trace_dir, "bench_step", KERNEL)
        med = statistics.median(programs)
        line = {
            "variant": name, "groups_per_block": gb,
            "device": jax.devices()[0].device_kind,
            "state": [args.rows, args.groups, args.state, args.width],
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
