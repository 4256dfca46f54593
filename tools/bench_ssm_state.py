"""The Mamba state-update kernel alone, on the chip, at a benchmark cell's
shapes: ``ssm_state_pallas`` over as many layers' states as the model has
scans, updated in place by one donated program as a decode step does,
timed by the device's own clock (a ``jax.profiler`` trace of the calls)
beside the oracle (``mixers.ssm.ssm_step`` with the ``where`` and the
write-back the decode step wrapped it in), and checked against the oracle
on four rows.

    chiprun -- python tools/bench_ssm_state.py \
        [--rows 129 --state 16 --inner 5120 --layers 9]

The least a call can cost is each visited row's state read once and
written once at the chip's 819 GB/s. Prints one JSON line a variant; fails
without a TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tools.bench_kda_state import HBM_BYTES_S, device_ms  # noqa: E402


def inputs(rows: int, n: int, inner: int, layers: int, seed: int):
    """A decode step's operands a layer (``dt`` as the softplus of the
    drawn bias leaves it, the last row dead as the engine's spare slot
    is), the layers' weights and their states."""
    def layer(key):
        ks = jax.random.split(key, 6)
        lp = {"a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                  1, n + 1, dtype=jnp.float32))[:, None], (n, inner)),
              "d_skip": jnp.ones((inner,), jnp.float32)}
        c = jax.random.normal(ks[0], (rows, inner))
        dt = jnp.exp(jax.random.uniform(ks[1], (rows, inner), minval=jnp.log(
            1e-3), maxval=jnp.log(1e-1)))
        bm = jax.random.normal(ks[2], (rows, n))
        cm = jax.random.normal(ks[3], (rows, n))
        state = jax.random.normal(ks[4], (rows, n, inner))
        return state, (lp, c, dt, bm, cm)

    made = [layer(k) for k in jax.random.split(jax.random.PRNGKey(seed),
                                               layers)]
    return tuple(m[0] for m in made), tuple(m[1] for m in made)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=129)
    ap.add_argument("--state", type=int, default=16)
    ap.add_argument("--inner", type=int, default=5120)
    ap.add_argument("--layers", type=int, default=9)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_ssm_state"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.models.mixers import ssm
    from polyrl_tpu.ops import ssm_state

    states, operands = inputs(args.rows, args.state, args.inner, args.layers,
                              args.seed)
    live = jnp.arange(args.rows) < args.rows - 1
    check = jnp.asarray([0, args.rows // 2, args.rows - 2, args.rows - 1])
    want = [ssm.ssm_step(ops[0], s[check], *(a[check] for a in ops[1:]))
            for s, ops in zip(states, operands)]

    def oracle(lp, state, c, dt, bm, cm, live):
        """The decode step without the kernel: the recurrence, the rows
        kept where no request lives, the write-back."""
        new, m = ssm.ssm_step(lp, state, c, dt, bm, cm)
        return jnp.where(live[:, None, None], new, state), m

    def program(update):
        def step(states, operands):
            return tuple(zip(*(update(ops[0], s, *ops[1:], live)
                               for s, ops in zip(states, operands))))

        step.__name__ = "bench_step"
        return jax.jit(step, donate_argnums=(0,))

    least_ms = (1e3 * 2 * 4 * args.layers * args.rows * args.state
                * args.inner / HBM_BYTES_S)
    os.makedirs(args.out, exist_ok=True)
    for n, (name, update) in enumerate([
            ("kernel", ssm_state.ssm_state_update), ("oracle", oracle)]):
        fn = program(update)
        held = jax.tree_util.tree_map(jnp.copy, states)
        held, outs = jax.block_until_ready(fn(held, operands))
        # the last row is dead: it keeps its state, its ``m`` is not for use
        err = max(
            max(float(jnp.abs(new[check[:3]] - w[0][:3]).max()),
                float(jnp.abs(new[check[3]] - s[check[3]]).max()),
                float(jnp.abs(m[check[:3]] - w[1][:3]).max()))
            for new, m, w, s in zip(held, outs, want, states))
        trace_dir = os.path.join(args.out, f"trace{n}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                held, outs = fn(held, operands)
            jax.block_until_ready(outs)
        kernels, programs = device_ms(trace_dir, "bench_step",
                                      kernel="ssm_state")
        med = statistics.median(programs)
        line = {
            "variant": name, "device": jax.devices()[0].device_kind,
            "state": [args.rows, args.state, args.inner],
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
