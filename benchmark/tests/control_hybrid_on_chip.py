#!/usr/bin/env python3
"""The controls of ``correct`` for the hybrid cell, at the cell's own
size, on the chip (run by hand through the chip tool; the benchmark's own
runs never run it):

    python3 benchmark/tests/control_hybrid_on_chip.py \\
        --control <state_bf16|int8_experts|reference_low> \\
        --workload ling-3.0-flash.rollout-long-wide --seed <n> [--seconds 6]

One whole run of the cell with the PROGRAM computing in the nearest
precision below the one the configuration states, compared with the same
float32 reference under the same limits (``planes/rollout_hybrid.py::
compare``); ``correct`` has to come out false, each control by the limit
that watches its part:

- ``state_bf16``: the engine keeps the KDA layers' recurrent state in
  bfloat16 (``models/cache_spec.py::STATE_DTYPE``; the configuration says
  float32): ``state_rel_diff`` has to pass its limit;
- ``int8_experts``: the engine serves every routed expert's three
  matrices rounded to int8 with one scale an output channel (the values
  ``models/quant.py`` would hold, kept in bfloat16 so that the tree and
  the programs are the cell's own); the reference compares with the
  unrounded weights, redrawn from ``--seed`` once the engine's are gone:
  ``experts_rel_diff`` has to pass its limit;
- ``reference_low``: the program as it is; beside its own comparison, the
  reference computed wholly in the precision below (``control="low"``:
  every matmul weight int8, the state bfloat16) is put in the program's
  place for the log-probabilities (``checks.reference.control_low``):
  ``logprob_mean_abs_diff`` has to pass its limit.

PERF.md section 4 gives the readings of all three beside the limits."""

import argparse
import gc
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def state_bf16(plane_mod) -> None:
    import jax.numpy as jnp

    from polyrl_tpu.models import cache_spec

    cache_spec.STATE_DTYPE = jnp.bfloat16


def int8_experts(plane_mod) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import harness
    from polyrl_tpu.models import decoder

    def rounded(w):
        """[.., in, out] bfloat16 -> the same, on int8's grid."""
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
        return (jnp.round(f / jnp.maximum(scale, 1e-30)) * scale).astype(
            w.dtype)

    round_in_place = jax.jit(rounded, donate_argnums=0)
    start = plane_mod.HybridRolloutPlane.start

    def start_rounded(self):
        start(self)
        eng = self.eng
        tree = eng.params
        moe = dict(tree["layers"]["moe"])
        for key in decoder.EXPERT_KEYS:
            moe[key] = jax.block_until_ready(round_in_place(moe[key]))
        tree = {**tree, "layers": {**tree["layers"], "moe": moe}}
        eng.update_weights(tree, version=eng.weight_version)
        harness.say("control: the routed experts are on int8's grid")

    def redrawn(plane, eng):
        cfg = eng.cfg
        bias = eng.params["layers"]["moe"]["router_bias"]   # as evened
        eng.params = None
        gc.collect()
        draw = jax.jit(lambda key: decoder.init_params(key, cfg))
        tree = jax.block_until_ready(
            draw(jax.random.PRNGKey(harness.fold_seed(plane.seed))))
        moe = dict(tree["layers"]["moe"], router_bias=bias)
        return {**tree, "layers": {**tree["layers"], "moe": moe}}

    plane_mod.HybridRolloutPlane.start = start_rounded
    plane_mod.weights_for_reference = redrawn


def reference_low(plane_mod) -> None:
    """The control as ``control_on_chip.py`` has it for the dense cells:
    the reference itself computed in the nearest precision below the
    configuration's (every matmul weight int8, the state bfloat16), its
    log-probabilities of the served tokens against the float32
    reference's, held to the log-probabilities' limits:
    ``control_low.ok`` has to be false."""
    import numpy as np

    compare = plane_mod.compare

    def with_control(reference, params, c, limits, samples, held, walked,
                     again=False):
        out = compare(reference, params, c, limits, samples, held, walked,
                      again)
        worst, total, count = 0.0, 0.0, 0
        for (prompt, toks, lps), h, tr in zip(samples, held, walked):
            n = min(len(toks), len(lps))
            low = reference.trace(params, c, list(prompt) + h["answer"],
                                  len(prompt), n, control="low")["logprobs"]
            diff = np.abs(low - tr["logprobs"])
            worst, total, count = (max(worst, float(diff.max())),
                                   total + float(diff.sum()), count + n)
        mean = total / max(count, 1)
        out["control_low"] = {
            "logprob_mean_abs_diff": mean, "logprob_max_abs_diff": worst,
            "ok": bool(mean <= limits["logprob_mean_abs_diff_max"]
                       and worst <= limits["logprob_max_abs_diff_max"])}
        return out

    plane_mod.compare = with_control


CONTROLS = {"state_bf16": state_bf16, "int8_experts": int8_experts,
            "reference_low": reference_low}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    args, rest = ap.parse_known_args(argv)

    from benchmark import run
    from benchmark.lib import harness

    CONTROLS[args.control](harness.load_named("planes", "rollout_hybrid"))
    return run.main(rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
