#!/usr/bin/env python3
"""The controls of ``correct`` for the all-latent cell, at the cell's own
size, on the chip (run by hand through the chip tool; the benchmark's own
runs never run it):

    python3 benchmark/tests/control_latent_on_chip.py \\
        --control <int8_experts|reference_low> \\
        --workload dots.vlm1.rollout-long-latent --seed <n> --seconds 20

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared.) One whole run
of the cell, compared with the same float32 reference under
the same limits (``planes/rollout_latent.py::compare``); ``correct`` has
to come out false, each control by the limit that watches its part:

- ``int8_experts``: the engine serves every routed expert's three
  matrices rounded to int8 with one scale an output channel (kept in
  bfloat16 so that the tree and the programs are the cell's own); the
  reference compares with the unrounded weights, redrawn from ``--seed``
  once the engine's are gone: ``experts_rel_diff`` has to pass its limit,
  and the log-probabilities must not (a sixteenth of a token's choices
  land here);
- ``reference_low``: the program as it is; beside its own comparison, the
  reference computed wholly in the precision below (``control="low"``:
  every matmul weight int8, the latent rows a token keeps int8 with one
  scale a row) is put in the program's place for the log-probabilities
  (``checks.reference.control_low``): ``logprob_mean_abs_diff`` has to
  pass its limit.

PERF.md section 4 gives the readings of both beside the limits. The two
patches are ``control_hybrid_on_chip.py``'s, applied to this plane."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def reference_low(plane_mod) -> None:
    import numpy as np

    compare = plane_mod.compare

    def with_control(reference, params, c, limits, samples, walked,
                     again=False):
        out = compare(reference, params, c, limits, samples, walked, again)
        worst, total, count = 0.0, 0.0, 0
        for (prompt, toks, lps), tr in zip(samples, walked):
            n = min(len(toks), len(lps))
            low = reference.trace(params, c, list(prompt) + list(toks[:n]),
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


def int8_experts(plane_mod) -> None:
    """``control_hybrid_on_chip.int8_experts``, which patches the hybrid
    plane's ``start`` (the served experts rounded once the router is
    evened; this plane's class inherits it) and hands back a
    ``weights_for_reference`` that redraws the unrounded tree."""
    import importlib.util
    import types

    spec = importlib.util.spec_from_file_location(
        "control_hybrid_on_chip",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "control_hybrid_on_chip.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    patched = types.SimpleNamespace(
        HybridRolloutPlane=plane_mod.hybrid.HybridRolloutPlane)
    theirs.int8_experts(patched)
    plane_mod.weights_for_reference = patched.weights_for_reference


CONTROLS = {"int8_experts": int8_experts, "reference_low": reference_low}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    args, rest = ap.parse_known_args(argv)

    from benchmark import run
    from benchmark.lib import harness

    CONTROLS[args.control](harness.load_named("planes", "rollout_latent"))
    return run.main(rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
