#!/usr/bin/env python3
"""The controls of ``correct`` for the CCA cell, at the cell's own size,
on the chip (run by hand through the chip tool; the benchmark's own runs
never run it):

    python3 benchmark/tests/control_cca_on_chip.py \\
        --control <int8_experts|reference_low|as_drawn> \\
        --workload zaya1-8b.rollout-wide-cca --seed <n> --seconds 20

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared, PERF.md section
7 (w).) One whole run of the cell, compared with the same float32
reference under the same limits (``planes/rollout_cca.py::compare``);
``correct`` has to come out false, each control by the limit that watches
its part:

- ``int8_experts``: the engine serves every routed expert's three
  matrices rounded to int8 with one scale an output channel (kept in
  bfloat16 so that the tree and the programs are the cell's own); the
  reference compares with the unrounded weights, redrawn from ``--seed``
  once the engine's are gone: ``experts_rel_diff`` has to pass its limit;
- ``reference_low``: the program as it is; beside its own comparison, the
  reference computed wholly in the precision below (``control="low"``:
  every matmul weight int8, the ``k`` and ``v`` a token keeps int8 with
  one scale a head's row) is put in the program's place for the
  log-probabilities and the tails (``checks.reference.control_low``), and
  the run's verdict is then the control's: ``logprob_mean_abs_diff`` and
  ``tails_rel_diff`` have to pass their limits.

``as_drawn`` is no control of ``correct``: the cell as it is but for the
router's balancing bias, left as the seed drew it (not evened), in a
TRACED run, for ``expert_load_skew`` and ``moe_experts_hit`` as drawn
(PERF.md section 6).

PERF.md section 4 gives the readings of both controls beside the limits. The two
patches are ``control_hybrid_on_chip.py``'s, applied to this plane."""

import argparse
import importlib.util
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def reference_low(plane_mod) -> None:
    compare = plane_mod.compare

    def with_control(reference, params, c, limits, samples, held, walked,
                     again=False):
        out = compare(reference, params, c, limits, samples, held, walked,
                      again)
        import numpy as np

        worst, total, count, tails = 0.0, 0.0, 0, []
        for (prompt, toks, lps), h, tr in zip(samples, held, walked):
            n = min(len(toks), len(lps))
            low = reference.trace(params, c, list(prompt) + h["answer"],
                                  len(prompt), n, control="low")
            diff = np.abs(low["logprobs"] - tr["logprobs"])
            worst, total, count = (max(worst, float(diff.max())),
                                   total + float(diff.sum()), count + n)
            tails.append([float(plane_mod.hybrid.rel(mine, ref)) for mine, ref
                          in zip(low["states"], tr["states"])])
        low = {"logprob_mean_abs_diff": total / max(count, 1),
               "logprob_max_abs_diff": worst,
               "tails_rel_diff": plane_mod.tails_stat(tails),
               "tails_rel_diffs": tails}
        low["failed_by"] = [k for k in ("logprob_mean_abs_diff",
                                        "logprob_max_abs_diff",
                                        "tails_rel_diff")
                            if not low[k] <= limits[k + "_max"]]
        low["ok"] = not low["failed_by"]
        out["control_low"] = low
        out["sound"] = {k: out[k] for k in ("ok", "logprob_mean_abs_diff",
                                            "logprob_max_abs_diff",
                                            "tails_rel_diff")}
        # the control stands in the program's place: the run reads what
        # the low reference reads
        out.update({k: low[k] for k in ("logprob_mean_abs_diff",
                                        "logprob_max_abs_diff",
                                        "tails_rel_diff")})
        out["failed_by"] = sorted(set(out["failed_by"] + low["failed_by"]))
        out["ok"] = bool(out["ok"] and low["ok"])
        return out

    plane_mod.compare = with_control


def int8_experts(plane_mod) -> None:
    """``control_hybrid_on_chip.int8_experts``, which patches a plane
    class's ``start`` (the served experts rounded once the router is
    evened) and hands back a ``weights_for_reference`` that redraws the
    unrounded tree with the evened bias."""
    spec = importlib.util.spec_from_file_location(
        "control_hybrid_on_chip",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "control_hybrid_on_chip.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    patched = types.SimpleNamespace(
        HybridRolloutPlane=plane_mod.CcaRolloutPlane)
    theirs.int8_experts(patched)
    plane_mod.weights_for_reference = patched.weights_for_reference


def as_drawn(plane_mod) -> None:
    plane_mod.CcaRolloutPlane.start = plane_mod.hybrid.HybridRolloutPlane.start


CONTROLS = {"int8_experts": int8_experts, "reference_low": reference_low,
            "as_drawn": as_drawn}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    args, rest = ap.parse_known_args(argv)

    from benchmark import run
    from benchmark.lib import harness

    CONTROLS[args.control](harness.load_named("planes", "rollout_cca"))
    return run.main(rest + ["--trace",
                            "1" if args.control == "as_drawn" else "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
