#!/usr/bin/env python3
"""The controls of ``correct`` for the looped cell, at the cell's own size,
on the chip (run by hand through the chip tool; the benchmark's own runs
never run it):

    python3 benchmark/tests/control_looped_on_chip.py \\
        --control <reference_low|passes_crossed>[,...] \\
        --workload ouro-2.6b.rollout-short-looped \\
        --seed <n> --seconds 20 [--trace 1]

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared.) One whole run
of the cell through ``run.py``, the program as it is; beside its own
comparison, the reference computed with the control
(``references/looped_gqa.py``: ``control=``) is put in the program's place
(``checks.reference.controls``: its log-probabilities and each pass's keys
and values against the sound reference's), and the run's verdict is then
the controls'. ``correct`` has to come out false, each control by the
limit that watches its part:

- ``reference_low``: the whole forward one precision below the bfloat16
  the configuration states for weights and cache (every matmul weight and
  the head int8 with one scale an output channel, the ``kk`` and ``v`` a
  token keeps int8 with one scale a head's row):
  ``logprob_mean_abs_diff`` has to pass its limit;
- ``passes_crossed``: passes 2-4 attend pass 1's keys and values and keep
  them as their own (the paper's shared-cache shortcut):
  ``pass_kv_rel_diff`` has to pass its limit (whether the
  log-probabilities move with it is read here and said in the
  configuration's file).

PERF.md section 4 gives the readings beside the limits."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NUMBERS = ("logprob_mean_abs_diff", "logprob_max_abs_diff",
           "pass_kv_rel_diff")
# a control's name here -> the reference's ``control=``
CONTROLS = {"reference_low": "low", "passes_crossed": "passes_crossed"}


def in_the_programs_place(plane_mod, controls) -> None:
    """``controls``: one name or several (one run of the cell then serves
    them all: the reference walks once more for each)."""
    walk, compare = plane_mod.walk, plane_mod.compare
    altered: dict = {}

    def walk_all(reference, c, params, samples, held, _control=""):
        for name in controls:
            altered[name] = walk(reference, c, params, samples, held,
                                 CONTROLS[name])
        return walk(reference, c, params, samples, held)

    def compare_all(limits, samples, held, walked):
        import numpy as np

        out = compare(limits, samples, held, walked)
        out["sound"] = {k: out[k] for k in ("ok", *NUMBERS)}
        out["controls"] = {}
        for name in controls:
            pairs = list(zip(altered[name], walked))
            diffs = [np.abs(a["logprobs"] - b["logprobs"]) for a, b in pairs]
            passes = [plane_mod.pass_kv_rel(a["pass_kv"], b["pass_kv"])
                      for a, b in pairs]
            got = {
                "logprob_mean_abs_diff":
                    float(np.mean(np.concatenate(diffs))),
                "logprob_max_abs_diff": float(max(d.max() for d in diffs)),
                "pass_kv_rel_diff": float(np.max(np.mean(passes, axis=0))),
                "pass_kv_rel_diffs": passes}
            got["failed_by"] = [k for k in NUMBERS
                                if not got[k] <= limits[k + "_max"]]
            got["ok"] = not got["failed_by"]
            out["controls"][name] = got
        # the controls stand in the program's place: the run reads the
        # worst that any of the altered references reads
        for k in NUMBERS:
            out[k] = max(got[k] for got in out["controls"].values())
        out["failed_by"] = sorted(set(out["failed_by"]).union(
            *(got["failed_by"] for got in out["controls"].values())))
        out["ok"] = bool(out["ok"] and all(
            got["ok"] for got in out["controls"].values()))
        return out

    plane_mod.walk, plane_mod.compare = walk_all, compare_all


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    help="one of %s, or several with commas"
                    % (tuple(CONTROLS),))
    args, rest = ap.parse_known_args(argv)
    controls = args.control.split(",")
    if set(controls) - set(CONTROLS):
        ap.error(f"--control takes {tuple(CONTROLS)}")

    from benchmark import run
    from benchmark.lib import harness

    in_the_programs_place(harness.load_named("planes", "rollout_looped"),
                          controls)
    return run.main(rest if "--trace" in rest else rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
