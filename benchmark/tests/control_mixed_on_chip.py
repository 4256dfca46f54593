#!/usr/bin/env python3
"""The controls of ``correct`` for the Laguna cell, at the cell's own size,
on the chip (run by hand through the chip tool; the benchmark's own runs
never run it):

    python3 benchmark/tests/control_mixed_on_chip.py \\
        --control <reference_low|int8_experts|window_off>[,...] \\
        --workload laguna-xs.2.rollout-long-mixed \\
        --seed <n> --seconds 20 [--trace 1]

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared.) One whole run
of the cell through ``run.py``, the program as it is; beside its own
comparison, the reference computed with the control
(``references/moe_gqa_mixed.py``: ``control=``) is put in the program's
place (``checks.reference.controls``: its log-probabilities, its held
experts and its first window layer's rows against the sound reference's),
and the run's verdict is then the controls'. ``correct`` has to come out
false, each control by the limit that watches its part:

- ``reference_low``: the whole forward one precision below the bfloat16
  the configuration states for weights and cache (every matmul weight and
  the head int8 with one scale an output channel, the ``kk`` and ``v`` a
  token keeps int8 with one scale a head's row):
  ``logprob_mean_abs_diff`` has to pass its limit;
- ``int8_experts``: the held experts alone rounded to int8, on the sound
  reference's hidden states: ``experts_rel_diff`` has to pass its limit
  and no other number moves;
- ``window_off``: a window of 511 keys: ``window_rel_diff`` has to pass
  its limit (the log-probabilities hardly move: with random weights
  attention is near uniform, and one key of 512 is 0.2% of a head's
  output).

PERF.md section 4 gives the readings beside the limits."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NUMBERS = ("logprob_mean_abs_diff", "logprob_max_abs_diff",
           "experts_rel_diff", "window_rel_diff")
# a control's name here -> the reference's ``control=``
CONTROLS = {"reference_low": "low", "int8_experts": "int8_experts",
            "window_off": "window_minus"}


def in_the_programs_place(plane_mod, controls) -> None:
    """``controls``: one name or several (one run of the cell then serves
    them all: the reference walks once more for each that alters its
    forward)."""
    walk, compare = plane_mod.walk, plane_mod.compare
    altered: dict = {}

    def walk_all(reference, cfg, params, c, samples, held, _control=""):
        for name in controls:
            if name != "int8_experts":
                altered[name] = walk(reference, cfg, params, c, samples,
                                     held, CONTROLS[name])
        return walk(reference, cfg, params, c, samples, held)

    def compare_all(reference, params, c, limits, samples, held, walked):
        import numpy as np

        out = compare(reference, params, c, limits, samples, held, walked)
        out["sound"] = {k: out[k] for k in ("ok", *NUMBERS)}
        out["controls"] = {}
        for name in controls:
            traces = altered.get(name, walked)
            pairs = list(zip(traces, walked))
            diffs = [np.abs(a["logprobs"] - b["logprobs"]) for a, b in pairs]
            rows = plane_mod.experts_rel(
                reference, params, c, walked,
                control=CONTROLS[name]) if name != "window_off" else None
            got = {
                "logprob_mean_abs_diff":
                    float(np.mean(np.concatenate(diffs))),
                "logprob_max_abs_diff": float(max(d.max() for d in diffs)),
                "experts_rel_diff": 0.0 if rows is None
                else float(np.median(rows)),
                "window_rel_diff": float(np.mean(
                    [plane_mod.sambay.ring_rel(a["rings"][0], b["rings"][0])
                     for a, b in pairs]))}
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

    in_the_programs_place(harness.load_named("planes", "rollout_mixed"),
                          controls)
    return run.main(rest if "--trace" in rest else rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
