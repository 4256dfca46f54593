#!/usr/bin/env python3
"""The control of ``correct`` for a dense cell, at the cell's own size, on
the chip (run by hand through the chip tool; the benchmark's own runs never
run it):

    python3 benchmark/tests/control_on_chip.py --workload <cell> --seed <n> [--seconds 6]

One whole run of the cell, and after its window, on the same prompts and
served tokens that ``correct`` scores, two numbers side by side: the
program's (its log-probabilities against the float32 reference, what
``correct`` holds to the configuration's limits) and the control's (the
reference with every matmul weight rounded to int8, the nearest precision
below the bfloat16 the configuration states, put in the program's place
against the same float32 reference). The limits have to lie above the
first over many seeds and below the second: PERF.md section 4 gives the
readings. The last line of standard output is the run's result with both
under ``checks.reference``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def with_control(check_logprobs):
    """``check_logprobs`` and, under ``control_int8``, the same comparison
    with the int8 reference's log-probabilities of the same tokens put
    where the program's stand: its ``ok`` has to be false."""
    def check(reference, params, config, samples):
        in_place = []
        for prompt, toks, lps in samples:
            n = min(len(toks), len(lps))
            low, _ = reference.score(params, config["config"],
                                     list(prompt) + list(toks[:n]), n,
                                     weights="int8")
            in_place.append((prompt, toks[:n], low.tolist()))
        out = check_logprobs(reference, params, config, samples)
        out["control_int8"] = check_logprobs(reference, params, config,
                                             in_place)
        return out
    return check


def main(argv) -> int:
    from benchmark import run
    from benchmark.lib import harness

    plane = harness.load_named("planes", "rollout")
    plane.check_logprobs = with_control(plane.check_logprobs)
    return run.main(argv + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
