#!/usr/bin/env python3
"""The controls of ``correct`` for Nemotron-3-Nano's cell, at the cell's own
size, on the chip (run by hand through the chip tool; the benchmark's own
runs never run it):

    python3 benchmark/tests/control_nemotron_h_on_chip.py \\
        --control <state_bf16|no_decay|int8_experts|fp8_weights>[,...] \\
        --workload nemotron-3-nano-30b-a3b.rollout-wide-ssd \\
        --seed <n> --seconds 20

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared.) One whole run
of the cell through ``run.py``, the program as it is; beside its own
comparison, the reference computed with the control
(``references/nemotron_h.py``: ``control=``) is put in the program's place
(``checks.reference.controls``: its first Mamba-2 state over the sound
walk's slowest heads, or its routed experts, against the sound reference's), and the run's verdict is then the
controls'. ``correct`` has to come out false, each control by the limit
that watches its part:

- ``state_bf16``: every Mamba-2 state rounded to bfloat16's mantissa after
  each token (``jax.lax.reduce_precision``), the nearest precision below
  the float32 the configuration states for it: ``state_rel_diff``;
- ``no_decay``: a = 1 in every Mamba-2 layer: ``state_rel_diff``, by far;
- ``int8_experts``: the routed experts' matrices weight-only int8 with one
  scale an output channel, one precision below the bfloat16 the
  configuration states: ``experts_rel_diff``;
- ``fp8_weights``: every matrix of every sublayer and the head rounded to
  float8 e4m3's three bits of mantissa, one precision below the bfloat16
  the configuration states for the weights, through all 52 layers (a whole
  second walk of the reference a scored request): the log-probabilities
  of the sampled tokens against the sound reference's,
  ``logprob_mean_abs_diff`` (``logprob_max_abs_diff`` is read beside it).

The two state controls alter the model's FIRST layer, so the reference
walks that layer alone for them; ``int8_experts`` is the reference's routed
block on the sound walk's hidden states. ``benchmark/tests/
test_nemotron_h_metrics.py`` runs all three on the CPU at the tiny size.
PERF.md section 4 gives the readings beside the limits."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = {"state_bf16": "state_rel_diff", "no_decay": "state_rel_diff",
            "int8_experts": "experts_rel_diff",
            "fp8_weights": "logprob_mean_abs_diff"}


def reading(plane_mod, reference, params, c, name, samples, held, walked):
    """The number that watches the control ``name``, with the control's
    reference in the program's place against the sound walk ``walked``
    (``fp8_weights``: a dict of it and ``logprob_max_abs_diff``)."""
    import numpy as np

    if name == "fp8_weights":
        diffs = []
        for (prompt, toks, lps), h, tr in zip(samples, held, walked):
            got = reference.trace(params, c, list(prompt) + h["answer"],
                                  len(prompt), min(len(toks), len(lps)), name)
            diffs.append(np.abs(got["logprobs"] - tr["logprobs"]))
        diffs = np.concatenate(diffs)
        return {"logprob_mean_abs_diff": float(diffs.mean()),
                "logprob_max_abs_diff": float(diffs.max())}

    if name == "int8_experts":
        rel = plane_mod.hybrid.rel
        rows = []
        for tr in walked:
            for j in tr["experts"]:
                ref = reference.routed_block(params, c, j, tr["moe_in"][j])
                low = reference.routed_block(params, c, j, tr["moe_in"][j],
                                             name)
                some = np.linalg.norm(ref, axis=-1) > 0
                rows.append(rel(low[some], ref[some], axis=-1))
        return float(np.median(np.concatenate(rows)))
    states = []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        got = reference.trace(params, c, list(prompt) + h["answer"],
                              len(prompt), min(len(toks), len(lps)), name,
                              upto=1)
        states.append(plane_mod.slow_state_rel(got["states"][0], tr))
    return float(np.mean(states))


def in_the_programs_place(plane_mod, controls) -> None:
    """``controls``: one name or several (one run of the cell then serves
    them all)."""
    compare = plane_mod.compare

    def compare_all(reference, params, c, limits, samples, held, walked):
        out = compare(reference, params, c, limits, samples, held, walked)
        out["sound"] = {k: out[k] for k in ("ok", *plane_mod.HELD)}
        out["controls"] = {}
        for name in controls:
            watched = CONTROLS[name]
            got = reading(plane_mod, reference, params, c, name, samples,
                          held, walked)
            got = got if isinstance(got, dict) else {watched: got}
            out["controls"][name] = {
                **got, "limit": limits[watched + "_max"],
                "ok": bool(got[watched] <= limits[watched + "_max"])}
            plane_mod.harness.say(f"control {name}: " + ", ".join(
                f"{k} {v:.4g} (limit {limits[k + '_max']:g})"
                for k, v in got.items()))
            # the control stands in the program's place: the run reads the
            # worst that any of the altered references reads
            for k, v in got.items():
                out[k] = max(out[k], v)
        out["failed_by"] = [k for k in plane_mod.HELD
                            if not out[k] <= limits[k + "_max"]]
        out["ok"] = bool(out["ok"] and all(
            got["ok"] for got in out["controls"].values()))
        return out

    plane_mod.compare = compare_all


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    help="one of %s, or several with commas" % (
                        tuple(CONTROLS),))
    args, rest = ap.parse_known_args(argv)
    controls = args.control.split(",")
    if set(controls) - set(CONTROLS):
        ap.error(f"--control takes {tuple(CONTROLS)}")

    from benchmark import run
    from benchmark.lib import harness

    in_the_programs_place(
        harness.load_named("planes", "rollout_nemotron_h"), controls)
    return run.main(rest if "--trace" in rest else rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
