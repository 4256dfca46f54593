#!/usr/bin/env python3
"""The controls of ``correct`` for MiniCPM-SALA's cell, at the cell's own
size, on the chip (run by hand through the chip tool; the benchmark's own
runs never run it):

    python3 benchmark/tests/control_sala_on_chip.py \\
        --control <state_bf16|no_decay|first_blocks|pooled_unwritten|low>[,...] \\
        --workload minicpm-sala.rollout-long-sparse-linear \\
        --seed <n> --seconds 20 [--trace 1]
    python3 benchmark/tests/control_sala_on_chip.py \\
        --fault <first_pages|no_head_offset> --workload ... --seed <n> \\
        --seconds 20

(At the cell's own 20 seconds: in a shorter window a request has fewer
than ``correct_positions`` tokens and nothing is compared.) One whole run
of the cell through ``run.py``, the program as it is; beside its own
comparison, the reference computed with the control
(``references/sala_sparse_linear.py``: ``control=``) is put in the
program's place (``checks.reference.controls``: its log-probabilities, its
first lightning state, its pooled keys as read and its chosen blocks
against the sound reference's), and the run's verdict is then the
controls'. ``correct`` has to come out false, each control by the limit
that watches its part:

- ``state_bf16``: every lightning state rounded to bfloat16's mantissa
  after each token (``jax.lax.reduce_precision``), the nearest precision
  below the float32 the configuration states for it: ``state_rel_diff``;
- ``no_decay``: lambda = 1 in every lightning layer: ``state_rel_diff``,
  by far;
- ``first_blocks``: the first 64 blocks in place of the best 64:
  ``selected_set_diff`` (the log-probabilities hardly move: with random
  weights attention is near uniform);
- ``pooled_unwritten``: the pooled keys of ONE page of the sequence read as
  the zeros of a store never written: ``pooled_rel_diff``;
- ``low``: every matmul weight and the head int8 with one scale an output
  channel, one precision below the bfloat16 the configuration states:
  ``logprob_mean_abs_diff``.

``--fault`` plants a fault in the PROGRAM instead (``plant``: the decode
step's own table of chosen pages, ``mixers/sparse.py::selected_table``,
altered before the engine compiles it), the reference and the comparison
as they are: ``plane.compare`` itself has to say false, by
``selected_set_diff``, which reads the table the timed step left in the
slot:

- ``first_pages``: a (row, K/V head)'s first pages and its own in place of
  the chosen ones, the count and the keys as they were (the
  log-probabilities hardly move);
- ``no_head_offset``: every head's table without its ``g * N`` offset, so
  that head 1's queries attend head 0's pages.

``benchmark/tests/test_sala_metrics.py`` runs both on the CPU at the tiny
size. PERF.md section 4 gives the readings beside the limits."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NUMBERS = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "state_rel_diff",
           "selected_set_diff", "pooled_rel_diff")
CONTROLS = ("state_bf16", "no_decay", "first_blocks", "pooled_unwritten",
            "low")
FAULTS = ("first_pages", "no_head_offset")


def plant(fault: str):
    """Alters ``mixers/sparse.py::selected_table`` as ``fault`` says, for
    every decode step compiled from now on; returns what undoes it."""
    import jax.numpy as jnp

    from polyrl_tpu.models.mixers import sparse

    real = sparse.selected_table

    def faulty(cfg, q, c_pool, ctx, n_pages):
        table, lens, count = real(cfg, q, c_pool, ctx, n_pages)
        s, hkv = count.shape
        w = table.shape[1]
        at = jnp.arange(w, dtype=jnp.int32)
        offset = jnp.arange(hkv, dtype=jnp.int32)[None, :, None] * n_pages
        table = table.reshape(s, hkv, w)
        if fault == "no_head_offset":
            table = table - offset
        else:
            own = ((ctx.attn_lens - 1) // cfg.sparse_block_size)[:, None, None]
            blocks = jnp.where(at < count[..., None] - 1, at, own)
            pages = jnp.take_along_axis(
                jnp.broadcast_to(ctx.page_table[:, None, :],
                                 (s, hkv, ctx.page_table.shape[1])),
                jnp.maximum(blocks, 0), axis=2) + offset
            table = jnp.where(at < count[..., None], pages, 0)
        return table.reshape(s * hkv, w), lens, count

    sparse.selected_table = faulty

    def undo():
        sparse.selected_table = real

    return undo


def readings(plane_mod, traces, walked) -> dict:
    """A control's numbers: its reference's walk ``traces`` in the
    program's place against the sound walk ``walked``."""
    import numpy as np

    rel = plane_mod.hybrid.rel
    pairs = list(zip(traces, walked))
    diffs = [np.abs(a["logprobs"] - b["logprobs"]) for a, b in pairs]
    return {
        "logprob_mean_abs_diff": float(np.mean(np.concatenate(diffs))),
        "logprob_max_abs_diff": float(max(d.max() for d in diffs)),
        "state_rel_diff": float(np.mean(
            [rel(a["state"], b["state"]) for a, b in pairs])),
        "pooled_rel_diff": float(np.mean(
            [rel(a["pooled"], b["pooled"]) for a, b in pairs])),
        "selected_set_diff": float(np.mean(
            [plane_mod.set_diff(a["chosen"], b["chosen"])
             for a, b in pairs]))}


def in_the_programs_place(plane_mod, controls) -> None:
    """``controls``: one name or several (one run of the cell then serves
    them all: the reference walks once more for each, and says what it
    read as soon as it has)."""
    walk, compare = plane_mod.walk, plane_mod.compare
    low: dict = {}

    def walk_all(reference, cfg, params, c, samples, held, _control=""):
        sound = walk(reference, cfg, params, c, samples, held)
        for name in controls:
            # all but ``low`` alter the first sparse or the first lightning
            # layer alone (the plan's first two): their walks stop there,
            # and their log-probabilities are the sound walk's
            upto = None if name == "low" else 2
            low[name] = walk(reference, cfg, params, c, samples, held, name,
                             upto)
            if upto:
                for got, whole in zip(low[name], sound):
                    got["logprobs"] = whole["logprobs"]
            plane_mod.harness.say(f"control {name}: " + ", ".join(
                f"{k} {v:.4g}" for k, v in readings(
                    plane_mod, low[name], sound).items()))
        return sound

    def compare_all(limits, c, samples, held, walked):
        out = compare(limits, c, samples, held, walked)
        out["sound"] = {k: out[k] for k in ("ok", *NUMBERS)}
        out["controls"] = {}
        for name, traces in low.items():
            got = readings(plane_mod, traces, walked)
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
    ap.add_argument("--control", default="",
                    help="one of %s, or several with commas" % (CONTROLS,))
    ap.add_argument("--fault", default="", choices=("",) + FAULTS)
    args, rest = ap.parse_known_args(argv)
    controls = args.control.split(",") if args.control else []
    if set(controls) - set(CONTROLS) or bool(controls) == bool(args.fault):
        ap.error(f"--control takes {CONTROLS}, or --fault one of {FAULTS}")

    from benchmark import run
    from benchmark.lib import harness

    if args.fault:
        plant(args.fault)
    else:
        in_the_programs_place(harness.load_named("planes", "rollout_sala"),
                              controls)
    return run.main(rest if "--trace" in rest else rest + ["--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
