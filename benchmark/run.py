#!/usr/bin/env python3
"""Run one cell of the benchmark in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``benchmark/configs``), traffic mix (``benchmark/traffic``)
with the plane and pattern it names (``benchmark/planes``,
``benchmark/patterns``) and per-layer metrics (``benchmark/layer_metrics``)
are files found by name, so a new cell is new files and one new entry,
and this file is not edited. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: each number ``correct`` holds to a limit, beside it.

Exits non-zero and prints no result without a TPU, with fewer chips than
the cell asks for, or with a device kind whose peaks are not in
``benchmark/lib/peaks.py``. ``--rehearse-cpu`` walks the same path at a
tiny size on the CPU and prints no metric at all.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROC0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)

    from benchmark.lib import harness, traffic

    try:
        cell, config, bench = harness.load_cell(args.workload)
        mix = traffic.load_mix(cell["traffic"])
        plane = harness.load_named("planes", mix["plane"])
        harness.load_named("patterns", mix["pattern"])
        if args.rehearse_cpu:
            os.environ["JAX_PLATFORMS"] = "cpu"
            config, mix = harness.rehearsal(config, mix)
        seconds = float(args.seconds if args.seconds is not None
                        else bench["run_seconds"])
        device = harness.Device(int(cell["chips"]), args.rehearse_cpu)
    except harness.Refused as exc:
        print(f"benchmark: refused: {exc}", file=sys.stderr)
        return 3
    harness.start_watchdog(1150.0)
    harness.configure_jax_cache()
    counter = harness.CompileCounter()
    harness.say(f"{cell['name']}: {device.platform} {device.kind} "
                f"x{device.count}, seed {args.seed}, {seconds:g}s, "
                f"trace {args.trace}")
    out = plane.run(cell, config, mix, device, args.seed, seconds,
                    bool(args.trace), counter, T_PROC0)

    out["correct"] = harness.verdict(out, args.rehearse_cpu)
    compared = harness.compared(out, config, args.rehearse_cpu)
    for name, row in compared.items():
        print(f"compared {name}: {row['value']:g} (limit {row['limit']:g})",
              file=sys.stderr, flush=True)
    observed = out.pop("observed")
    observed.update(config=config, mix=mix, peaks=device.peaks,
                    end_to_end=out["end_to_end"], checks=out["checks"])
    if args.rehearse_cpu:
        # the same readers run, so that a broken one shows here; their
        # numbers are of a CPU and are not printed
        for m in harness.cell_metrics(bench, cell["name"], "per_layer"):
            harness.load_reader(m["name"])(observed)
        print(json.dumps({"rehearsal": "passed" if out["correct"]
                          else "failed", "checks": out["checks"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "failures": out.get("failures", []),
                          "device": {"platform": device.platform},
                          "compared": compared}))
        return 0 if out["correct"] else 1

    metrics = {}
    if args.trace:
        for m in harness.cell_metrics(bench, cell["name"], "per_layer"):
            value = harness.load_reader(m["name"])(observed)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in harness.cell_metrics(bench, cell["name"], "end_to_end"):
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"], "checks": out["checks"],
            "failures": out.get("failures", [])}
    reduced = observed.get("trace")
    if args.trace and reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["compared"] = compared     # last: the end of the line is kept
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
