"""``run.py`` from the outside: it refuses a CPU, and each cell walks
through on the CPU at the rehearsal size without printing a metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [w["name"] for w in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def test_refuses_a_cpu_and_prints_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_an_unknown_workload():
    p = run("--workload", "no-such.cell", "--seed", "1")
    assert p.returncode == 3 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_walks_the_cell_and_prints_no_metric(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "0.5",
            "--trace", trace, "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
    assert "metrics" not in line and line["device"] == {"platform": "cpu"}
    assert line["checks"]["reference"]["ok"]
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    assert list(line)[-1] == "compared"
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    assert p.stderr.strip().splitlines()[-1].startswith(
        "compared kernels_off_their_tpu_path: 0 (limit 0)")
    # the two mixes that offer what the engine holds have it all admitted;
    # ``rollout-short`` offers 8 where the tiny pool admits 5, and the run
    # goes on with 3 waiting in the engine's queue
    c = line["checks"]
    assert c["offered"] == line["attempted"] and line["failed"] == 0
    if cell.endswith(".rollout-short"):
        assert (c["offered"], c["admitted"], c["queued"]) == (8, 5, 3)
    else:
        assert (c["admitted"], c["queued"]) == (c["offered"], 0)
