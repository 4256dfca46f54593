"""``BENCHMARK.json`` against the files it names."""

import json
import os
import re

from benchmark.lib import harness, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_files():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        cfg = harness.load_config(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        assert harness.load_named("references", cfg["reference"]).score
        assert set(cfg["correct"]) >= {"logprob_mean_abs_diff_max",
                                       "logprob_max_abs_diff_max"}
    cfgs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = traffic.load_mix(w["traffic"])
        assert harness.load_named("planes", mix["plane"]).run
        assert harness.load_named("patterns", mix["pattern"]).run
        for key in ("prompt_tokens", "answer_tokens"):
            assert traffic.quantile(mix[key], 0.5) > 0
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_every_cell_reports_what_its_layer_metrics_move():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]

    def where(m):
        return set(m.get("workloads", cells))

    e2e = {m["name"]: where(m) for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert harness.load_reader(m["name"])
        assert where(m) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        assert sum(cell in w for n, w in e2e.items() if n != "setup_s") >= 1
        assert any(cell in where(m) for m in b["per_layer"])
