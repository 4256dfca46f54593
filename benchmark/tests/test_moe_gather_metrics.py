"""The reader of the counter of the experts' kernels that take their rows
by table: ``moe_gather_kernel_share`` on a pair of ``server_info``
samples, and None where the counter is absent (a parent without it) or no
step landed.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import os

import pytest

from benchmark.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("samples,want", [
    # every step of the window took its rows by table; none; a part
    ([{"decode_steps_done": 80, "moe_gather_kernel_steps": 80},
      {"decode_steps_done": 880, "moe_gather_kernel_steps": 880}], 100.0),
    ([{"decode_steps_done": 80, "moe_gather_kernel_steps": 0},
      {"decode_steps_done": 880, "moe_gather_kernel_steps": 0}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_steps_done": 80, "moe_gather_kernel_steps": 16},
      {"decode_steps_done": 880, "moe_gather_kernel_steps": 216}], 25.0),
    # a parent's engine has no such counter; no step landed
    ([{"decode_steps_done": 80}, {"decode_steps_done": 880}], None),
    ([{"decode_steps_done": 80, "moe_gather_kernel_steps": 80},
      {"decode_steps_done": 80, "moe_gather_kernel_steps": 80}], None),
])
def test_moe_gather_kernel_share_of_a_server_info_pair(samples, want):
    got = harness.load_reader("moe_gather_kernel_share")(
        {"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))


def test_the_metric_is_declared_for_the_five_routed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "moe_gather_kernel_share"]
    routed = [w["name"] for w in bench["workloads"] if any(
        m["name"] == "moe_experts_ms" and w["name"] in m["workloads"]
        for m in bench["per_layer"])]
    assert entry == {
        "name": "moe_gather_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "forward pass and kernels",
        "moves": "rollout_tok_s", "workloads": routed}
    assert bench["per_layer"][-1] == entry      # appended, nothing moved
