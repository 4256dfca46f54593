"""The reader of the KDA state-update kernel's counter:
``kda_kernel_share`` on a pair of ``server_info`` samples, and None where
the counter is absent (a parent without it) or no step landed.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import os

import pytest

from benchmark.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("samples,want", [
    # every step of the window took the kernel; none; a part
    ([{"decode_steps_done": 80, "kda_kernel_steps": 80},
      {"decode_steps_done": 880, "kda_kernel_steps": 880}], 100.0),
    ([{"decode_steps_done": 80, "kda_kernel_steps": 0},
      {"decode_steps_done": 880, "kda_kernel_steps": 0}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_steps_done": 80, "kda_kernel_steps": 16},
      {"decode_steps_done": 880, "kda_kernel_steps": 216}], 25.0),
    # a parent's engine has no such counter; no step landed
    ([{"decode_steps_done": 80}, {"decode_steps_done": 880}], None),
    ([{"decode_steps_done": 80, "kda_kernel_steps": 80},
      {"decode_steps_done": 80, "kda_kernel_steps": 80}], None),
])
def test_kda_kernel_share_of_a_server_info_pair(samples, want):
    got = harness.load_reader("kda_kernel_share")({"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))


def test_the_metric_is_declared_for_the_hybrid_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "kda_kernel_share"]
    assert entry == {
        "name": "kda_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "forward pass and kernels",
        "moves": "rollout_tok_s",
        "workloads": ["ling-3.0-flash.rollout-long-wide"]}
