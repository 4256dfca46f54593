"""The reader of the counter of the kernel that chooses a sparse layer's
blocks: ``sparse_select_kernel_share`` on a pair of ``server_info``
samples, and None where the counter is absent (a parent without it) or no
step landed; its declaration, appended for MiniCPM-SALA's cell alone.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import os

import pytest

from benchmark.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("samples,want", [
    # every step of the window chose in the kernel; none; a part
    ([{"decode_steps_done": 80, "sparse_kernel_steps": 80},
      {"decode_steps_done": 880, "sparse_kernel_steps": 880}], 100.0),
    ([{"decode_steps_done": 80, "sparse_kernel_steps": 0},
      {"decode_steps_done": 880, "sparse_kernel_steps": 0}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_steps_done": 80, "sparse_kernel_steps": 16},
      {"decode_steps_done": 880, "sparse_kernel_steps": 216}], 25.0),
    # a parent's engine has no such counter; no step landed
    ([{"decode_steps_done": 80}, {"decode_steps_done": 880}], None),
    ([{"decode_steps_done": 80, "sparse_kernel_steps": 80},
      {"decode_steps_done": 80, "sparse_kernel_steps": 80}], None),
])
def test_sparse_select_kernel_share_of_a_server_info_pair(samples, want):
    got = harness.load_reader("sparse_select_kernel_share")(
        {"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))


def test_the_metric_is_declared_for_the_sparse_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "sparse_select_kernel_share"]
    assert entry == {
        "name": "sparse_select_kernel_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "forward pass and kernels", "moves": "rollout_tok_s",
        "workloads": ["minicpm-sala.rollout-long-sparse-linear"]}
    # appended after what the benchmark had: nothing moved
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(entry["name"]) > names.index("lightning_kernel_share")


def test_the_tools_know_the_counter():
    """``tools/engine_report.py`` and ``tools/check_metric_names.py`` read
    the profiler's one declaration of the cumulative keys."""
    from polyrl_tpu.obs import engine_profile, statusz

    assert "sparse_kernel_steps" in engine_profile.CUMULATIVE_KEYS
    assert "sparse_kernel_steps" in statusz.CUMULATIVE_INFO_KEYS
