"""The two readers of the head that samples: ``head_sample_ms`` on a
hand-made decoded trace and on the scoped trace recorded on a TPU,
``fused_sample_share`` on a pair of ``server_info`` samples, and None
where the scope or the counter is absent (a parent without them).

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os

import pytest

from benchmark.lib import harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
OBS = {"mix": {"engine": {"steps_per_dispatch": 2}}}


def _trace(sampler_scope=True):
    """Two whole ``jit_step`` programs of 2 fused steps: the kernel and
    the norm under ``head`` (300 ns), three small operations under
    ``sample`` (30 ns), one container event, one operation of another
    program."""
    body = "jit(step)/while/body/closed_call/"
    ops = [("fusion.1", body + "head/rms_norm/mul", 1000.0, 20.0),
           ("head_sample.2", body + "head/jit(head_sample_pallas)/head_sample/"
            "pallas_call", 1020.0, 130.0),
           ("head_sample.2", body + "head/jit(head_sample_pallas)/head_sample/"
            "pallas_call", 3020.0, 150.0),
           ("while.3", body + "head/while", 1000.0, 900.0),
           ("fusion.4", body + "mlp/dot_general", 1200.0, 500.0),
           ("fusion.9", "jit(prefill_one)/head/dot_general", 9000.0, 70.0)]
    if sampler_scope:
        ops += [("fusion.5", body + "sample/threefry2x32", 990.0, 10.0),
                ("fusion.6", body + "sample/select_n", 1160.0, 12.0),
                ("fusion.6", body + "sample/select_n", 3180.0, 8.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_one(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


def test_head_sample_ms_sums_both_scopes_over_the_fused_steps(monkeypatch):
    read = harness.load_reader("head_sample_ms")
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    assert read(OBS) == pytest.approx(1e3 * (300e-9 + 30e-9) / 4)
    # a sampler wholly inside the kernel: ``head`` alone
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace(False))
    assert read(OBS) == pytest.approx(1e3 * 300e-9 / 4)


def test_head_sample_ms_on_the_trace_recorded_on_a_tpu(monkeypatch):
    """That program has ``attn_core`` and ``mlp`` and no ``head``; and a
    rehearsal has no xplane at all."""
    read = harness.load_reader("head_sample_ms")
    monkeypatch.setattr(xspans, "load",
                        lambda path=None, _load=xspans.load: _load(SCOPED))
    assert read(OBS) is None
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    assert read(OBS) is None


@pytest.mark.parametrize("samples,want", [
    # every step of the window sampled in the head; none; half
    ([{"decode_steps_done": 80, "fused_sample_steps": 80},
      {"decode_steps_done": 880, "fused_sample_steps": 880}], 100.0),
    ([{"decode_steps_done": 80, "fused_sample_steps": 0},
      {"decode_steps_done": 880, "fused_sample_steps": 0}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_steps_done": 80, "fused_sample_steps": 16},
      {"decode_steps_done": 880, "fused_sample_steps": 416}], 50.0),
    # a parent's engine has no such counter; no step landed
    ([{"decode_steps_done": 80}, {"decode_steps_done": 880}], None),
    ([{"decode_steps_done": 80, "fused_sample_steps": 80},
      {"decode_steps_done": 80, "fused_sample_steps": 80}], None),
])
def test_fused_sample_share_of_a_server_info_pair(samples, want):
    got = harness.load_reader("fused_sample_share")({"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))
